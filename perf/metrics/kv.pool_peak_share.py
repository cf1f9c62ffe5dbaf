"""Most pages the KV pool held at once, over its pages (%)."""


def read(run):
    return 100.0 * run.pool["peak_in_use"] / run.pool["total_pages"]
