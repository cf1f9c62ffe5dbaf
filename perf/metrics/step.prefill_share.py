"""Device time in the cold-prefill and pool-scatter programs over device
busy time, in the traced window (%)."""
PROGRAMS = ("jit_prefill", "jit_scatter")


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    t = sum(run.trace["programs"].get(p, {}).get("seconds", 0.0)
            for p in PROGRAMS)
    return 100.0 * t / run.trace["busy_s"]
