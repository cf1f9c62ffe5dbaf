"""The paged-attention kernel's share of its roofline in the traced
window (%): the least time for the work its decode tokens need (live
k/v read, new row written, q read, o written; ``perf/work.py``), over the
device time of the events named ``paged_attention``."""
KERNEL = "paged_attention"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace["ops"].get(KERNEL, 0.0)
    flops = nbytes = 0
    for r, i in run.tokens_in(run.ta, run.tb):
        if i >= 1:
            f, b = run.work.paged_attention_work(
                run.spec, len(r.req.prompt) + i - 1)
            flops += f
            nbytes += b
    if not seconds or not nbytes:
        return None
    return run.work.roofline_share(flops, nbytes, seconds, run.peak)
