"""Model FLOPs served in the window over the window times the chips'
bf16 peak (%): each prompt whose first token came in the window, and
each decode token delivered in it, at published head counts and live
attention length (``perf/work.py``)."""


def read(run):
    flops = 0
    for r, i in run.tokens_in(run.ws, run.we):
        plen = len(r.req.prompt)
        flops += run.work.prompt_flops(run.spec, plen) if i == 0 \
            else run.work.decode_flops(run.spec, plen + i - 1)
    peak = run.peak["bf16_flops_per_s"] * run.chips * (run.we - run.ws)
    return 100.0 * flops / peak if flops else None
