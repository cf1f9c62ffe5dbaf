#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest Poisson
rate the system sustains, by bisection over the rate on one warm
``harness.Served``.

    python3 perf/sweep.py --workload <cell> --lo <rate> --hi <rate>

A probe at rate ``r`` sends the cell's mix at ``r`` requests per second
for its ramp and a 30-s window. The rate is sustained when the 95th
percentile of time to first token stays under 1 s (a queue that grows
through the window pushes it past that) and the tokens delivered in the
window reach 90% of the tokens offered in it (the window's edges and
the mix's long requests move less than that at light load). Six
bisection steps follow the two ends. The cell's file then takes four
fifths of the knee (written by hand, once). Bisection as in the
program's ``bench/sweep.saturation_sweep``.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SECONDS = 30.0
ITERS = 6
KEEP = 0.90
TTFT_LIMIT_MS = 1000.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="knee of an open-loop cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lo", type=float, required=True)
    ap.add_argument("--hi", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from perf import drive, generate, harness, model
    from repro.compile_cache import configure
    configure()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == args.workload)
    spec, traffic = model.load(cell["config"]), generate.load(cell["traffic"])
    if traffic["loop"] != "open":
        raise SystemExit("the knee is for open-loop cells")
    sv = harness.Served(cell, spec, traffic, args.seed)
    mean_out = generate.mean_request(traffic)["output_len"]

    def probe(rate: float) -> bool:
        w = sv.window(args.seed, SECONDS,
                      traffic=dict(traffic, rate_per_s=rate))
        m = drive.end_to_end(drive.window_metrics(w["records"], w["ws"],
                                                  w["we"]))
        offered = rate * mean_out
        ok = (m["tokens_per_s"] >= KEEP * offered
              and m["ttft_p95_ms"] <= TTFT_LIMIT_MS)
        print(json.dumps({"rate": rate, "ok": ok, "offered_tokens_per_s":
                          offered, **m}), flush=True)
        return ok

    good, bad = None, None
    if probe(args.lo):
        good = args.lo
        if probe(args.hi):
            good = args.hi
        else:
            bad = args.hi
            for _ in range(ITERS):
                mid = (good + bad) / 2
                if probe(mid):
                    good = mid
                else:
                    bad = mid
    sv.close()
    print(json.dumps({"knee_per_s": good, "first_failing_per_s": bad}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
