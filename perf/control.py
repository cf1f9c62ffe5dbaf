#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, in one process:

    python3 perf/control.py --workload <cell> [--seeds 12] [--seconds 20]
                            [--first-seed 1000]

For each seed: new weights from it, a window of the cell's own traffic
at its own load, and then, over the same sample of finished requests
that a run compares, two numbers against the float32 reference: the
program's widest served-token gap (its lower reading is the largest over
the seeds) and the fp8 control's (the configuration's architecture's
``Reference``, ``perf/arch/<arch>.py``, with every matrix rounded to
float8 e4m3, put in the program's place; its upper reading is the
smallest over the seeds). One JSON line per seed, then a summary.
The benchmark's own runs do not run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, spec, traffic, seeds, seconds, *, require_tpu=True,
             kind=None, log=print):
    """Per seed ``{"seed", "served", "control"}``; the engine and its
    compiled programs are built once."""
    from perf import harness
    sv = harness.Served(cell, spec, traffic, seeds[0],
                        require_tpu=require_tpu, kind=kind)
    out = []
    for i, seed in enumerate(seeds):
        if i:
            sv.reseed(seed)
        t0 = time.monotonic()
        w = sv.window(seed, seconds)
        sv.free_pool()
        t1 = time.monotonic()
        served, control = harness.max_gap(spec, traffic, sv.params,
                                          w["records"], seed, control=True,
                                          log=log)
        row = {"seed": seed, "served": served, "control": control,
               "window_s": t1 - t0, "reference_s": time.monotonic() - t1}
        log(json.dumps(row))
        out.append(row)
    sv.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="correctness readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from perf import generate, model
    from repro.compile_cache import configure
    configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = readings(cell, model.load(cell["config"]),
                    generate.load(cell["traffic"]), seeds, args.seconds,
                    log=lambda m: print(m, flush=True))
    print(json.dumps({"lower": max(r["served"] for r in rows),
                      "upper": min(r["control"] for r in rows),
                      "served": [r["served"] for r in rows],
                      "control": [r["control"] for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
