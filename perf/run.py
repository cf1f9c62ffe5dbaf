#!/usr/bin/env python3
"""One run of one benchmark cell, from the root of a checkout::

    python3 perf/run.py --workload <cell> --seed <n>
                        --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
through ``BENCHMARK.json``. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared beside its limit, which also end standard error). Without a TPU,
with fewer chips than the cell needs, with a device kind missing from
``perf/peaks.json``, or without the program under ``src/``, it exits
non-zero and prints no result.
"""
import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"perf: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"perf: no cell {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    from perf import generate, harness, model, work
    from repro.compile_cache import configure
    import jax
    configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run(bench, cell, model.load(cell["config"]),
                          generate.load(cell["traffic"]), seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_proc=T_PROC)
    except (harness.NoChip, work.UnknownDevice) as exc:
        print(f"perf: {exc}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"perf check: {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
