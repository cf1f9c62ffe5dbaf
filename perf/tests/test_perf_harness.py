"""The benchmark's run, driven on the CPU at toy size: the harness's look
for a chip is skipped, everything else runs as on the chip. A sound run
is correct and compiles nothing inside its window; each fault planted
under the timed path, and the fp8 control, come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from perf import control, faults, harness
from perf.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _run(traffic=None, fault=None, trace=False, seed=2**33 + 5, bench=None):
    logs = []
    out = harness.run(bench or tiny.bench(), tiny.CELL, tiny.SPEC,
                      traffic or tiny.traffic(), seed=seed, seconds=2.0,
                      trace=trace, t_proc=time.monotonic(),
                      require_tpu=False, kind="TPU v5 lite", fault=fault,
                      log=logs.append)
    return out, logs


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    out, _ = sound
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 20
    m = out["metrics"]
    assert set(m) == {"ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
                      "tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert list(out)[-1] == "checks"
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["device"]["count"] >= 1


def test_nothing_compiles_inside_the_window(sound):
    _, logs = sound
    line = next(m for m in logs if "compiles inside the window" in m)
    counts = json.loads(line.split("window", 1)[1])
    assert counts and all(v == 0 for v in counts.values()), line


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_fault_under_the_timed_path_is_not_correct(fault):
    out, _ = _run(fault=fault)
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_traced_open_loop_run_reads_the_counters():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    traffic = tiny.traffic(loop="open", rate_per_s=60.0, arrival_block=20)
    out, _ = _run(traffic=traffic, trace=True, bench=bench)
    m = out["metrics"]
    read = {harness.by_name(k, HOST_READ) for k in m} - {None}
    assert read == HOST_READ
    assert all(v["value"] > 0 for v in m.values())
    # no device trace on the CPU: the device metrics stay silent
    assert not any("roofline" in k or k.startswith("step.") for k in m)
    assert "busy_s" in out["device"] and "breakdown" in out
    assert out["correct"] is True


# what a traced run reads from host clocks and the program's counters
HOST_READ = {"client.gen_lag_p95_ms", "engine.batch_occupancy",
             "kv.pool_peak_share", "mfu", "runtime.complete_to_run_mean_us"}


def test_fp8_control_fails_the_limit_the_program_meets():
    rows = control.readings(tiny.CELL, tiny.SPEC, tiny.traffic(),
                            [11, 12, 13], 1.5, require_tpu=False,
                            kind="TPU v5 lite", log=lambda m: None)
    limit = tiny.TRAFFIC["check"]["max_gap"]
    assert max(r["served"] for r in rows) <= limit
    assert min(r["control"] for r in rows) > limit


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    return subprocess.run(
        [sys.executable, "perf/run.py", "--workload",
         cell["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    last = proc.stdout.strip().splitlines()[-1:] or [""]
    return proc.returncode != 0 and not last[0].startswith("{")


def test_no_chip_no_result():
    proc = _command(ROOT)
    assert _no_result(proc), proc.stdout + proc.stderr
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perf", tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_command(tmp_path))


def test_router_cell_from_files_alone():
    """A cell whose mix states two replicas and shared prefixes runs
    behind the program's Router with no code of its own: warm-up per
    replica covers every prompt and suffix, nothing compiles inside the
    window, requests with one system prompt go to one replica, and the
    served tokens pass the reference."""
    from perf import harness as h
    sv = h.Served(tiny.CELL, tiny.SPEC, tiny.ROUTER, 7, require_tpu=False,
                  kind="TPU v5 lite")
    w = sv.window(7, 2.0)
    seen = dict(sv.compiles.seen)
    router = sv.client.serve
    sv.close()
    assert all(v == 0 for v in seen.values()), seen
    assert len(sv.engines) == 2
    assert router.stats["routed"] > 8 and router.stats["affinity_hits"] > 0
    assert w["stats1"]["suffix_steps"] > w["stats0"]["suffix_steps"]
    gap = h.max_gap(tiny.SPEC, tiny.ROUTER, sv.params, w["records"], 7,
                    log=lambda m: None)
    assert gap <= tiny.ROUTER["check"]["max_gap"]
