"""The trace reduction: hand-made events with known answers, and a slice
of a device trace recorded on a TPU v5e (``data/``)."""
import json
from pathlib import Path

import pytest

from perf import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data"
OPS, MODS = rt.OP_LINE, rt.MODULE_LINE


def _events():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    return {"device": [
        (d0, OPS, "fusion.1", 0.0, 10.0),
        (d0, OPS, "fusion.2", 5.0, 10.0),          # overlaps: union 0..15
        (d0, OPS, "paged_attention", 40.0, 20.0),   # 40..60
        (d0, MODS, "jit_step(7)", 0.0, 15.0),
        (d0, MODS, "jit_step(7)", 40.0, 20.0),
        (d1, OPS, "fusion.1", 0.0, 100.0),          # busy throughout
    ], "host": [
        ("python", "outer", 0.0, 1000.0),
        ("python", "PjitFunction(step)", 16.0, 20.0),
        ("python", "short", 70.0, 1.0),
    ]}


def test_busy_union_idle_and_gaps():
    r = rt.reduce(_events(), 0.0, 100.0)
    # device 0 busy 15 + 20 = 35 ns, device 1 busy 100 ns: mean 67.5
    assert r["busy_s"] == pytest.approx(67.5e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.325)
    assert r["devices"] == 2
    assert r["ops"]["fusion"] == pytest.approx(120e-9)
    assert r["ops"]["paged_attention"] == pytest.approx(20e-9)
    assert r["programs"]["jit_step"] == {"seconds": pytest.approx(35e-9),
                                         "launches": 2}
    names = [g[0] for g in r["idle_gaps"]]
    secs = [g[1] for g in r["idle_gaps"]]
    assert secs == pytest.approx([40e-9, 25e-9])       # 60..100, 15..40
    # each gap is named by the innermost host event covering most of it
    assert names == ["outer", "PjitFunction(step)"]


def test_marked_window():
    ev = _events()
    ev["host"] += [("python", rt.MARK, 12.0, 1.0), ("python", rt.MARK, 80.0,
                                                     1.0)]
    assert rt.marked_window(ev) == (12.0, 80.0)
    with pytest.raises(ValueError):
        rt.marked_window(_events())


def test_clipping_to_the_window():
    r = rt.reduce(_events(), 10.0, 50.0)
    assert r["window_s"] == pytest.approx(40e-9)
    # device 0: 10..15 and 40..50 = 15; device 1: 40
    assert r["busy_s"] == pytest.approx(27.5e-9)


def test_no_device_events():
    r = rt.reduce({"device": [], "host": []}, 0.0, 1e9)
    assert r["devices"] == 0 and r["idle_share"] == 1.0


def test_fold():
    assert rt.fold("jit_step(123)") == "jit_step"
    assert rt.fold("fusion.12") == "fusion"
    assert rt.fold("paged_attention") == "paged_attention"


def _recorded():
    return json.loads((DATA / "v5e_dsc33b_decode_slice.json").read_text())


def test_recorded_v5e_slice_matches_a_brute_force_union():
    """A slice of a decode step of ``dsc33b.codegen.closed32`` traced on a
    TPU v5e: the ops' union, counted on a 100 ns grid, agrees with the
    reduction's interval arithmetic."""
    import numpy as np
    ev = _recorded()
    ops = [e for e in ev["device"] if e[1] == OPS]
    t0 = min(e[3] for e in ops)
    t1 = max(e[3] + e[4] for e in ops)
    r = rt.reduce(ev, t0, t1)
    grid = np.zeros(int((t1 - t0) / 100) + 1, bool)
    for _, _, _, s, d in ops:
        grid[int((s - t0) / 100):int((s + d - t0) / 100)] = True
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(grid.sum() * 100e-9, rel=1e-3)
    assert 0.0 < r["idle_share"] < 1.0
    # the decode step is one jitted program holding the layer scan, and
    # the kernel runs inside it once per layer
    assert r["programs"]["jit_step"]["launches"] == 1
    assert r["ops"]["while"] > r["ops"]["paged_attention"] > 0
    assert r["ops"]["paged_attention"] == pytest.approx(sum(
        min(s + d, t1) - max(s, t0) for _, line, n, s, d in ops
        if n.startswith("%paged_attention.")) * 1e-9)
    names = [g[0] for g in r["idle_gaps"]]
    assert "np.asarray(jax.Array)" in names
    assert [g[1] for g in r["idle_gaps"]] == sorted(
        [g[1] for g in r["idle_gaps"]], reverse=True)
