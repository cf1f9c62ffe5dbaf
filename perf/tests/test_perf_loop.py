"""The serve loop's record beside the device trace (``perf/loop_trace.py``)
on hand-made events with known answers, on the slice of a device trace
recorded on a TPU v5e (``data/``) and on a real CPU profiler session;
and the loop's readers (``perf/metrics/engine.*``) on a tiny traced
window."""
import json
import types
from pathlib import Path

import pytest

from perf import harness
from perf import loop_trace as lt
from perf import reduce_trace as rt
from perf.tests import tiny

DATA = Path(__file__).resolve().parent / "data"
OPS, MODS = rt.OP_LINE, rt.MODULE_LINE


def _recorded():
    return json.loads((DATA / "v5e_dsc33b_decode_slice.json").read_text())


# ------------------------------------------------- the serve loop's view
def _obs_event(kind, ts, dur=0.0, rid=-1, meta=None, tid=1):
    from repro.obs import Event
    return Event(ts, dur, kind, rid, "engine", meta, tid)


# the hand-made loop's unit of time: 1 ms, in trace ns
U = 1e6


def _loop_case():
    """One device busy over [0, 100) and [300, 400) ms of a [0, 1000)
    window, both ``jit_step`` launches; the loop's spans on an obs clock
    whose 5.0 s lies at 10 ms of the trace's."""
    def obs_ts(t):
        return 5.0 + (t - 10.0) * U * 1e-9

    d0 = "/device:TPU:0"
    events = {"device": [
        (d0, OPS, "fusion.1", 0.0, 100 * U),
        (d0, OPS, "fusion.1", 300 * U, 100 * U),
        (d0, MODS, "jit_step(1)", 0.0, 100 * U),
        (d0, MODS, "jit_step(1)", 300 * U, 100 * U),
    ], "host": [("python", lt.ANCHOR, 10 * U, 1.0),
                ("python", lt.ANCHOR, 900 * U, 1.0)]}

    def span(kind, a, b, **kw):
        return _obs_event(kind, obs_ts(a), (b - a) * U * 1e-9, **kw)

    loop = [
        # the first launch's dispatch, before the window; its commit
        # holds the host for no time
        span("serve.dispatch", -20.0, -5.0, meta=(6, "jit_step")),
        span("serve.commit", 100.0, 100.0, meta=6),
        span("serve.admit", 100.0, 150.0),
        span("serve.dispatch", 150.0, 200.0, meta=(7, "jit_step")),
        _obs_event("cont.posted", obs_ts(190.0), rid=42),
        span("serve.progress", 420.0, 600.0),
        _obs_event("cont.ready", obs_ts(430.0), rid=42),
        _obs_event("cont.ran", obs_ts(440.0), rid=42),
        span("serve.commit", 450.0, 500.0, meta=7),
    ]
    anchors = [obs_ts(10.0), obs_ts(900.0)]
    return events, anchors, loop


def test_paint_keeps_the_innermost_span():
    spans = [(0, 10, "progress"), (2, 4, "commit"), (3, 5, "admit"),
             (20, 30, "dispatch")]
    assert lt.paint(spans) == [(0, 2, "progress"), (2, 3, "commit"),
                               (3, 5, "admit"), (5, 10, "progress"),
                               (20, 30, "dispatch")]
    assert lt.split_by([(1, 6), (8, 25)], lt.paint(spans)) == {
        "progress": 1 + 1 + 2, "commit": 1, "admit": 2,
        "dispatch": 5, lt.OUTSIDE: 10}


def _steps(*dispatches):
    return [(seq, t, None) for seq, t in dispatches]


def test_pair_launches_in_dispatch_order():
    launches = [(5.0, 9.0), (12.0, 20.0), (21.0, 30.0)]
    steps = _steps((1, 4.0), (2, 6.0), (3, 7.0), (4, 25.0))
    commits = {1: 10.0, 2: 20.5, 3: 31.0, 4: 45.0}
    assert lt.pair_launches(steps, launches, commits, slack=0.0) == [
        (steps[0], 5.0, 9.0), (steps[1], 12.0, 20.0), (steps[2], 21.0, 30.0)]
    # a record that starts later: the first launch has no dispatch left
    assert lt.pair_launches(steps[1:], launches, commits, slack=0.0) is None
    assert lt.pair_launches(steps, [], commits) == []


def test_pair_launches_allows_an_early_device_clock():
    """On a v5e trace the device's clock may read a launch's start
    before the host call that made it: within the slack, the launch is
    still that dispatch's."""
    ms = 1e6
    launches = [(0.0, 130 * ms), (166 * ms, 296 * ms), (331 * ms, 461 * ms)]
    steps = _steps((0, -3 * ms), (1, 167.2 * ms), (2, 332.0 * ms))
    commits = {0: 150 * ms, 1: 316 * ms, 2: 480 * ms}
    assert [(p[0][0], p[1]) for p in lt.pair_launches(
        steps, launches, commits)] == [(0, 0.0), (1, 166 * ms), (2, 331 * ms)]
    assert lt.pair_launches(steps, launches, commits, slack=0.0) is None


def _short_poll_lag(lag, stall_at=None):
    """Decode steps of 140 ms, two in flight: step k is dispatched as
    step k-2 commits and launches 0.5 ms later or when step k-1 ends,
    and its commit begins ``lag`` ms after it ends, so a dispatch comes
    ``lag`` after the launch before it started. At ``stall_at`` the host
    holds the commit 300 ms more."""
    ms = 1e6
    steps, commits, launches = [], {}, []
    end = 0.0
    for k in range(12):
        began = commits[k - 2] if k >= 2 else (k - 2) * ms
        start = max(end, began + 0.5 * ms)
        end = start + 140 * ms
        hold = 300 * ms if k == stall_at else 0.0
        commits[k] = max(end + lag * ms + hold, commits.get(k - 1, 0.0))
        steps.append((k, began, None))
        launches.append((start, end))
    return steps, commits, launches


def test_pair_launches_under_a_poll_lag_shorter_than_the_slack():
    """Each dispatch comes 1 ms after the launch before it started, well
    inside the slack: the launch before the window belongs to its own
    dispatch, not to the first in the window, and a shifted pairing
    (which would read every step one step too late) is refused."""
    steps, commits, launches = _short_poll_lag(1.0)
    pairs = lt.pair_launches(steps, launches[3:9], commits)
    assert [(p[0][0], p[1]) for p in pairs] == [
        (k, launches[k][0]) for k in range(3, 9)]
    for (seq, _t, _key), _start, end in pairs:
        assert 0 < commits[seq] - end == pytest.approx(1e6)


def test_pair_launches_across_a_host_stall():
    """A stall holds a commit past the next launch's end, so the right
    pairing has one launch end before the commit ahead of it began; the
    pairing one step late then fails at the dispatch after the stall."""
    steps, commits, launches = _short_poll_lag(1.0, stall_at=5)
    pairs = lt.pair_launches(steps, launches[3:9], commits)
    assert [p[0][0] for p in pairs] == list(range(3, 9))


def test_loop_view_on_the_window_keeps_in_window_dispatches():
    """The launch that started before the window's first dispatch is
    taken by its own dispatch, before the window, which the view then
    leaves out."""
    steps, commits, launches = _short_poll_lag(1.0)
    obs_events = [_obs_event("serve.dispatch", t * 1e-9, 1e-4,
                             meta=(seq, "jit_step"))
                  for seq, t, _ in steps]
    obs_events += [_obs_event("serve.commit", c * 1e-9, 1e-5, meta=seq)
                   for seq, c in commits.items()]
    t0, t1 = launches[4][0] - 0.5e6, launches[9][1] - 0.5e6
    events = {"device": [("/device:TPU:0", MODS, "jit_step(1)", a, b - a)
                         for a, b in launches],
              "host": [("python", lt.ANCHOR, t0, 1.0),
                       ("python", lt.ANCHOR, t1, 1.0)]}
    v = lt.loop_view(events, [t0 * 1e-9, t1 * 1e-9], obs_events, t0, t1)
    assert [s["seq"] for s in v["decode"]] == list(range(5, 10))
    for s in v["decode"]:
        assert s["commit"] - s["end"] == pytest.approx(1e6, abs=1e3)


def test_loop_view_on_hand_made_events():
    events, anchors, loop = _loop_case()
    v = lt.loop_view(events, anchors, loop, 0.0, 1000 * U)
    # obs stamps hold about 1 ns at 5 s: the map is exact to that
    assert v["residual_us"] == pytest.approx(0.0, abs=1e-2)
    assert v["devices"] == 1
    assert v["launches"] == {"jit_step": [(0.0, 100 * U),
                                          (300 * U, 400 * U)]}
    (step,) = v["decode"]
    want = {"seq": 7, "dispatch": 150, "start": 300, "end": 400,
            "ready": 430, "ran": 440, "commit": 450}
    assert step.keys() == want.keys()
    for k, x in want.items():
        assert step[k] == pytest.approx(x * U if k != "seq" else x,
                                        abs=10.0), k
    idle = v["idle_by_phase"]
    want_idle = {"admit": 50, "dispatch": 50, "progress": 130,
                 "commit": 50, lt.OUTSIDE: 520}
    assert idle.keys() == want_idle.keys()
    for k, ms in want_idle.items():
        assert idle[k] == pytest.approx(ms * 1e-3, abs=1e-8), k
    assert sum(idle.values()) + v["busy_s"] == pytest.approx(v["window_s"])
    # what ``reduce`` counts busy, the view counts busy
    assert v["busy_s"] == pytest.approx(
        rt.reduce(events, 0.0, 1000 * U)["busy_s"])


def test_loop_view_needs_anchors():
    events, anchors, loop = _loop_case()
    assert lt.loop_view(events, [], loop, 0.0, 1000 * U) is None
    events["host"] = []
    assert lt.loop_view(events, anchors, loop, 0.0, 1000 * U) is None


def test_recorded_v5e_slice_pairs_the_decode_dispatch():
    """On the recorded slice, a dispatch span around the host's
    ``PjitFunction(step)`` call pairs with the one ``jit_step`` module
    event, not with the per-slot ``jit_dynamic_slice`` programs, and the
    idle time split by phase adds up to the window less busy time."""
    ev = _recorded()
    (call,) = {(s, d) for _line, n, s, d in ev["host"]
               if n == "PjitFunction(step)" and d > 395000}
    (mod,) = [e for e in ev["device"]
              if e[1] == MODS and e[2].startswith("jit_step(")]
    t0 = min(e[3] for e in ev["device"])
    t1 = max(e[3] + e[4] for e in ev["device"])
    ev["host"] += [("python3", lt.ANCHOR, t0, 0.0),
                   ("python3", lt.ANCHOR, t1, 0.0)]
    loop = [_obs_event("serve.dispatch", call[0] * 1e-9, call[1] * 1e-9,
                       meta=(3, "jit_step")),
            _obs_event("serve.commit", (mod[3] + mod[4]) * 1e-9 + 2e-5,
                       1e-5, meta=3)]
    v = lt.loop_view(ev, [t0 * 1e-9, t1 * 1e-9], loop, t0, t1)
    assert abs(v["residual_us"]) < 1e-3
    (step,) = v["decode"]
    assert step["start"] == mod[3] and step["end"] == mod[3] + mod[4]
    assert step["commit"] - step["end"] == pytest.approx(2e4, abs=1.0)
    assert {"jit_dynamic_slice", "jit_squeeze", "jit_step"} == \
        set(v["launches"])
    assert sum(v["idle_by_phase"].values()) + v["busy_s"] == \
        pytest.approx(v["window_s"])
    assert v["idle_by_phase"]["dispatch"] > 0


def test_obs_span_and_annotation_agree_after_the_anchor_map(tmp_path):
    """A real profiler session on this backend: ``obs`` spans and
    ``TraceAnnotation``s around the same code land within 100 us of each
    other once the anchors map ``obs`` time onto the trace."""
    import time

    import jax

    from repro import obs
    tr = obs.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.anchor()
        for i in range(5):
            t = tr.now()
            with jax.profiler.TraceAnnotation("probe"):
                time.sleep(0.004)
            tr.loop_span("dispatch", t, meta=(i, "probe"))
        time.sleep(0.02)
        tr.anchor()
    finally:
        jax.profiler.stop_trace()
        obs.stop()
    events = rt.load(rt.find_xplane(str(tmp_path)))
    drained = tr.drain()
    offset, residual_us = lt.anchor_map(
        events, [e.ts for e in drained if e.kind == lt.ANCHOR])
    assert abs(residual_us) < 50
    probes = sorted((s, d) for _line, n, s, d in events["host"]
                    if n == "probe")
    spans = [e for e in drained if e.kind == "serve.dispatch"]
    assert len(probes) == len(spans) == 5
    for (s, d), e in zip(probes, spans):
        assert abs(e.ts * 1e9 + offset - s) < 1e5
        assert abs((e.ts + e.dur) * 1e9 + offset - (s + d)) < 1e5


# ------------------------------------------------------- the loop's readers
LOOP_READ = ("engine.queue_wait_p50_ms", "engine.queue_wait_p95_ms",
             "engine.dispatch_host_ms", "engine.loop_stall_ms")


@pytest.fixture(scope="module")
def traced_window():
    sv = harness.Served(tiny.CELL, tiny.SPEC, tiny.traffic(), 11,
                        require_tpu=False, kind="TPU v5 lite")
    w = sv.window(11, 2.0, trace=True)
    sv.close()
    return w


def _loop_run(w, obs):
    return harness.RunData(records=w["records"], ws=w["ws"], we=w["we"],
                           obs=obs)


def test_loop_readers_read_a_tiny_traced_window(traced_window):
    run = _loop_run(traced_window, traced_window["obs"])
    values = {name: harness.reader(name)(run) for name in LOOP_READ}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["engine.queue_wait_p95_ms"] >= \
        values["engine.queue_wait_p50_ms"]
    assert values["engine.loop_stall_ms"] >= \
        values["engine.dispatch_host_ms"]


def test_loop_readers_are_silent_without_obs(traced_window):
    run = _loop_run(traced_window, None)
    assert {name: harness.reader(name)(run) for name in LOOP_READ} == \
        dict.fromkeys(LOOP_READ)


def test_span_readers_are_silent_where_the_ring_dropped(traced_window):
    tr = traced_window["obs"]
    run = _loop_run(traced_window, types.SimpleNamespace(
        drain=tr.drain, histograms=tr.histograms, dropped=1))
    for name in LOOP_READ:
        v = harness.reader(name)(run)
        # events may be partial; histograms hold the whole window
        assert (v is None) == (".queue_wait" in name), (name, v)
