"""Observability core: ring-buffer drop-not-block semantics, the
default-off fast path, deterministic sampling, continuation lifecycle
ordering (op-complete never after callback-ran) with all four edge
histograms, and the Chrome/Prometheus exporters."""
import pytest

from repro import obs
from repro.obs import events as E
from repro.obs import tracer as tracer_mod
from repro.obs.buffer import TraceBuffer
from repro.obs.hist import Histogram


@pytest.fixture(autouse=True)
def _tracing_off():
    # never leak an armed global tracer into other tests
    yield
    tracer_mod.stop()


def _drive_engine(n=8):
    """Register n continuations on pushable ops, trigger, wait."""
    from repro.core import Engine, Status
    from repro.core.completable import Completable

    class Op(Completable):
        @property
        def supports_push(self):
            return True

        def trigger(self):
            self._complete(Status())

    eng = Engine()
    cr = eng.continue_init()
    done = []
    try:
        ops = [Op() for _ in range(n)]
        for op in ops:
            eng.continue_when(op, lambda st, d: done.append(d), cr=cr)
        for op in ops:
            op.trigger()
        assert cr.wait(timeout=10)
    finally:
        eng.shutdown()
    assert len(done) == n
    return done


# ---------------------------------------------------------------- basics
def test_tracing_is_off_by_default():
    assert tracer_mod.TRACE is None
    assert not obs.is_enabled()
    assert obs.active() is None


def test_start_stop_arm_and_disarm():
    tr = obs.start(sample=0.5, capacity=32)
    assert obs.is_enabled() and obs.active() is tr
    assert obs.stop() is tr
    assert not obs.is_enabled()
    assert obs.stop() is None          # idempotent


# ------------------------------------------------------------- overflow
def test_ring_overflow_drops_not_blocks():
    buf = TraceBuffer(4)
    for i in range(10):
        buf.record((float(i), 0.0, "k", i, "t", None))
    assert len(buf) == 4               # oldest records kept, never grows
    assert buf.dropped == 6
    snap = buf.snapshot()
    assert [ev.rid for ev in snap] == [0, 1, 2, 3]
    assert all(ev.tid == buf.tid for ev in snap)


def test_tracer_surfaces_drop_counter():
    tr = obs.start(capacity=8)
    for i in range(20):
        tr.evt(E.REQ_STEP, i, "test")
    assert tr.dropped == 12
    events = tr.drain()
    assert len(events) == 8
    doc = obs.chrome_trace(events, dropped=tr.dropped)
    assert doc["otherData"]["dropped_events"] == 12
    assert doc["otherData"]["event_count"] == 8


# ------------------------------------------------------------- sampling
def test_sampling_deterministic_by_id():
    a = obs.Tracer(sample=0.5)
    b = obs.Tracer(sample=0.5)
    picked = [i for i in range(1000) if a.want(i)]
    # same subset on every component/tracer; genuinely partial
    assert picked == [i for i in range(1000) if b.want(i)]
    assert 0 < len(picked) < 1000
    assert all(obs.Tracer(sample=1.0).want(i) for i in range(100))
    assert not any(obs.Tracer(sample=0.0).want(i) for i in range(100))


def test_sample_zero_records_nothing_from_core():
    obs.start(sample=0.0)
    _drive_engine()
    tr = tracer_mod.stop()
    assert tr.drain() == []
    assert tr.histograms() == {}


# --------------------------------------------------- lifecycle ordering
def test_lifecycle_edges_ordered_and_histogrammed():
    obs.start()
    _drive_engine()
    tr = tracer_mod.stop()
    by_cont = {}
    for ev in tr.drain():
        if ev.kind.startswith("cont."):
            by_cont.setdefault(ev.rid, {})[ev.kind] = ev
    assert by_cont
    full = {E.CONT_POSTED, E.CONT_READY, E.CONT_ENQUEUED, E.CONT_RAN}
    for kinds in by_cont.values():
        # sampled-at-registration => traced end-to-end, in causal order;
        # in particular op-complete (READY) never lands after the
        # callback-ran timestamp
        assert set(kinds) == full
        assert (kinds[E.CONT_POSTED].ts <= kinds[E.CONT_READY].ts
                <= kinds[E.CONT_ENQUEUED].ts <= kinds[E.CONT_RAN].ts)
        assert kinds[E.CONT_RAN].dur >= 0.0
    hist = tr.histograms()
    assert {edge for edge, _ in hist} == set(E.LIFECYCLE_EDGES)
    for h in hist.values():
        assert h.count > 0
        assert h.total >= 0.0


# ------------------------------------------------------- serve-loop spans
def test_loop_span_records_span_and_histogram():
    tr = obs.start()
    t1 = tr.loop_span(E.LOOP_DISPATCH, tr.now(), meta=(3, "jit_step"))
    assert t1 <= tr.now()
    (ev,) = tr.drain()
    assert ev.kind == "serve.dispatch" and ev.meta == (3, "jit_step")
    assert ev.dur >= 0.0 and ev.ts + ev.dur == pytest.approx(t1)
    assert set(tr.histograms()) == {(E.LOOP, E.LOOP_DISPATCH)}
    assert tr.histograms()[(E.LOOP, E.LOOP_DISPATCH)].count == 1


def test_loop_span_of_an_empty_phase_records_nothing():
    tr = obs.start()
    t0 = tr.now()
    assert tr.loop_span(E.LOOP_PROGRESS, t0, did=False,
                        context=lambda: pytest.fail("read")) >= t0
    assert tr.drain() == [] and tr.histograms() == {}


@pytest.mark.parametrize("did", [True, False])
def test_a_phase_past_the_stall_threshold_records_one_stall(did):
    tr = obs.start()
    t0 = tr.now() - 2 * E.STALL_S
    tr.loop_span(E.LOOP_ADMIT, t0, did,
                 context=lambda: {"program": "jit_prefill"})
    events = tr.drain()
    assert [ev.kind for ev in events] == ["serve.admit", E.SERVE_STALL]
    stall = events[1]
    assert stall.meta == {"phase": E.LOOP_ADMIT, "program": "jit_prefill"}
    assert stall.ts == t0 and stall.dur > E.STALL_S
    h = tr.histograms()[(E.LOOP, E.LOOP_ADMIT)]
    assert h.count == 1 and h.max > E.STALL_S * 1e6


def test_a_stopped_tracer_takes_no_loop_span():
    """A loop step begun before ``stop()`` ends after it: what it closes
    then is not the session's."""
    tr = obs.start()
    t0 = tr.now()
    tr.loop_span(E.LOOP_DISPATCH, t0)
    assert obs.stop() is tr
    assert tr.loop_span(E.LOOP_DISPATCH, t0 - 2 * E.STALL_S) >= t0
    assert [ev.kind for ev in tr.drain()] == ["serve.dispatch"]
    h = tr.histograms()[(E.LOOP, E.LOOP_DISPATCH)]
    assert h.count == 1 and h.max < E.STALL_S * 1e6


# ------------------------------------------------------------- exporters
def test_chrome_trace_tracks_and_phases():
    events = [
        E.Event(1.0, 0.5, E.REQ_ADMIT, 7, "engine", None, 1),
        E.Event(1.6, 0.0, E.REQ_DELIVER, 7, "serve", 3, 1),
        E.Event(1.7, 0.0, E.CONT_READY, 42, "core", None, 9),
    ]
    doc = obs.chrome_trace(events)
    recs = [r for r in doc["traceEvents"] if r["ph"] != "M"]
    admit, deliver, ready = recs
    assert admit["ph"] == "X"                      # span
    assert admit["dur"] == pytest.approx(0.5e6)    # us
    assert deliver["ph"] == "i"                    # instant
    assert admit["pid"] == deliver["pid"] == 8     # request 7's process
    assert ready["pid"] == 0                       # runtime process
    assert ready["tid"] == 9                       # real thread id


def test_chrome_trace_collapses_shadow_chains():
    events = [
        E.Event(1.0, 0.0, E.REQ_SUBMIT, 1, "router", None, 1),
        E.Event(1.1, 0.0, E.REQ_LINK, 5, "router", 1, 1),
        E.Event(1.2, 0.0, E.REQ_LINK, 9, "router", 5, 1),   # re-shadowed
        E.Event(1.3, 0.0, E.REQ_STEP, 9, "engine", None, 1),
    ]
    assert obs.link_roots(events) == {5: 1, 9: 1}   # transitive
    doc = obs.chrome_trace(events)
    pids = {r["pid"] for r in doc["traceEvents"] if r["ph"] != "M"}
    assert pids == {2}                 # everything on request 1's track


def test_prometheus_text_shapes():
    h = Histogram()
    for v in (0.5, 3.0, 100.0):
        h.observe(v)
    text = obs.prometheus_text(
        {"finished": 3, "ttft_mean": 0.25},
        histograms={("complete_to_run", "sched"): h},
        dropped=2,
        transport={"sent_bytes": 11, "per_tag": {7: {"sent_msgs": 4}}})
    assert "repro_trace_dropped_events 2" in text
    assert "repro_serve_finished 3" in text
    assert "repro_transport_sent_bytes 11" in text
    assert 'repro_transport_sent_msgs{tag="7"} 4' in text
    assert 'le="+Inf"' in text         # cumulative buckets close at +Inf
    assert ('repro_lifecycle_latency_us_count'
            '{edge="complete_to_run",policy="sched"} 3') in text
