"""Serve-layer trace invariants on real engines: correlated per-request
timelines on the colocated tier, KV ship-before-import ordering across
the disaggregated role boundary, router shadow linking, recorder cause
attribution, and page-leak freedom while traced."""
import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import lm
from repro.obs import events as E
from repro.obs import tracer as tracer_mod
from repro.serve import Request, RequestState, Router, serve_requests
from repro.serve.disagg import DisaggServer


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    tracer_mod.stop()


PROMPTS = [
    list(range(1, 12)),              # 11 tokens -> 3 pages @ page_size=4
    [2, 3, 4, 5, 6],                 # 5 tokens
]
KW = dict(max_batch=2, max_cache_len=64, page_size=4, max_seq_len=48)


def _by_rid(events):
    out = {}
    for ev in events:
        if ev.kind.startswith("req.") and ev.rid >= 0:
            out.setdefault(ev.rid, []).append(ev)
    return out


# --------------------------------------------------------- colocated tier
def test_colocated_timeline_complete_and_ordered(small_model, tmp_path):
    cfg, params = small_model
    rec = obs.Recorder()
    with rec:
        reqs = serve_requests(cfg, params, [Request(p, 6) for p in PROMPTS],
                              timeout=300, paged=True, **KW)
    assert all(r.req_state is RequestState.FINISHED for r in reqs)
    timelines = _by_rid(rec.events)
    needed = {E.REQ_SUBMIT, E.REQ_ADMIT, E.REQ_PREFILL, E.REQ_PAGES_ALLOC,
              E.REQ_SEAT, E.REQ_STEP, E.REQ_DELIVER, E.REQ_PAGES_RELEASE,
              E.REQ_FINISH}
    for r in reqs:
        tl = timelines[r.req_id]
        kinds = {ev.kind for ev in tl}
        # the acceptance timeline: admission -> prefill -> every decode
        # step -> delivery, all on one correlated request id
        assert needed <= kinds
        # the admission span opens at arrival (before submit() ran) and
        # closes at placement: submission lands inside it
        admit = next(ev for ev in tl if ev.kind == E.REQ_ADMIT)
        submit = next(ev for ev in tl if ev.kind == E.REQ_SUBMIT)
        assert admit.ts <= submit.ts <= admit.ts + admit.dur
        assert tl[-1].kind in (E.REQ_FINISH, E.REQ_PAGES_RELEASE)
        steps = [ev for ev in tl if ev.kind == E.REQ_STEP]
        delivers = [ev for ev in tl if ev.kind == E.REQ_DELIVER]
        assert len(steps) >= len(r.tokens) - 1   # one span per decode step
        assert sum(ev.meta for ev in delivers) == len(r.tokens)
        prefill = next(ev for ev in tl if ev.kind == E.REQ_PREFILL)
        assert prefill.dur > 0.0
        assert all(prefill.ts <= ev.ts for ev in steps)

    # the runtime's own edges rode along: all four lifecycle histograms
    # and the serve loop's phases
    assert ({edge for edge, _ in rec.histograms}
            == set(E.LIFECYCLE_EDGES) | {E.LOOP})
    cause = rec.cause_summary()
    assert cause["requests"] == len(reqs)
    assert cause["compute_ms_mean"] > 0.0
    assert cause["notify_latency_us_mean"] > 0.0
    assert cause["dropped"] == 0

    # chrome export: one process per request, spans render as "X"
    path = rec.write(str(tmp_path / "trace.json"))
    doc = rec.chrome_trace()
    pids = {r_["pid"] for r_ in doc["traceEvents"] if r_["ph"] != "M"}
    assert {r.req_id + 1 for r in reqs} <= pids
    assert any(r_["ph"] == "X" for r_ in doc["traceEvents"])
    assert path.endswith("trace.json")


# -------------------------------------------------------- serve-loop spans
def test_loop_phases_carry_the_commit_sequence(small_model):
    """Each dispatch span names the sequence number and program of the
    step it launched; that step's commit span carries the same number,
    begins after the dispatch ended, and every phase feeds its
    histogram."""
    cfg, params = small_model
    tr = obs.start()
    reqs = serve_requests(cfg, params, [Request(p, 6) for p in PROMPTS],
                          timeout=300, paged=True, **KW)
    tracer_mod.stop()
    assert all(r.req_state is RequestState.FINISHED for r in reqs)
    events = tr.drain()
    kinds = {ev.kind for ev in events}
    assert set(E.SERVE_KINDS.values()) <= kinds
    dispatch = [ev for ev in events if ev.kind == "serve.dispatch"]
    commits = {ev.meta: ev for ev in events if ev.kind == "serve.commit"}
    assert dispatch and all(ev.meta[1] == "jit_step" for ev in dispatch)
    for ev in dispatch:
        assert commits[ev.meta[0]].ts >= ev.ts + ev.dur
    # commits run in dispatch order, one per registered step
    seqs = [ev.meta for ev in events if ev.kind == "serve.commit"]
    assert seqs == sorted(seqs) == list(range(len(seqs)))
    hist = tr.histograms()
    for phase in E.SERVE_KINDS:
        assert hist[(E.LOOP, phase)].count > 0


def test_untraced_loop_calls_no_span_helper(small_model, monkeypatch):
    cfg, params = small_model
    calls = []
    monkeypatch.setattr(tracer_mod.Tracer, "loop_span",
                        lambda *a, **k: calls.append(a))
    reqs = serve_requests(cfg, params, [Request(p, 6) for p in PROMPTS],
                          timeout=300, paged=True, **KW)
    assert all(r.req_state is RequestState.FINISHED for r in reqs)
    assert tracer_mod.TRACE is None and calls == []


# ------------------------------------------------------------ disagg tier
def test_disagg_ship_before_import_across_roles(small_model):
    cfg, params = small_model
    reqs = [Request(p, 6) for p in PROMPTS]
    obs.start()
    srv = DisaggServer(cfg, params, chunk_pages=1, **KW)
    try:
        for r in reqs:
            srv.submit(r)
        srv.close_intake()
        srv.run(timeout=300)
        assert all(r.req_state is RequestState.FINISHED for r in reqs)
        assert srv.decode.pool.pages_in_use == 0
        assert srv.prefill.pool.pages_in_use == 0
    finally:
        srv.shutdown()
        tr = tracer_mod.stop()

    timelines = _by_rid(tr.drain())
    for r in reqs:
        tl = timelines[r.req_id]
        ships = {ev.meta: ev.ts for ev in tl if ev.kind == E.REQ_KV_SHIP}
        imports = {ev.meta: ev.ts for ev in tl
                   if ev.kind == E.REQ_KV_IMPORT}
        # every shipped block is imported, and never before it shipped:
        # the request timeline stays monotone across the role boundary
        assert ships and set(ships) == set(imports)
        for block, t_ship in ships.items():
            assert imports[block] >= t_ship
        # prefill-role work precedes decode-role work on the same track
        prefill_ts = [ev.ts for ev in tl
                      if ev.src == "prefill" and ev.kind == E.REQ_PREFILL]
        step_ts = [ev.ts for ev in tl if ev.kind == E.REQ_STEP]
        assert prefill_ts and step_ts
        assert min(prefill_ts) <= min(step_ts)
        srcs = {ev.src for ev in tl}
        assert {"prefill", "decode"} <= srcs


# ------------------------------------------------------------ router tier
def test_router_links_shadows_to_originals(small_model):
    cfg, params = small_model
    obs.start()
    r = Router(cfg, params, n_replicas=2, paged=True, **KW)
    try:
        reqs = [r.submit(Request(p, 6)) for p in PROMPTS]
        r.close_intake()
        r.run(timeout=300)
        assert all(q.req_state is RequestState.FINISHED for q in reqs)
        for w in r.workers:
            if w.pool is not None:
                assert w.pool.pages_in_use == 0
        m = r.metrics()
        assert m["transport_sent_msgs"] > 0   # typed transport fields
    finally:
        r.shutdown()
        tr = tracer_mod.stop()

    events = tr.drain()
    roots = E.link_roots(events)
    originals = {q.req_id for q in reqs}
    assert roots                              # every dispatch is a shadow
    assert set(roots.values()) <= originals
    # the exporter collapses shadow events onto the originals' tracks
    doc = obs.chrome_trace(events)
    req_pids = {rec["pid"] for rec in doc["traceEvents"]
                if rec["ph"] != "M" and rec["name"].startswith("req.")}
    assert req_pids == {rid + 1 for rid in originals}
