"""Streaming session API tests.

Covers the ISSUE-5 surface end to end: ``GenerationConfig`` validation
and the deprecated ``Request`` kwarg shims, sync + asyncio token
streams that are token-identical to retirement delivery (greedy and
speculative), first-token-before-retirement, slow-consumer backpressure
that never blocks the decode loop, stop-sequence truncation identity,
deadline expiry releasing pages in the completion continuation,
priority ordering under oversubscription, and cancel-mid-stream (incl.
mid-speculative-verify) with page-leak checks.
"""
import asyncio
import threading
import time

import jax
import pytest

from repro.core import Engine, PromiseCancelled
from repro.serve import (Batcher, DeadlineExceeded, GenerationConfig,
                         Request, RequestState, ServeClient, ServeEngine,
                         TokenStream, serve_requests)


# ------------------------------------------------------ GenerationConfig
def test_generation_config_validation():
    cfg = GenerationConfig(max_tokens=4, stop=[[1, 2]], priority=3,
                           deadline_s=1.5, stream_buffer=8)
    assert cfg.stop == ((1, 2),)
    with pytest.raises(ValueError, match="max_tokens"):
        GenerationConfig(max_tokens=0)
    with pytest.raises(ValueError, match="speculate"):
        GenerationConfig(max_tokens=1, speculate=-1)
    with pytest.raises(ValueError, match="greedy"):
        GenerationConfig(max_tokens=1, temperature=0.7)
    with pytest.raises(ValueError, match="stop"):
        GenerationConfig(max_tokens=1, stop=[[]])
    with pytest.raises(ValueError, match="stop"):
        GenerationConfig(max_tokens=1, stop=7)
    with pytest.raises(ValueError, match="deadline_s"):
        GenerationConfig(max_tokens=1, deadline_s=0.0)
    with pytest.raises(ValueError, match="stream_buffer"):
        GenerationConfig(max_tokens=1, stream_buffer=0)


def test_generation_config_merged_revalidates():
    cfg = GenerationConfig(max_tokens=4)
    assert cfg.merged(priority=2).priority == 2
    assert cfg.merged(priority=2).max_tokens == 4  # original preserved
    assert cfg.priority == 0                       # frozen: no mutation
    with pytest.raises(ValueError):
        cfg.merged(max_tokens=-1)


# ------------------------------------------------- deprecated kwarg shims
def test_request_deprecated_kwargs_still_work():
    with pytest.warns(DeprecationWarning, match="max_new_tokens"):
        old = Request([1, 2], max_new_tokens=5)
    assert old.config.max_tokens == 5 and old.max_new_tokens == 5
    with pytest.warns(DeprecationWarning, match="speculate"):
        old2 = Request([1, 2], 5, speculate=2)
    assert old2.config.speculate == 2 and old2.speculate == 2
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            Request([1, 2], 4, speculate=-1)   # shimmed but still validated
    # canonical forms emit no warning
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert Request([1], 3).config.max_tokens == 3
        assert Request([1], GenerationConfig(max_tokens=3,
                                             speculate=1)).speculate == 1
    with pytest.raises(ValueError, match="not both"):
        Request([1], 3, max_new_tokens=4)
    with pytest.raises(ValueError):
        Request([1])                           # no budget at all


# ----------------------------------------------------- batcher QoS order
def test_batcher_priority_order_and_arrival_within_class():
    eng = Engine()
    try:
        b = Batcher(eng)
        reqs = [b.submit(Request([i], GenerationConfig(max_tokens=2,
                                                       priority=p)))
                for i, p in enumerate([0, 5, 1, 5])]
        got = b.admit(10)
        # strict priority, arrival order within a class
        assert got == [reqs[1], reqs[3], reqs[2], reqs[0]]
    finally:
        eng.shutdown()


def test_batcher_requeue_heads_priority_class():
    eng = Engine()
    try:
        b = Batcher(eng)
        r_hi = b.submit(Request([0], GenerationConfig(max_tokens=2,
                                                      priority=1)))
        r_a = b.submit(Request([1], 2))
        r_b = b.submit(Request([2], 2))
        got = b.admit(10)
        assert got == [r_hi, r_a, r_b]
        b.requeue(r_b)
        b.requeue(r_a)       # engine requeues in reverse, head-first
        assert r_a.req_state is RequestState.QUEUED
        assert b.admit(10) == [r_a, r_b]
    finally:
        eng.shutdown()


def test_batcher_refuses_past_deadline_queued():
    eng = Engine()
    try:
        b = Batcher(eng)
        doomed = b.submit(Request([1], GenerationConfig(max_tokens=2,
                                                        deadline_s=0.01)))
        ok = b.submit(Request([2], 2))
        time.sleep(0.03)
        assert b.admit(10) == [ok]
        assert b.stats["expired_queued"] == 1
        assert doomed.req_state is RequestState.EXPIRED
        assert doomed.wait(timeout=1.0)
        with pytest.raises(DeadlineExceeded):
            doomed.status.raise_for_error()
    finally:
        eng.shutdown()


# ---------------------------------------------------- streaming end-to-end
@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0,
                                 cfg.vocab_size)
    return cfg, params, prompts


@pytest.fixture(scope="module")
def baseline(small_model):
    """Retirement-delivery tokens for the shared prompts (the identity
    reference for every streaming test)."""
    cfg, params, prompts = small_model
    reqs = serve_requests(cfg, params,
                          [Request(prompts[i], 8) for i in range(4)],
                          max_batch=2, max_cache_len=16, timeout=300)
    return [r.tokens for r in reqs]


def test_stream_tokens_identical_and_first_token_before_retirement(
        small_model, baseline):
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=16) as client:
        session = client.session(max_tokens=8)
        streams = [session.generate(prompts[i]) for i in range(4)]
        out = [list(s) for s in streams]
        assert out == baseline
        for s in streams:
            assert s.reason == "finished"
            assert s.request.req_state is RequestState.FINISHED
            # TTFT claim: the first token was published strictly before
            # the request finished (multi-token request => earlier step)
            assert s.first_token_time < s.request.finish_time
        assert client.metrics()["pages_in_use"] == 0


def test_stream_async_consumers_and_text(small_model, baseline):
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=16) as client:
        session = client.session(max_tokens=8)

        async def consume(i):
            return [t async for t in session.generate(prompts[i])]

        async def main():
            toks = await asyncio.gather(*(consume(i) for i in range(3)))
            text = await session.generate(prompts[3]).text()
            return toks, text

        toks, text = asyncio.run(main())
        assert toks == baseline[:3]
        assert text == " ".join(str(t) for t in baseline[3])


def test_stream_speculative_identity(small_model, baseline):
    """Streaming through the verify path (accept runs deliver in bursts)
    is token-identical to plain greedy retirement delivery."""
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=32,
                      paged=True, page_size=8, max_seq_len=16, speculate=2)
    with ServeClient(engine=eng) as client:
        session = client.session(max_tokens=8)
        streams = [session.generate(prompts[i]) for i in range(4)]
        assert [list(s) for s in streams] == baseline
        m = client.metrics()
        assert m["verify_steps"] > 0
        assert m["pages_in_use"] == 0


def test_slow_consumer_marks_lagging_never_blocks_loop(small_model,
                                                       baseline):
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=16) as client:
        stream = client.generate(prompts[0], max_tokens=8, stream_buffer=2)
        # don't consume at all: the decode loop must finish regardless
        assert stream.request.wait(timeout=120)
        assert stream.request.req_state is RequestState.FINISHED
        assert stream.lagging is True
        assert stream.pending == 8        # everything still readable
        assert list(stream) == baseline[0]
        # a keeping-up consumer never lags
        fast = client.generate(prompts[1], max_tokens=8, stream_buffer=64)
        assert list(fast) == baseline[1]
        assert fast.lagging is False


def _apply_stop(tokens, stop_seqs):
    """Independent oracle for stop-truncation semantics: scan token by
    token, finish at the first completed stop sequence, exclude it."""
    out = []
    for t in tokens:
        out.append(t)
        for s in stop_seqs:
            if len(out) >= len(s) and tuple(out[-len(s):]) == tuple(s):
                return out[:len(out) - len(s)], True
    return out, False


def test_stop_sequence_stream_vs_retirement_identity(small_model, baseline):
    cfg, params, prompts = small_model
    stop = tuple(baseline[0][3:5])          # spans two decode steps
    expected, hit = _apply_stop(baseline[0], [stop])
    assert hit and len(expected) < len(baseline[0])
    gen = GenerationConfig(max_tokens=8, stop=[stop])
    # retirement path
    req = serve_requests(cfg, params, [Request(prompts[0], gen)],
                         max_batch=2, max_cache_len=16, timeout=300)[0]
    assert req.tokens == expected           # stop excluded from output
    # streaming path delivers exactly the same, and never leaks a token
    # of the stop sequence (holdback)
    with ServeClient(cfg, params, max_batch=2, max_cache_len=16) as client:
        st = client.generate(prompts[0], gen)
        assert list(st) == expected
        assert st.request.tokens == expected
        assert st.reason == "finished"
        assert client.metrics()["stopped"] == 1
        assert client.metrics()["pages_in_use"] == 0


def test_stop_on_first_token(small_model, baseline):
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=16) as client:
        st = client.generate(prompts[0], max_tokens=8,
                             stop=[[baseline[0][0]]])
        assert list(st) == []
        assert st.request.tokens == []
        assert st.request.req_state is RequestState.FINISHED
        assert client.metrics()["pages_in_use"] == 0


def test_deadline_expiry_releases_pages_mid_decode(small_model):
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=256,
                     max_seq_len=256) as client:
        client.generate(prompts[0], max_tokens=2).result(timeout=300)  # warm
        st = client.generate(prompts[1], max_tokens=200, deadline_s=0.3)
        with pytest.raises(DeadlineExceeded) as exc:
            st.tokens().result(timeout=60)
        assert st.request.req_state is RequestState.EXPIRED
        assert st.reason == "expired"
        # partial tokens survive on the request and ride the exception
        assert 0 < len(st.request.tokens) < 200
        assert exc.value.tokens == st.request.tokens
        m = client.metrics()
        assert m["expired"] == 1
        assert m["pages_in_use"] == 0     # released by the continuation


def test_priority_admission_under_oversubscription(small_model):
    """One slot, four queued requests: admission must seat strictly by
    priority (arrival order within a class), not submission order."""
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=1, max_cache_len=16)
    try:
        reqs = [Request(prompts[i % 4],
                        GenerationConfig(max_tokens=3, priority=p))
                for i, p in enumerate([0, 0, 7, 3])]
        for r in reqs:
            eng.submit(r)
        eng.close_intake()
        eng.run(timeout=300)
        order = sorted(reqs, key=lambda r: r.admit_time)
        assert [r.priority for r in order] == [7, 3, 0, 0]
        assert order[2] is reqs[0]        # arrival order within class
        assert all(r.req_state is RequestState.FINISHED for r in reqs)
    finally:
        eng.shutdown()


# --------------------------------------------------- cancel-mid-stream
def _drive_until(eng, pred, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not pred():
        eng.step()
        if time.monotonic() > deadline:
            raise TimeoutError("condition never became true")


def test_cancel_mid_stream_no_delivery_after_cancel(small_model):
    """Tokens produced in the same step a request is cancelled must not
    be delivered after cancel() returns — driven deterministically on
    this thread so a step is guaranteed in flight at cancel time."""
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=64,
                      max_seq_len=64)
    try:
        req = Request(prompts[0], GenerationConfig(max_tokens=40))
        stream = TokenStream(req)
        eng.submit(req)
        _drive_until(eng, lambda: stream.received >= 2)
        eng._dispatch_step()              # a step is now in flight…
        assert eng._inflight > 0
        assert req.cancel() is True       # …and cancel returns before it
        n_at_cancel = stream.received
        # run its continuation + sweeps, however long the device takes
        _drive_until(eng, lambda: eng.idle)
        assert stream.received == n_at_cancel
        assert list(stream)[:n_at_cancel] == stream._toks
        assert stream.reason == "cancelled"
        assert req.req_state is RequestState.CANCELLED
        with pytest.raises(PromiseCancelled):
            stream.tokens().result(timeout=5)
        assert eng.metrics()["pages_in_use"] == 0
    finally:
        eng.shutdown()


def test_cancel_mid_speculative_verify_no_delivery_no_leaks(small_model):
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=64,
                      paged=True, page_size=8, max_seq_len=64, speculate=3)
    try:
        req = Request(prompts[0], GenerationConfig(max_tokens=40))
        stream = TokenStream(req)
        eng.submit(req)
        _drive_until(eng, lambda: stream.received >= 2)
        # force a verify step in flight, then cancel before its
        # continuation runs: the whole accepted run must be dropped
        _drive_until(eng, lambda: eng._dispatch_step() or eng._verifying)
        assert req.cancel() is True
        n_at_cancel = stream.received
        _drive_until(eng, lambda: eng.idle)
        assert stream.received == n_at_cancel
        assert stream.reason == "cancelled"
        assert not eng._verifying
        assert eng.metrics()["pages_in_use"] == 0
        assert eng.stats["cancelled"] >= 1
    finally:
        eng.shutdown()


def test_cancel_from_consumer_thread_closes_stream(small_model):
    """stream.cancel() from a real consumer thread while the client loop
    decodes: iteration ends, nothing arrives after cancel returns."""
    cfg, params, prompts = small_model
    with ServeClient(cfg, params, max_batch=2, max_cache_len=64,
                     max_seq_len=64) as client:
        stream = client.generate(prompts[0], max_tokens=50)
        got = []
        post_cancel = []
        for tok in stream:
            got.append(tok)
            if len(got) == 3:
                stream.cancel()
                post_cancel.append(stream.received)
        time.sleep(0.2)                   # loop keeps running
        assert stream.received == post_cancel[0]
        assert stream.reason == "cancelled"
        assert client.metrics()["pages_in_use"] == 0


def test_completed_budget_outranks_lapsed_deadline(small_model):
    """A request whose final budgeted step is already in flight when the
    deadline lapses still FINISHES — the engine returns the output it
    already paid for instead of expiring it."""
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=16)
    try:
        warm = Request(prompts[1], 2)       # compile before the deadline
        eng.submit(warm)
        eng.run(until=lambda: warm.req_state is RequestState.FINISHED,
                timeout=300)
        req = Request(prompts[0],
                      GenerationConfig(max_tokens=2, deadline_s=2.0))
        eng.submit(req)
        eng._admit()              # seat + prefill (continuation pending)
        limit = time.monotonic() + 60
        while req.delivered == 0:  # first token delivers before deadline
            eng.engine.tick()
            assert time.monotonic() < limit
        assert not req.past_deadline()
        eng._dispatch_step()      # final budgeted token now in flight
        assert eng._draining
        # deadline lapses mid-flight
        time.sleep(max(0.0, req.deadline_time - time.monotonic()) + 0.05)
        _drive_until(eng, lambda: eng.idle)
        assert req.req_state is RequestState.FINISHED
        assert len(req.tokens) == 2
        assert eng.stats["expired"] == 0
        assert eng.metrics()["pages_in_use"] == 0
    finally:
        eng.shutdown()


def test_client_loop_death_cancels_streams_and_reraises(small_model):
    """A decode-loop crash must not strand stream consumers: live
    requests are cancelled (closing their streams) and close()
    re-raises the loop error."""
    cfg, params, prompts = small_model
    client = ServeClient(cfg, params, max_batch=2, max_cache_len=64,
                         max_seq_len=64)
    stream = client.generate(prompts[0], max_tokens=50)

    def boom():
        raise RuntimeError("loop-test-crash")

    client.serve.step = boom          # next loop iteration raises
    list(stream)                      # must terminate, not hang
    assert stream.reason == "cancelled"
    with pytest.raises(PromiseCancelled):
        stream.tokens().result(timeout=5)
    # a failed client refuses new work instead of silently restarting
    with pytest.raises(RuntimeError, match="crashed"):
        client.generate(prompts[1], max_tokens=2)
    with pytest.raises(RuntimeError, match="loop-test-crash"):
        client.close()
