"""Serving subsystem tests: request lifecycle, batcher admission CR
semantics, and the continuous-batching engine end-to-end vs the
synchronous ``greedy_generate`` baseline (token-exact)."""
import threading
import time

import jax
import pytest

from repro.core import Engine
from repro.serve import (Batcher, Request, RequestState, ServeEngine,
                         greedy_generate, serve_requests, summarize)


# ------------------------------------------------------------- request
def test_request_lifecycle_and_timing():
    req = Request([1, 2, 3], 4)
    assert req.req_state is RequestState.QUEUED
    assert req.remaining == 4
    req.on_admitted()
    assert req.req_state is RequestState.PREFILLING
    req.push_device_token(7)
    req.on_first_token()
    assert req.req_state is RequestState.DECODING
    assert req.ttft is not None and req.ttft >= 0
    for t in (8, 9, 10):
        req.push_device_token(t)
    assert req.remaining == 0
    req.retire()
    assert req.req_state is RequestState.FINISHED
    assert req.tokens == [7, 8, 9, 10]
    assert req.wait(timeout=0.1)
    assert req.latency is not None


def test_request_is_completable():
    """A Request is an op: continuations attach to its completion."""
    eng = Engine()
    try:
        cr = eng.continue_init()
        req = Request([1], 1)
        seen = []
        flag = eng.continue_when(req, lambda st, d: seen.append(st[0].payload),
                                 status=[None], cr=cr)
        assert flag is False
        req.push_device_token(5)
        req.retire()
        assert seen == [[5]]
        assert cr.test() is True
    finally:
        eng.shutdown()


def test_request_cancel():
    req = Request([1], 3)
    assert req.cancel() is True
    assert req.req_state is RequestState.CANCELLED
    assert req.cancel() is False
    done = Request([1], 1)
    done.push_device_token(1)
    done.retire()
    assert done.cancel() is False
    assert done.req_state is RequestState.FINISHED


def test_request_validates_budget():
    with pytest.raises(ValueError):
        Request([1], 0)


# ------------------------------------------------------------- batcher
def test_batcher_defers_admission_to_loop():
    """Submissions must not run callbacks on the submitting thread — they
    queue on the poll_only CR until admit() (the paper's burst pattern)."""
    eng = Engine()
    try:
        b = Batcher(eng)
        reqs = [b.submit(Request([i], 2)) for i in range(3)]
        assert b.queued == 0             # nothing transferred yet
        assert b.cr.active_count == 3    # parked on the CR
        eng.tick()                       # generic progress must NOT admit
        assert b.queued == 0
        got = b.admit(2)
        assert [r.req_id for r in got] == [reqs[0].req_id, reqs[1].req_id]
        assert all(r.req_state is RequestState.PREFILLING for r in got)
        assert b.queued == 1             # third transferred, not admitted
        assert b.admit(5) == [reqs[2]]
    finally:
        eng.shutdown()


def test_batcher_submit_from_other_threads():
    eng = Engine()
    try:
        b = Batcher(eng)
        n = 40
        threads = [threading.Thread(
            target=lambda i=i: b.submit(Request([i], 1)))
            for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = b.admit(n)
        assert len(got) == n
        assert b.drained is False        # not closed yet
        b.close()
        assert b.drained is True
        with pytest.raises(RuntimeError, match="closed"):
            b.submit(Request([0], 1))
    finally:
        eng.shutdown()


def test_batcher_drops_cancelled_before_admit():
    eng = Engine()
    try:
        b = Batcher(eng)
        r1, r2 = Request([1], 2), Request([2], 2)
        b.submit(r1)
        b.submit(r2)
        r1.cancel()
        got = b.admit(5)
        assert got == [r2]
        assert b.stats["dropped_cancelled"] == 1
    finally:
        eng.shutdown()


# ------------------------------------------------- engine (end-to-end)
@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_config
    from repro.models import lm
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0,
                                 cfg.vocab_size)
    return cfg, params, prompts


def test_serve_matches_greedy_baseline(small_model):
    cfg, params, prompts = small_model
    base = [list(map(int, greedy_generate(cfg, params, prompts[i:i + 1], 5,
                                          max_cache_len=16)[0]))
            for i in range(3)]
    reqs = serve_requests(cfg, params,
                          [Request(prompts[i], 5) for i in range(3)],
                          max_batch=2, max_cache_len=16, timeout=300)
    assert all(r.req_state is RequestState.FINISHED for r in reqs)
    assert [r.tokens for r in reqs] == base


def test_serve_heterogeneous_lengths_and_slot_reuse(small_model):
    cfg, params, prompts = small_model
    lengths = [1, 7, 3, 5]
    reqs = [Request(prompts[i], lengths[i]) for i in range(4)]
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=16)
    try:
        for r in reqs:
            eng.submit(r)
        eng.close_intake()
        eng.run(timeout=300)
        assert [len(r.tokens) for r in reqs] == lengths
        assert eng.stats["retired"] == 4
        # 4 requests through 2 slots => slots were reused
        assert eng.stats["prefills"] == 4
        m = summarize(reqs)
        assert m["finished"] == 4
        assert m["total_tokens"] == sum(lengths)
        assert m["ttft_p99"] >= m["ttft_p50"] >= 0
    finally:
        eng.shutdown()


def test_serve_overlapped_submission_thread(small_model):
    """Requests arriving mid-decode are admitted without restarting the
    loop (prefill overlaps in-flight decode)."""
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=16,
                      scheduler="affinity")
    try:
        first = Request(prompts[0], 6)
        late = [Request(prompts[i], 3) for i in (1, 2)]
        eng.submit(first)

        def straggler():
            time.sleep(0.02)
            for r in late:
                eng.submit(r)
            eng.close_intake()

        t = threading.Thread(target=straggler)
        t.start()
        eng.run(timeout=300)
        t.join()
        assert len(first.tokens) == 6
        assert all(len(r.tokens) == 3 for r in late)
    finally:
        eng.shutdown()


def test_serve_single_token_requests_skip_slots(small_model):
    """max_new_tokens=1 is answered by prefill alone."""
    cfg, params, prompts = small_model
    base = list(map(int, greedy_generate(cfg, params, prompts[:1], 1,
                                         max_cache_len=16)[0]))
    reqs = serve_requests(cfg, params, [Request(prompts[0], 1)],
                          max_batch=2, max_cache_len=16, timeout=300)
    assert reqs[0].tokens == base


def test_submit_async_awaitable(small_model):
    """The promise front-end over serving: submit_async returns an
    awaitable that resolves with the token list at retirement, while the
    decode loop runs on its own thread."""
    import asyncio
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=32,
                      paged=False)
    try:
        async def main():
            reqs = [Request(prompts[i], 3 + i) for i in range(2)]
            proms = [eng.submit_async(r) for r in reqs]
            eng.close_intake()
            loop = threading.Thread(target=lambda: eng.run(timeout=300))
            loop.start()
            toks = await asyncio.gather(*proms)
            loop.join()
            return reqs, toks

        reqs, toks = asyncio.run(main())
        for i, (r, t) in enumerate(zip(reqs, toks)):
            assert t == r.tokens
            assert len(t) == 3 + i
    finally:
        eng.shutdown()


def test_submit_async_cancel_rejects(small_model):
    """promise.cancel() cancels the underlying request; the awaitable
    rejects with PromiseCancelled."""
    import asyncio
    from repro.core import PromiseCancelled
    cfg, params, prompts = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=32,
                      paged=False)
    try:
        async def main():
            req = Request(prompts[0], 50)
            prom = eng.submit_async(req)
            prom.cancel()
            eng.close_intake()
            loop = threading.Thread(target=lambda: eng.run(timeout=300))
            loop.start()
            with pytest.raises(PromiseCancelled):
                await prom
            loop.join()
            return req

        req = asyncio.run(main())
        assert req.req_state is RequestState.CANCELLED
    finally:
        eng.shutdown()


def test_put_snapshots_host_state(small_model):
    """Host state the engine mutates right after dispatching a step
    (``_pos``, ``_tables``) reaches the device as a private copy: a later
    mutation never shows through. ``jnp.asarray`` may alias an aligned
    NumPy buffer on the CPU backend, which made greedy token streams
    diverge under load."""
    import numpy as np
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_cache_len=32)
    try:
        for n in range(1, 129):
            host = np.zeros(n, np.int32)
            dev = eng._put(host)
            host += 1
            assert int(dev.sum()) == 0, n
            assert dev.devices() == {eng.device}
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kw", [
    dict(paged=False),
    dict(paged=True, page_size=8, max_seq_len=32),
    dict(paged=True, page_size=8, max_seq_len=32, speculate=2),
], ids=["dense", "paged", "speculative"])
def test_tokens_commit_in_dispatch_order(small_model, monkeypatch, kw):
    """Completion discovery may find a later step's array ready before an
    earlier one's (a scan polls the earlier op just before it completes).
    Here every scan waits for all in-flight device work and then discovers
    ops newest first: the engine must still deliver tokens and retire
    slots in dispatch order."""
    from repro.core.completable import ArrayOp
    from repro.core.progress import Progress

    def newest_first_scan(self):
        with self._poll_lock:
            ops = list(self._poll_ops)
        for op in ops:
            if isinstance(op, ArrayOp):
                jax.block_until_ready(op._leaves)
        done = {id(op) for op in reversed(ops) if op.done()}
        with self._poll_lock:
            self._poll_ops = [op for op in self._poll_ops
                              if id(op) not in done]

    cfg, params, prompts = small_model
    base = [list(map(int, greedy_generate(cfg, params, prompts[i:i + 1], 5,
                                          max_cache_len=16)[0]))
            for i in range(3)]
    monkeypatch.setattr(Progress, "scan", newest_first_scan)
    reqs = serve_requests(cfg, params,
                          [Request(prompts[i], 5) for i in range(3)],
                          max_batch=2, max_cache_len=16, timeout=300, **kw)
    assert [r.tokens for r in reqs] == base
