"""Fused-vs-unfused serve engine: token identity and page hygiene.

The fused paged steps (one ``lm_paged_decode`` call through the
paged-attention kernel) must produce EXACTLY the token streams of the
unfused gather/scatter steps and of dense-cache serving, on randomized
workloads mixing ragged prompts, prefix-cache hits, budgets that retire
slots mid-batch, and speculative verify windows — with every page back
in the pool afterwards.
"""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.kernels import impl as impl_mod
from repro.models import lm
from repro.serve import Request, ServeEngine
from repro.serve.steps import greedy_generate


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, prompts, news, *, impl=None, **kw):
    eng = ServeEngine(cfg, params, max_batch=3, max_cache_len=64,
                      page_size=8, total_pages=24, max_seq_len=48, **kw)
    try:
        reqs = [Request(p, n) for p, n in zip(prompts, news)]
        for r in reqs:
            eng.submit(r)
        eng.close_intake()
        if impl:
            with impl_mod.use_impl(impl):
                eng.run(timeout=600)
        else:
            eng.run(timeout=600)
        if eng.pool is not None:
            assert eng.pool.pages_in_use == 0, "leaked pages"
        return [r.tokens for r in reqs], eng.metrics()
    finally:
        eng.shutdown()


def _workload(seed, n_req=5):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 500, size=int(rng.integers(4, 28))).tolist()
               for _ in range(n_req)]
    news = [int(rng.integers(2, 14)) for _ in range(n_req)]
    return prompts, news


@pytest.mark.parametrize("seed", [0, 1])
def test_fused_matches_unfused_and_dense(small_model, seed):
    cfg, params = small_model
    prompts, news = _workload(seed)
    fused, mf = _serve(cfg, params, prompts, news, paged=True, fused=True)
    unfused, mu = _serve(cfg, params, prompts, news, paged=True, fused=False)
    dense, _ = _serve(cfg, params, prompts, news, paged=False)
    assert fused == unfused == dense
    assert mf["fused"] and not mu["fused"]


def test_fused_matches_greedy_oracle(small_model):
    cfg, params = small_model
    prompts, news = _workload(2, n_req=3)
    fused, _ = _serve(cfg, params, prompts, news, paged=True, fused=True)
    for p, n, got in zip(prompts, news, fused):
        want = list(map(int, greedy_generate(
            cfg, params, np.asarray(p, np.int32)[None, :], n,
            max_cache_len=64)[0]))
        assert got == want


def test_fused_prefix_cache_hit_identical(small_model):
    """Shared page-aligned prefixes route through the fused suffix step."""
    cfg, params = small_model
    base = list(range(1, 17))  # 16 tokens = 2 full pages at ps=8
    prompts = [base + [100, 101], base + [200], base + [300, 301, 302]]
    news = [6, 5, 4]
    fused, mf = _serve(cfg, params, prompts, news, paged=True, fused=True)
    unfused, _ = _serve(cfg, params, prompts, news, paged=True, fused=False)
    assert fused == unfused
    assert mf["suffix_steps"] > 0  # the fused suffix path actually ran


def test_fused_speculative_identical(small_model):
    cfg, params = small_model
    rng = np.random.default_rng(3)
    base = rng.integers(1, 40, size=6).tolist()
    prompts = [base * 3, (base * 2)[:10], base * 4]
    news = [10, 7, 12]
    kw = dict(max_batch=3, max_cache_len=96, page_size=8,
              total_pages=40, max_seq_len=90)

    def run(**extra):
        eng = ServeEngine(cfg, params, **kw, **extra)
        try:
            reqs = [Request(p, n) for p, n in zip(prompts, news)]
            for r in reqs:
                eng.submit(r)
            eng.close_intake()
            eng.run(timeout=600)
            assert eng.pool.pages_in_use == 0
            return [r.tokens for r in reqs], eng.metrics()
        finally:
            eng.shutdown()

    plain, _ = run(paged=True, fused=True)
    spec_f, mf = run(paged=True, fused=True, speculate=3)
    spec_u, mu = run(paged=True, fused=False, speculate=3)
    assert spec_f == plain == spec_u
    # same schedule too: the fused verify accepts exactly what unfused did
    assert mf["draft_accepted"] == mu["draft_accepted"]
    assert mf["draft_proposed"] == mu["draft_proposed"]


def test_fused_interpret_kernel_identical(small_model):
    """The Pallas kernel body (interpret mode) drives the engine to the
    same tokens as the jnp reference path — the CPU-side proof the TPU
    lowering computes the serve semantics."""
    cfg, params = small_model
    prompts, news = _workload(4, n_req=2)
    ref, _ = _serve(cfg, params, prompts, news, paged=True, fused=True)
    interp, _ = _serve(cfg, params, prompts, news, paged=True, fused=True,
                       impl="pallas_interpret")
    assert interp == ref


def test_fused_requires_paged(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError, match="fused"):
        ServeEngine(cfg, params, paged=False, fused=True)


def test_device_table_cache_incremental(small_model):
    """Placements/evictions refresh only dirty rows of the device-resident
    table mirror; the mirror always equals the host tables at dispatch."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=3, max_cache_len=64,
                      page_size=8, total_pages=24, max_seq_len=48,
                      paged=True, fused=True)
    try:
        reqs = [Request(list(range(1, 10)), 3) for _ in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.close_intake()
        while not (eng.batcher.closed and eng.idle):
            eng.step()
            if eng._tables_dev is not None and not eng._tables_dirty:
                np.testing.assert_array_equal(
                    np.asarray(eng._tables_dev), eng._tables)
        assert eng.pool.pages_in_use == 0
        # post-run: evictions marked their rows dirty; a final refresh
        # converges the mirror to the all-null host state
        np.testing.assert_array_equal(
            np.asarray(eng._device_tables()), eng._tables)
        assert not eng._tables_dirty
    finally:
        eng.shutdown()


@pytest.mark.parametrize("fused", [True, False])
def test_step_programs_lower_under_their_own_names(small_model, fused):
    """Decode, suffix prefill and verify are three programs, and a
    profiler trace names each apart: ``jit_step`` is the decode step
    alone."""
    import jax.numpy as jnp
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=3, max_cache_len=64,
                      page_size=8, total_pages=24, max_seq_len=48,
                      speculate=2, fused=fused)
    try:
        eng._ensure_state()
        S, T, K = eng.max_batch, eng._table_pages, eng.speculate
        pool = eng.pool.arrays

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        if fused:
            decode = (i32(S, 1, 1), i32(S), i32(S, T), i32(S))
            suffix = (i32(1, 8), i32(1), i32(1, T), i32(1))
            verify = (i32(S, 1, 1 + K), i32(S), i32(S, T), i32(S))
        else:
            decode = (i32(S, 1, 1), i32(S), i32(S, T))
            suffix = (i32(1, 8), i32(), i32(T), i32(T))
            verify = (i32(S, 1, 1 + K), i32(S), i32(S, T),
                      i32(S, eng._verify_pages), i32(S))
        for name, fn, args in (("jit_step", eng._decode_fn, decode),
                               ("jit_suffix_step", eng._suffix_fn, suffix),
                               ("jit_verify_step", eng._verify_fn, verify)):
            text = fn.lower(params, pool, *args).as_text()
            assert f"module @{name} " in text, name
    finally:
        eng.shutdown()
