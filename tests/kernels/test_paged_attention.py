"""Fused paged-attention parity: interpret-mode kernel vs pure-jnp ref,
and the ref vs an independent dense oracle.

Covers the shapes the serve engine actually dispatches — W=1 decode,
W=1+K verify windows (K = 0..n_draft), page-padded suffix prefill —
plus the write-side contract: accept-masked rows land in real pages,
rejected/padded rows only ever touch the scratch page, untouched pages
round-trip bit-exactly, and idle slots (n_valid=0) write nothing and
output zeros.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import kernel as pa_kernel, ref as pa_ref

TOL = dict(atol=2e-5, rtol=2e-5)

# (KV, G, hd): the small shape first, then kv-head counts, group sizes
# and head dims of the served models, so the kernel folds up to eight kv
# heads into one grid step at a lane-wide (128) and a ragged (120) head
# dim; ``ids`` keep the small shape's cases under their names
SMALL = (2, 2, 16)
FOLDED = [(2, 4, 128), (8, 8, 128), (8, 4, 120)]
PS_HEADS = pytest.mark.parametrize(
    "ps,heads",
    [(4, SMALL), (8, SMALL)] + [(8, h) for h in FOLDED],
    ids=["4", "8"] + [f"8-kv{kv}g{g}hd{hd}" for kv, g, hd in FOLDED])


def _scenario(seed, S, W, H, KV, hd, ps, T, n_real, positions, n_valid,
              tables=None):
    """Random pool + per-slot tables. ``tables=None`` builds disjoint
    footprints covering each slot's positions, null-padded past them."""
    rng = np.random.default_rng(seed)
    null = n_real
    if tables is None:
        tables = np.full((S, T), null, np.int32)
        nxt = 0
        for s in range(S):
            need = min(T, -(-(positions[s] + W) // ps))
            for e in range(need):
                tables[s, e] = nxt % n_real
                nxt += 1
    args = dict(
        q=jnp.asarray(rng.normal(size=(S, W, H, hd)), jnp.float32),
        k_new=jnp.asarray(rng.normal(size=(S, W, KV, hd)), jnp.float32),
        v_new=jnp.asarray(rng.normal(size=(S, W, KV, hd)), jnp.float32),
        k_pages=jnp.asarray(rng.normal(size=(n_real + 1, KV, ps, hd)),
                            jnp.float32),
        v_pages=jnp.asarray(rng.normal(size=(n_real + 1, KV, ps, hd)),
                            jnp.float32),
        tables=jnp.asarray(tables),
        positions=jnp.asarray(positions, jnp.int32),
        n_valid=jnp.asarray(n_valid, jnp.int32),
    )
    return args, np.asarray(tables), null


def _compare(args, null):
    ps = args["k_pages"].shape[2]
    o_r, k_r, v_r = pa_ref.paged_attention(**args, page_size=ps)
    o_k, k_k, v_k = pa_kernel.paged_attention(
        **args, page_size=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(o_r), np.asarray(o_k), **TOL)
    # pools bit-exact on every REAL page (the scratch page is garbage by
    # contract: the ref parks rejected rows there, the kernel does not)
    np.testing.assert_array_equal(np.asarray(k_r)[:null], np.asarray(k_k)[:null])
    np.testing.assert_array_equal(np.asarray(v_r)[:null], np.asarray(v_k)[:null])
    return o_k, k_k, v_k


@PS_HEADS
@pytest.mark.parametrize("K", [0, 1, 2, 3])
def test_verify_window_parity(ps, heads, K):
    """1+K verify windows at ragged per-slot positions, incl. an idle
    slot and a slot with a rejected tail (n_valid < W)."""
    KV, G, hd = heads
    W = 1 + 3  # engine compiles one W for every slot; n_valid masks K
    positions = [ps + 1, 3 * ps - 1, 0, 2 * ps]
    n_valid = [1 + K, max(1, K), 0, 1 + K]
    args, tables, null = _scenario(
        0, 4, W, KV * G, KV, hd, ps, 6, 12, positions, n_valid)
    _compare(args, null)


@PS_HEADS
def test_decode_parity(ps, heads):
    """W=1 plain decode, positions straddling page boundaries."""
    KV, G, hd = heads
    positions = [0, ps - 1, ps, 2 * ps + 1]
    args, tables, null = _scenario(
        1, 4, 1, KV * G, KV, hd, ps, 4, 10, positions, [1, 1, 1, 1])
    _compare(args, null)


def test_suffix_prefill_parity():
    """S=1 page-padded suffix window (n_valid = real tail < W)."""
    ps, tail = 8, 13
    W = 16  # padded to a page multiple
    args, tables, null = _scenario(2, 1, W, 4, 2, 16, ps, 6, 5, [8], [tail])
    _compare(args, null)


def test_suffix_prefill_parity_narrow_fold():
    """A suffix window too wide to fold every kv head walks blocks of
    fewer heads (1 < kb < KV) with the same results."""
    ps, tail, W, KV, G, hd = 8, 50, 64, 8, 8, 128
    kb = pa_kernel.kv_fold(KV, W, G, hd, ps, 4)
    assert 1 < kb < KV
    args, tables, null = _scenario(2, 1, W, KV * G, KV, hd, ps, 10, 12,
                                   [8], [tail])
    _compare(args, null)


@pytest.mark.parametrize("W,G,hd,itemsize", [
    (1, 8, 128, 2), (4, 8, 128, 2), (1, 4, 120, 2), (1, 3, 64, 2),
    (16, 3, 64, 2), (1024, 4, 128, 2), (64, 8, 128, 4)])
@pytest.mark.parametrize("KV", [1, 2, 4, 6, 8])
def test_kv_fold_divides_and_fits(KV, W, G, hd, itemsize):
    """kb is always a divisor of KV; decode and verify windows fold every
    kv head; a 1024-row suffix window at hd 128 folds fewer."""
    kb = pa_kernel.kv_fold(KV, W, G, hd, 16, itemsize)
    assert 1 <= kb <= KV and KV % kb == 0
    if W <= 4:
        assert kb == KV
    if W == 1024 and KV > 1:
        assert kb < KV


@pytest.mark.parametrize("heads", FOLDED,
                         ids=[f"kv{kv}g{g}hd{hd}" for kv, g, hd in FOLDED])
def test_folded_pool_writes_only_window_rows(heads):
    """With every kv head in one grid step, exactly the live slots'
    window rows change in the real pages, for every kv head; an idle
    slot's footprint is untouched."""
    KV, G, hd = heads
    ps, W = 8, 4
    positions, n_valid = [ps - 2, 2 * ps + 3, ps], [3, 0, 4]
    args, tables, null = _scenario(8, 3, W, KV * G, KV, hd, ps, 5, 14,
                                   positions, n_valid)
    _, k_k, v_k = _compare(args, null)
    expect = np.zeros((null, KV, ps), bool)
    for s in range(3):
        for j in range(n_valid[s]):
            at = positions[s] + j
            expect[tables[s, at // ps], :, at % ps] = True
    for new, old, rows in ((k_k, args["k_pages"], args["k_new"]),
                           (v_k, args["v_pages"], args["v_new"])):
        new, old = np.asarray(new)[:null], np.asarray(old)[:null]
        changed = np.any(new != old, axis=-1)
        np.testing.assert_array_equal(changed, expect)
        for s in range(3):
            for j in range(n_valid[s]):
                at = positions[s] + j
                np.testing.assert_array_equal(
                    new[tables[s, at // ps], :, at % ps],
                    np.asarray(rows)[s, j])


def test_idle_slot_writes_nothing_outputs_zero():
    ps = 8
    args, tables, null = _scenario(3, 2, 2, 4, 2, 16, ps, 3, 4,
                                   [5, 9], [0, 0])
    o, k_k, v_k = _compare(args, null)
    assert np.all(np.asarray(o) == 0)
    np.testing.assert_array_equal(np.asarray(k_k)[:null],
                                  np.asarray(args["k_pages"])[:null])


def test_accept_masked_rows_only_touch_scratch():
    """Rows j >= n_valid must not modify any REAL page; rows j < n_valid
    land exactly at (pos+j) in the slot's footprint."""
    ps, W, nv = 4, 4, 2
    pos = 3  # rows at positions 3,4,5,6 span a page boundary
    args, tables, null = _scenario(4, 1, W, 4, 2, 16, ps, 4, 6,
                                   [pos], [nv])
    _, k_k, _ = _compare(args, null)
    k_k = np.asarray(k_k)
    kp = np.asarray(args["k_pages"])
    kn = np.asarray(args["k_new"])
    for j in range(W):
        p = tables[0, (pos + j) // ps]
        row = (pos + j) % ps
        if j < nv:
            np.testing.assert_array_equal(k_k[p, :, row], kn[0, j])
        else:
            np.testing.assert_array_equal(k_k[p, :, row], kp[p, :, row])


def test_shared_page_read_only():
    """Two slots gathering one shared prefix page leave it bit-exact."""
    ps, W = 4, 2
    tables = np.array([[0, 1, 3, 3], [0, 2, 3, 3]], np.int32)
    args, tables, null = _scenario(5, 2, W, 4, 2, 16, ps, 4, 3,
                                   [ps + 1, ps], [2, 2], tables=tables)
    _, k_k, _ = _compare(args, null)
    np.testing.assert_array_equal(np.asarray(k_k)[0],
                                  np.asarray(args["k_pages"])[0])


def test_ref_matches_dense_oracle():
    """Triangulate: the ref (and therefore the kernel) reproduces plain
    dense causal attention computed on the contiguous history."""
    rng = np.random.default_rng(6)
    S, W, H, KV, hd, ps, T = 2, 3, 4, 2, 16, 4, 4
    G = H // KV
    hist_len = [6, 9]  # positions already in the pool, then W new rows
    n_real, null = 6, 6
    tables = np.full((S, T), null, np.int32)
    tables[0, :3] = [0, 1, 2]
    tables[1, :3] = [3, 4, 5]
    kp = np.zeros((n_real + 1, KV, ps, hd), np.float32)
    vp = np.zeros((n_real + 1, KV, ps, hd), np.float32)
    hist_k = [rng.normal(size=(hl, KV, hd)).astype(np.float32)
              for hl in hist_len]
    hist_v = [rng.normal(size=(hl, KV, hd)).astype(np.float32)
              for hl in hist_len]
    for s in range(S):
        for t in range(hist_len[s]):
            kp[tables[s, t // ps], :, t % ps] = hist_k[s][t]
            vp[tables[s, t // ps], :, t % ps] = hist_v[s][t]
    q = rng.normal(size=(S, W, H, hd)).astype(np.float32)
    kn = rng.normal(size=(S, W, KV, hd)).astype(np.float32)
    vn = rng.normal(size=(S, W, KV, hd)).astype(np.float32)
    o, _, _ = pa_ref.paged_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(hist_len, jnp.int32), jnp.asarray([W, W], jnp.int32),
        page_size=ps)
    for s in range(S):
        full_k = np.concatenate([hist_k[s], kn[s]])   # (L+W, KV, hd)
        full_v = np.concatenate([hist_v[s], vn[s]])
        for j in range(W):
            L = hist_len[s] + j + 1                   # causal horizon
            for h in range(H):
                kv = h // G
                sc = (q[s, j, h] @ full_k[:L, kv].T) * hd ** -0.5
                p = np.exp(sc - sc.max()); p /= p.sum()
                np.testing.assert_allclose(
                    np.asarray(o)[s, j, h], p @ full_v[:L, kv], **TOL)


def test_kernel_jits_and_is_deterministic():
    ps = 4
    args, tables, null = _scenario(7, 2, 2, 4, 2, 16, ps, 3, 5,
                                   [2, 5], [2, 1])
    f = jax.jit(lambda **kw: pa_kernel.paged_attention(
        **kw, page_size=ps, interpret=True))
    o1, k1, v1 = f(**args)
    o2, k2, v2 = f(**args)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
