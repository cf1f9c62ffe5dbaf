"""Fused Pallas paged-attention decode kernel: gather + attend + write.

ONE kernel walks each slot's page table on device and does everything the
unfused serve path needed three stages for:

1. **gather** — the page table rides in as a *scalar-prefetch* operand
   (``pltpu.PrefetchScalarGridSpec``), so the k/v pool BlockSpec index
   maps read ``tables[s, p]`` directly and the pipeline streams exactly
   the slot's pages HBM→VMEM; no ``(S, T*ps, KV, hd)`` contiguous view is
   ever materialized.
2. **attend** — flash-style online softmax over the page axis (innermost
   grid dimension); the (m, l, acc) state lives in VMEM scratch that
   persists across pages of the same (slot, kv-head block). Causal
   masking is positional (``kpos <= qpos``), so null-page garbage in
   table tails and stale speculative rows are never attended.
3. **accept-masked KV write** — the ``1 + K`` window's new KV rows are
   inserted into the loaded page in-register (rows ``j < n_valid`` whose
   position falls inside the page) and every gathered page is written
   back through an output aliased onto the pool (``input_output_aliases``
   → in-place update, donated by the serve steps). The *gather* table
   doubles as the write map: row ``j`` lands in entry ``(pos+j) //
   page_size``, which the slot owns inside its footprint and which is the
   scratch page past it — reproducing ``PagePool.write_table``'s
   rollback semantics with no host-built write tables at all.

The query window ``W`` generalizes the kernel over every serve step
shape: ``W=1`` is plain decode, ``W=1+K`` is the speculative verify
window (``n_valid = 1 + k_live`` accept-masks the live draft count), and
``W=page-padded tail`` with ``S=1`` is the chunked suffix prefill for a
prefix-cache hit.

On-device page-table memory layout (pinned contract, shared with
``ref.py`` and ``serve.kv_cache.PagePool``):

* pool (one layer): ``(total_pages + 1, KV, page_size, head_dim)`` —
  page-major, so one page of ``kb`` consecutive kv heads is one
  contiguous ``(kb, page_size, head_dim)`` block. Page index
  ``total_pages`` is the scratch ("null") page; table padding points at
  it so idle slots and table tails read/write garbage there.
* ``tables (S, T)`` int32 — entry ``p`` holds the pool page owning
  absolute token positions ``[p*page_size, (p+1)*page_size)``.
* ``positions (S,)`` int32 — absolute position of window row 0.
* ``n_valid (S,)`` int32 — rows actually written (0 = idle slot).

Grid ``(S, KV // kb, T)``: one step per (slot, block of ``kb`` kv heads,
table entry). The pipeline's cost per grid step, not the page bytes, sets
a decode step's pace, so the step carries as many kv heads as fit:
:func:`kv_fold` takes the largest divisor of ``KV`` whose per-step VMEM
footprint (q, o, new-KV and pool blocks double-buffered, the f32
accumulator and scores) fits ``VMEM_BUDGET``. Decode and verify windows
fold every kv head; a long suffix-prefill window keeps a narrow block,
down to ``kb = 1``.

TPU shaping: every block's last two dims are whole array dims — pool
blocks ``(kb, page_size, head_dim)``, query/output blocks
``(kb*W*G, head_dim)`` (the ``G`` query heads of each of the ``kb`` kv
heads are flattened into rows ``h*W*G + w*G + g`` outside the kernel),
new-KV blocks ``(kb, W, head_dim)`` — which the TPU tiling rule accepts
at any page size and head dim. Attention is one block-diagonal dot pair
per step: every query row against the page's ``kb*page_size`` key rows,
scores of another kv head masked off before the softmax. The insert's
one-hot dots run only on a page that holds a window row; every other
visited page round-trips unchanged (read-modify-write through the
alias), trading one page of write bandwidth per gathered page for the
one-kernel structure; a write-window-only output spec is the follow-up
optimization.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM a grid step's blocks and f32 temporaries may take when choosing
# kv heads per step: a quarter of a v5e's 16-MiB default scoped VMEM
VMEM_BUDGET = 4 << 20


def kv_fold(kv_heads: int, window: int, groups: int, head_dim: int,
            page_size: int, itemsize: int) -> int:
    """kv heads per grid step: the largest divisor of ``kv_heads`` whose
    per-step VMEM footprint fits ``VMEM_BUDGET``, else 1."""
    for kb in range(kv_heads, 0, -1):
        if kv_heads % kb:
            continue
        rows, cols = kb * window * groups, kb * page_size
        need = (rows * head_dim * (2 * 2 * itemsize + 4)    # q, o x2; acc
                + 2 * 2 * kb * window * head_dim * itemsize  # new k/v x2
                + 4 * 2 * cols * head_dim * itemsize       # pool in/out x2
                + 2 * rows * cols * 4)                     # scores, probs
        if need <= VMEM_BUDGET:
            return kb
    return 1


def _paged_kernel(tab_ref, pos_ref, nv_ref,          # scalar prefetch
                  q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
                  o_ref, ko_ref, vo_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, page_size: int, window: int, groups: int,
                  fold: int):
    s = pl.program_id(0)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)
    pos = pos_ref[s]
    nv = nv_ref[s]
    W, ps, G, kb = window, page_size, groups, fold
    page_start = p * ps        # absolute position of the page's first row

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # unconditional writeback: the output block aliases the pool, so
    # untouched pages round-trip their own content and inserted rows land
    # in place (identical stores for pages shared across slots; the null
    # page collects garbage by contract)
    ko_ref[...] = kp_ref[...]
    vo_ref[...] = vp_ref[...]

    # accept-masked in-register KV insert, only on a page holding a window
    # row: page row t receives window row j (absolute position pos + j)
    # when pos + j - page_start == t AND j < n_valid. A (ps, W) one-hot
    # matmul keeps the select vectorized; 0/1 weights make it exact.
    @pl.when((nv > 0) & (page_start <= pos + nv - 1)
             & (pos < page_start + ps))
    def _insert():
        tt = jax.lax.broadcasted_iota(jnp.int32, (ps, W), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (ps, W), 1)
        ohf = (((pos + jj - page_start) == tt) & (jj < nv)).astype(
            jnp.float32)
        hit = jnp.max(ohf, axis=1, keepdims=True) > 0.0  # (ps, 1)
        for h in range(kb):
            for new_ref, out_ref in ((kn_ref, ko_ref), (vn_ref, vo_ref)):
                rows = jnp.dot(ohf, new_ref[0, h].astype(jnp.float32))
                out_ref[0, h] = jnp.where(
                    hit, rows.astype(out_ref.dtype), out_ref[0, h])

    def _attend():
        hd = q_ref.shape[-1]
        qf = q_ref[0, 0].astype(jnp.float32)             # (kb*W*G, hd)
        kf = ko_ref[0].astype(jnp.float32).reshape(kb * ps, hd)
        vf = vo_ref[0].astype(jnp.float32).reshape(kb * ps, hd)
        sc = jax.lax.dot_general(
            qf, kf, (((1,), (1,)), ((), ()))) * scale    # (kb*W*G, kb*ps)
        # row r is window row (r % (W*G)) // G of kv head r // (W*G);
        # column c is page row c % ps of kv head c // ps. Causal by
        # absolute position, horizon clamped to the last written row
        # (ref.py pins the same clamp: padding rows never read
        # unwritten/null-page positions); another head's keys are masked
        r = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qpos = pos + jnp.minimum((r % (W * G)) // G, nv - 1)
        kpos = page_start + c % ps
        sc = jnp.where((r // (W * G) == c // ps) & (kpos <= qpos), sc,
                       NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pe = jnp.exp(sc - m_new)
        pe = jnp.where(m_new <= NEG_INF, 0.0, pe)        # fully-masked rows
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pe, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(pe, vf)
        m_scr[...] = m_new

    # idle slots (nv == 0) and pages entirely in the future (null-padded
    # table tails) contribute nothing: skip the matmul/softmax work (the
    # zero-initialized scratch yields a zero output), keep the writeback
    pl.when((nv > 0) & (page_start <= pos + W - 1))(_attend)

    @pl.when(p == n_p - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, positions: jax.Array,
                    n_valid: jax.Array, *, page_size: int,
                    scale: float | None = None, interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shapes as in ``ref.paged_attention``; returns (out, new_k, new_v)
    with the new pool arrays aliased in place over the inputs."""
    S, W, H, hd = q.shape
    P1, KV, ps, _ = k_pages.shape
    assert ps == page_size, (ps, page_size)
    T = tables.shape[1]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    kb = kv_fold(KV, W, G, hd, ps,
                 max(q.dtype.itemsize, k_pages.dtype.itemsize))
    R = kb * W * G                 # query rows of one (slot, head block)

    # head-major layouts so one (slot, kv-head block) grid step owns one
    # block; query head g of kv head h at window row w becomes row
    # (h % kb)*W*G + w*G + g of head block h // kb
    qt = q.reshape(S, W, KV, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        S, KV // kb, R, hd)
    knt = k_new.transpose(0, 2, 1, 3)                    # (S, KV, W, hd)
    vnt = v_new.transpose(0, 2, 1, 3)

    def _slot_map(s, h, p, tab, pos, nv):
        return (s, h, 0, 0)

    def _page_map(s, h, p, tab, pos, nv):
        return (tab[s, p], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KV // kb, T),
        in_specs=[
            pl.BlockSpec((1, 1, R, hd), _slot_map),
            pl.BlockSpec((1, kb, W, hd), _slot_map),
            pl.BlockSpec((1, kb, W, hd), _slot_map),
            pl.BlockSpec((1, kb, ps, hd), _page_map),
            pl.BlockSpec((1, kb, ps, hd), _page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, hd), _slot_map),
            pl.BlockSpec((1, kb, ps, hd), _page_map),
            pl.BlockSpec((1, kb, ps, hd), _page_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
    )
    o, nk, nv_out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=ps,
                          window=W, groups=G, fold=kb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, KV // kb, R, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # pool arrays update in place (operand index counts the 3 scalar-
        # prefetch args: k_pages is operand 6, v_pages operand 7)
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
        name="paged_attention",
    )(tables, positions, n_valid, qt, knt, vnt, k_pages, v_pages)
    o = o.reshape(S, KV, W, G, hd).transpose(0, 2, 1, 3, 4)
    return o.reshape(S, W, H, hd), nk, nv_out
