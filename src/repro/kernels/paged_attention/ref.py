"""Pure-jnp reference for fused paged attention (scatter → gather → attend).

Semantically identical to the Pallas kernel and numerically identical to
the unfused serve path (``steps._gather_pages`` + ``decode_attention``):
new KV rows are scattered into the pool first, the page tables gather a
contiguous per-slot view, and grouped-einsum GQA attention runs over it
with per-query-row causal masking by absolute position — fp32 scores,
``-1e30`` mask value, softmax in fp32 cast back to the compute dtype.

On-device page-table layout (the contract `kernel.py` pins too):

* pool (one layer): ``(total_pages + 1, KV, page_size, head_dim)``;
  index ``total_pages`` is the scratch ("null") page.
* ``tables (S, T)``: entry ``p`` of a slot's row is the pool page holding
  absolute positions ``[p*page_size, (p+1)*page_size)``; entries past the
  slot's footprint are the null page.
* ``positions (S,)``: absolute position of window row 0 per slot.
* ``n_valid (S,)``: window rows actually WRITTEN per slot — 0 for idle
  slots, 1 for plain decode, ``1 + k_live`` for a verify window, the real
  tail length for suffix prefill. Rows past ``n_valid`` land in the
  scratch page (accept-masked write / rollback).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, positions: jax.Array,
                    n_valid: jax.Array, *, page_size: int,
                    scale: float | None = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q: (S, W, H, hd); k_new/v_new: (S, W, KV, hd);
    k_pages/v_pages: (P+1, KV, ps, hd); tables: (S, T) int32;
    positions/n_valid: (S,) int32.

    Returns ``(out (S, W, H, hd), new_k_pages, new_v_pages)``.
    """
    S, W, H, hd = q.shape
    P1, KV, ps, _ = k_pages.shape
    T = tables.shape[1]
    G = H // KV
    null = P1 - 1
    scale = hd ** -0.5 if scale is None else scale

    # ---- accept-masked scatter of the new KV rows into the pool.
    # Window row j of slot s holds absolute position pos_s + j; its write
    # target is the table entry owning that position. Rows past n_valid
    # are redirected to the scratch page (collisions there are garbage by
    # contract), so rejected/padded rows can never touch a real page.
    offs = jnp.arange(W, dtype=jnp.int32)
    pos_j = positions[:, None] + offs[None, :]              # (S, W)
    entry = jnp.clip(pos_j // ps, 0, T - 1)
    page = jnp.take_along_axis(tables, entry, axis=1)       # (S, W)
    valid = offs[None, :] < n_valid[:, None]
    page = jnp.where(valid, page, null)
    page, row = page.reshape(-1), (pos_j % ps).reshape(-1)
    new_k = k_pages.at[page, :, row].set(k_new.reshape(S * W, KV, hd))
    new_v = v_pages.at[page, :, row].set(v_new.reshape(S * W, KV, hd))

    # ---- gather each slot's contiguous view and attend (grouped GQA,
    # exactly decode_attention's math on the gathered cache)
    gk = new_k[tables].swapaxes(2, 3).reshape(S, T * ps, KV, hd)
    gv = new_v[tables].swapaxes(2, 3).reshape(S, T * ps, KV, hd)
    qg = q.reshape(S, W, KV, G, hd)
    scores = jnp.einsum("swkgd,stkd->skgwt", qg, gk).astype(jnp.float32) \
        * scale
    idx = jnp.arange(T * ps, dtype=jnp.int32)
    # causal horizon clamped to the last WRITTEN position: rows past
    # n_valid attend as if they were row n_valid - 1, so no row ever
    # reads unwritten positions (which only null-page entries cover);
    # idle slots (n_valid == 0) are fully masked and output zeros —
    # the kernel pins the same clamps, making padding rows deterministic
    qpos = jnp.where(n_valid[:, None] > 0,
                     positions[:, None] + jnp.minimum(offs, n_valid[:, None] - 1),
                     -1)
    mask = idx[None, None, :] <= qpos[:, :, None]           # (S, W, T*ps)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    alive = mask.any(axis=-1)                               # (S, W)
    probs = jnp.where(alive[:, None, None, :, None], probs, 0.0)
    o = jnp.einsum("skgwt,stkd->swkgd", probs, gv)
    return o.reshape(S, W, H, hd), new_k, new_v
