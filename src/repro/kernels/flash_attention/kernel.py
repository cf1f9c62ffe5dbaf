"""Pallas TPU flash attention (fwd) with causal / sliding-window / GQA.

TPU adaptation notes (DESIGN.md §2): tiles are shaped for the MXU
(block_q × block_k matmuls with head_dim as the lane axis, all multiples of
128 at production sizes) and the online-softmax state (m, l and the output
accumulator) lives in VMEM scratch that persists across the sequential TPU
grid — the kv-block axis is the innermost grid dimension, so each (batch,
head, q-block) revisits its accumulator while streaming KV tiles HBM→VMEM.

VMEM working set per step ≈ (block_q·D) q + 2·(block_k·D) kv +
(block_q·block_k) scores + (block_q·D) acc, all fp32 ≤ ~2 MB at
block_q = block_k = 512, D = 128 — comfortably inside 16 MB, leaving room
for double-buffered pipelining of the KV stream.

Fully-masked KV tiles are skipped via ``@pl.when`` on block-index
arithmetic: causal skips ki·bk > (qi+1)·bq; sliding-window additionally
skips tiles older than the window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, q_offset: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_offset          # absolute pos of first query
    k_start = ki * block_k

    def _not_skipped():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale      # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len                   # tail padding
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                    # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # guard fully-masked rows (exp(NEG_INF - NEG_INF) = 1 otherwise)
        p = jnp.where(m_new <= NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    if causal or window > 0:
        skip = jnp.array(False)
        if causal:  # tile entirely in the future
            skip |= k_start > q_start + block_q - 1
        if window > 0:  # tile entirely before every query's window
            skip |= (k_start + block_k - 1) <= (q_start - window)
        pl.when(~skip)(_not_skipped)
    else:
        _not_skipped()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _pick_block(seq: int, want: int) -> int:
    b = min(seq, want)
    while seq % b:
        b -= 1
    return b


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_offset", "block_q",
                     "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D);  k, v: (B, Sk, KV, D). Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    groups = H // KV
    scale = D ** -0.5 if scale is None else scale
    bq = _pick_block(Sq, block_q)
    # pad kv length to a block multiple; padding masked via kv_len
    bk = min(block_k, max(Sk, 1))
    pad = (-Sk) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Skp = Sk + pad

    # (B, S, H, D) → (B, H, S, D) blocks; kv head index = h // groups
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, Sq // bq, Skp // bk)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, q_offset=q_offset, kv_len=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, g=groups: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, g=groups: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            # fp32 online-softmax state persisted across the kv grid axis
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
