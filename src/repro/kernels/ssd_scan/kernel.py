"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation (DESIGN.md §2): the GPU Mamba-2 kernel leans on warp-level
shuffles for the intra-chunk scan; here the chunk decomposition is recast as
MXU matmuls — the (Q×Q) intra-chunk decay-weighted score matrix, the (Q×N)
state projection and the (P×N) running state are all dense tiles. The
running state lives in fp32 VMEM scratch and is carried across the
*innermost, sequential* chunk axis of the grid, so state passing costs no
HBM traffic.

Grid: (B, H, T/Q) with chunk innermost. The per-head scalars ``A`` and
``D`` sit whole in SMEM and are read at the head's grid index. ``dt``
arrives twice, as a (Q, 1) column and a (1, Q) row, so the kernel needs
no transpose; the inclusive cumulative sum of ``dt·A`` is a masked
reduction of the (Q, Q) triangle in both orientations (Mosaic has no
cumsum). Per step the kernel holds x (Q,P), B/C (Q,N), scores (Q,Q) and
state (P,N) in VMEM — ≈ 1 MB fp32 at Q=256, P=64, N=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                state_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    h = pl.program_id(1)
    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    dt_c = dtc_ref[0, 0].astype(jnp.float32)   # (Q, 1)
    dt_r = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    bm = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    a = a_ref[h]                               # scalar (per head), SMEM
    d = d_ref[h]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    # inclusive cumsum of dA = dt·A as a column (over j <= i) and a row
    # (over i <= j)
    cum_c = jnp.sum(jnp.where(lower, dt_r * a, 0.0), axis=1, keepdims=True)
    cum_r = jnp.sum(jnp.where(ii <= jj, dt_c * a, 0.0), axis=0,
                    keepdims=True)
    total = jnp.sum(dt_c * a, axis=0, keepdims=True)             # (1, 1)

    # intra-chunk: scores[i,j] = C_i·B_j · exp(cum_i - cum_j) · dt_j, i ≥ j
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))   # (Q, Q)
    l_mat = jnp.where(lower, jnp.exp(cum_c - cum_r), 0.0)
    scores = cb * l_mat * dt_r
    y = jnp.dot(scores, x)                                       # (Q, P)

    # inter-chunk: y += exp(cum_i) · C_i · state_inᵀ
    state_in = state_scr[...]                  # (P, N)
    y += jnp.exp(cum_c) * jax.lax.dot_general(
        cm, state_in, (((1,), (1,)), ((), ())))

    # state update: state' = exp(total)·state + Σ_j dt_j e^{total-cum_j} x_jᵀB_j
    w = dt_c * jnp.exp(total - cum_c)                            # (Q, 1)
    state_scr[...] = state_in * jnp.exp(total) + jax.lax.dot_general(
        x * w, bm, (((0,), (0,)), ((), ())))                     # (P, N)

    y_ref[0, 0] = (y + x * d).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool = False):
    """Shapes as in ``ref.py``; returns y (B, T, H, P)."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert T % chunk == 0
    hpg = H // G
    nC = T // chunk

    xt = x.transpose(0, 2, 1, 3)                       # (B, H, T, P)
    dth = dt.transpose(0, 2, 1)                        # (B, H, T)
    dtc = dth[..., None]                               # (B, H, T, 1)
    dtr = dth.reshape(B_, H, nC, 1, chunk)             # (B, H, nC, 1, Q)
    bt = Bm.transpose(0, 2, 1, 3)                      # (B, G, T, N)
    ct = Cm.transpose(0, 2, 1, 3)

    grid = (B_, H, nC)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, g=hpg: (b, h // g, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, g=hpg: (b, h // g, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B_, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xt, dtc, dtr, A.astype(jnp.float32), bt, ct, D.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3)
