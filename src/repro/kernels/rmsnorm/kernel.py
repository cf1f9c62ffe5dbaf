"""Pallas TPU kernel: fused RMSNorm over the last axis.

Tiling: rows are flattened to (R, D); the grid walks row blocks. Each step
holds a (block_rows, D) tile of x plus the (1, D) scale in VMEM, computes
the fp32 row-wise rsqrt(mean-square) and writes the scaled tile. D is kept
whole per tile. ``block_rows`` is a multiple of 8 (the sublane tile) and
the tile stays under ~4 MB of VMEM; the row count is zero-padded up to a
whole number of blocks and the padding sliced off the result, so any row
count (a 13-token prompt, a decode batch of 12) compiles. Zero rows
normalize to zeros, never NaN (``rsqrt(0 + eps)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANE = 8


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                     # (bR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def pick_block_rows(n_rows: int, d: int, budget_bytes: int = 4 << 20) -> int:
    """Rows per block: the fewest blocks whose tiles (fp32 in + out, ``8 *
    d`` bytes a row) fit the budget, each a multiple of the sublane tile."""
    cap = max(SUBLANE, budget_bytes // (d * 8) // SUBLANE * SUBLANE)
    n_blocks = -(-n_rows // cap)
    per_block = -(-n_rows // n_blocks)
    return -(-per_block // SUBLANE) * SUBLANE


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
            interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    rows = xf.shape[0]
    br = pick_block_rows(rows, d)
    pad = -rows % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, scale.reshape(1, d))
    return out[:rows].reshape(orig_shape)
