"""Dispatching wrapper for attention: xla | pallas | pallas_interpret."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import impl as impl_mod
from repro.kernels.flash_attention import kernel, ref


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset: int = 0, scale: Optional[float] = None,
              impl: str | None = None, lean: bool = False,
              block_q: int = 512, block_k: int = 512) -> jax.Array:
    impl = impl_mod.resolve(impl)
    if impl == "xla":
        return ref.attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale, lean=lean)
    return kernel.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k,
        interpret=(impl == "pallas_interpret"))
