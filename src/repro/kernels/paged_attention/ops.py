"""Dispatching wrapper for fused paged attention: xla | pallas | interpret.

``xla`` runs the pure-jnp reference (identical semantics, including the
in-pool scatter); the Pallas kernel serves ``pallas`` and
``pallas_interpret``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels import impl as impl_mod
from repro.kernels.paged_attention import kernel, ref


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, positions: jax.Array,
                    n_valid: jax.Array, *, page_size: int,
                    scale: Optional[float] = None,
                    impl: str | None = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused gather→attend→write over the paged KV pool.

    Shapes: q ``(S, W, H, hd)``, k_new/v_new ``(S, W, KV, hd)``,
    k_pages/v_pages ``(P+1, KV, ps, hd)``, tables ``(S, T)`` int32,
    positions/n_valid ``(S,)`` int32. Returns
    ``(out (S, W, H, hd), new_k_pages, new_v_pages)``.
    """
    impl = impl_mod.resolve(impl)
    if impl == "xla":
        return ref.paged_attention(
            q, k_new, v_new, k_pages, v_pages, tables, positions, n_valid,
            page_size=page_size, scale=scale)
    return kernel.paged_attention(
        q, k_new, v_new, k_pages, v_pages, tables, positions, n_valid,
        page_size=page_size, scale=scale,
        interpret=(impl == "pallas_interpret"))
