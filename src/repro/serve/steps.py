"""Serving step factories: prefill / decode / paged variants.

``decode_32k`` and ``long_500k`` lower ``serve_step`` — one new token
against a KV cache (or SSM state) of the shape's sequence length — NOT a
training step (assignment note). Caches are donated by the drivers so the
update is in-place on device.

The ``make_paged_*`` factories are the jitted half of the paged KV cache
(``serve.kv_cache``): inside the step, a slot's page table gathers its
pages into a contiguous per-slot view, the ordinary ``lm_decode_step``
runs against it, and only the one page containing the written position
scatters back to the pool — shared prefix pages are read, never written.
Shapes are static and bounded: tables are null-page padded to
``max_pages`` (decode/scatter compile once) and suffix tails are padded
to a page multiple by the engine (at most ``max_pages`` suffix shapes).

The jitted function's name is the program's name in a profiler trace:
decode steps are ``step`` (``jit_step``), suffix prefills
``suffix_step`` and speculative verifies ``verify_step``, so each
program's device time reads apart.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, lm
from repro.models.common import AUDIO, ModelConfig
from repro.serve.kv_cache import pages_for


def make_prefill_step(cfg: ModelConfig, max_cache_len: int) -> Callable:
    """(params, batch) → (next-token logits, primed cache/state)."""
    if cfg.family == AUDIO:
        def prefill(params, batch):
            # whisper prefill_32k = encoder forward over 32k frames +
            # decoder state init (cross-KV precompute)
            state = encdec.init_decode_state(params, batch["audio_embed"],
                                             cfg, max_cache_len)
            bos = jnp.zeros((batch["audio_embed"].shape[0], 1), jnp.int32)
            logits, state = encdec.encdec_decode_step(
                params, bos, cfg, state, jnp.zeros((), jnp.int32))
            return logits, state
        return prefill

    def prefill(params, batch):
        B = batch["tokens"].shape[0]
        cache = lm.init_cache(cfg, B, max_cache_len)
        return lm.lm_prefill(params, batch, cfg, cache)
    return prefill


def make_decode_step(cfg: ModelConfig) -> Callable:
    """(params, cache, token, pos) → (logits, new cache). One token."""
    if cfg.family == AUDIO:
        def decode(params, cache, token, pos):
            return encdec.encdec_decode_step(params, token, cfg, cache, pos)
        return decode

    def decode(params, cache, token, pos):
        return lm.lm_decode_step(params, token, cfg, cache, pos)
    return decode


def make_batched_decode_step(cfg: ModelConfig) -> Callable:
    """Dense-mode slot decode with per-step token surfacing.

    ``(params, caches(S,...), tokens(S,1,1), positions(S,)) →
    (next_tokens(S,1), new caches)`` — one vmapped decode step over every
    slot with the greedy argmax fused *inside* the jitted step, so the
    engine's step-completion continuation receives the accepted tokens
    directly (a vocab-times smaller transfer than logits, and one fewer
    dispatch on the per-token critical path the streaming API rides).
    """
    decode_one = make_decode_step(cfg)

    def step(params, caches, tokens, positions):
        logits, new_caches = jax.vmap(
            decode_one, in_axes=(None, 0, 0, 0))(params, caches, tokens,
                                                 positions)
        nxt = jnp.argmax(logits[:, :, -1, :], axis=-1).astype(jnp.int32)
        return nxt, new_caches
    return step


# ------------------------------------------------------------- paged steps

def _pool_pages(c: jax.Array, page_size: int) -> jax.Array:
    """Contiguous cache rows -> pool pages: (L, n*ps, KV, hd) ->
    (L, n, KV, ps, hd), the pool's page-major, kv-head-major layout."""
    L = c.shape[0]
    return c.reshape((L, -1, page_size) + c.shape[2:]).swapaxes(2, 3)


def _gather_pages(pool: Dict[str, jax.Array], table: jax.Array,
                  page_size: int) -> Dict[str, jax.Array]:
    """Page table -> contiguous per-slot cache view.

    pool leaf: (L, total_pages+1, KV, page_size, hd); table: (max_pages,)
    -> (L, 1, max_pages*page_size, KV, hd), i.e. a batch-1 stacked cache
    exactly as ``lm_decode_step`` expects. Null-padded table tails gather
    scratch-page garbage, which the decode mask (idx <= pos) zeroes out.
    """
    def one(p):
        g = p[:, table].swapaxes(2, 3)        # (L, max_pages, ps, KV, hd)
        L, n_pages = g.shape[0], g.shape[1]
        return g.reshape((L, n_pages * page_size) + g.shape[3:])[:, None]
    return jax.tree_util.tree_map(one, pool)


def _written_page(new_cache: Dict[str, jax.Array], pos: jax.Array,
                  page_size: int) -> Dict[str, jax.Array]:
    """Slice the page containing ``pos`` out of the contiguous view, in
    pool layout (L, KV, ps, hd)."""
    pi = (pos // page_size).astype(jnp.int32)
    return jax.tree_util.tree_map(
        lambda c: _pool_pages(jax.lax.dynamic_slice_in_dim(
            c[:, 0], pi * page_size, page_size, axis=1), page_size)[:, 0],
        new_cache)


def make_paged_decode_step(cfg: ModelConfig, page_size: int, *,
                           return_tokens: bool = False) -> Callable:
    """(params, pool, tokens(S,1,1), positions(S,), tables(S,max_pages))
    → (logits(S,1,1,V), new pool). One token for every slot.

    ``return_tokens=True`` surfaces the greedy next tokens instead:
    → (next_tokens(S,1), new pool), with the argmax fused into the step
    (same per-step token surfacing as ``make_batched_decode_step`` — the
    serving engine's continuations deliver tokens straight from it)."""
    decode_one = make_decode_step(cfg)

    def step(params, pool, tokens, positions, tables):
        def one(token, pos, table):
            cache = _gather_pages(pool, table, page_size)
            logits, new_cache = decode_one(params, cache, token, pos)
            pi = (pos // page_size).astype(jnp.int32)
            return logits, _written_page(new_cache, pos, page_size), table[pi]

        logits, pages, targets = jax.vmap(one)(tokens, positions, tables)
        # each live slot owns its write page, so targets collide only on
        # the null page (idle slots) — scatter order there is irrelevant
        new_pool = jax.tree_util.tree_map(
            lambda p, pg: p.at[:, targets].set(jnp.swapaxes(pg, 0, 1)),
            pool, pages)
        if return_tokens:
            nxt = jnp.argmax(logits[:, :, -1, :], axis=-1).astype(jnp.int32)
            return nxt, new_pool
        return logits, new_pool
    return step


def make_paged_verify_step(cfg: ModelConfig, page_size: int,
                           n_draft: int) -> Callable:
    """Speculative verify: score ``1 + n_draft`` tokens per slot in ONE
    multi-token paged decode and compute each slot's accept length on
    device.

    ``(params, pool, tokens(S,1,1+K), positions(S,), tables(S,T),
    write_tables(S,W), n_drafts(S,))`` → ``(emitted(S,1+K), accepts(S,),
    new pool)`` where K = ``n_draft`` and W = ``1 + ceil(K/page_size)``
    (the most pages a K+1-token write window can span).

    Per slot: token 0 is the slot's real next-input token, tokens 1..K
    are drafter guesses. The ordinary ``lm_decode_step`` runs all K+1
    positions against the gathered page view (causal mask per query
    row), ``emitted[j] = argmax(logits[j])`` is the token the model
    *actually* produces at position ``pos+j+1``, and the accept length
    is the longest prefix where the guesses reproduce it:
    ``accepts = max a such that tokens[1..a] == emitted[0..a-1]``
    (masked to the slot's live draft count ``n_drafts``). Everything in
    ``emitted[:accepts+1]`` is exactly the greedy-decode token stream —
    speculation changes the schedule, never the tokens.

    Rollback is split between the write tables and the engine's position
    bookkeeping: the KV writes for all K+1 positions land in the slices
    ``write_tables`` maps — the engine maps only request-owned pages in
    the write window and points everything else (rejected tails past the
    token budget, idle slots) at the scratch page — and positions past
    the accepted run are overwritten by the next verify step before the
    advancing causal mask can expose them, so no stale entry is ever
    attended and no page leaks.
    """
    decode_one = make_decode_step(cfg)
    n_wpages = 1 + pages_for(n_draft, page_size)

    def verify_step(params, pool, tokens, positions, tables, write_tables,
                    n_drafts):
        def one(token, pos, table, k):
            cache = _gather_pages(pool, table, page_size)
            logits, new_cache = decode_one(params, cache, token, pos)
            emitted = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
            ok = ((token[0, 1:] == emitted[:-1])
                  & (jnp.arange(n_draft, dtype=jnp.int32) < k))
            accept = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
            pi = (pos // page_size).astype(jnp.int32)
            pages = jax.tree_util.tree_map(
                lambda c: _pool_pages(jax.lax.dynamic_slice_in_dim(
                    c[:, 0], pi * page_size, n_wpages * page_size, axis=1),
                    page_size), new_cache)
            return emitted, accept.astype(jnp.int32), pages

        emitted, accepts, pages = jax.vmap(one)(tokens, positions, tables,
                                                n_drafts)

        # write pages are request-owned and disjoint across slots, so the
        # flattened scatter collides only on the scratch page (idle slots,
        # out-of-footprint tails) where order is irrelevant
        def scat(p, pg):                      # pg: (S, L, W, KV, ps, hd)
            pg = jnp.moveaxis(pg, 0, 1)       # (L, S, W, KV, ps, hd)
            pg = pg.reshape((pg.shape[0], -1) + pg.shape[3:])
            return p.at[:, write_tables.reshape(-1)].set(pg)

        return emitted, accepts, jax.tree_util.tree_map(scat, pool, pages)
    return verify_step


def make_paged_suffix_step(cfg: ModelConfig, page_size: int) -> Callable:
    """Chunked suffix prefill for a prefix-cache hit: run the whole prompt
    tail (positions ``pos .. pos+S-1``) against the shared pages in ONE
    call — (params, pool, tokens(1,S), pos, gather_table, scatter_table)
    → (logits(1,S,V), new pool). ``gather_table`` is the request's full
    page table; ``scatter_table`` maps only request-OWNED entries (shared
    prefix pages and padding point at the null page), so shared pages are
    read but never written. Unwritten owned pages scatter their gathered
    content back — an identity write."""
    decode_one = make_decode_step(cfg)

    def suffix_step(params, pool, tokens, pos, gather_table, scatter_table):
        cache = _gather_pages(pool, gather_table, page_size)
        logits, new_cache = decode_one(params, cache, tokens, pos)

        def one(p, c):
            return p.at[:, scatter_table].set(_pool_pages(c[:, 0], page_size))
        return logits, jax.tree_util.tree_map(one, pool, new_cache)
    return suffix_step


# ------------------------------------------------------- fused paged steps
#
# The make_fused_* factories run the whole batch through ONE
# ``lm.lm_paged_decode`` call: the Pallas kernel (or its jnp reference
# under impl="xla") walks each slot's page table on device — gather,
# flash-style attend, accept-masked KV write — so there is no
# ``_gather_pages`` materialization, no ``_written_page`` slice, no
# host-built write tables, and no per-slot vmap. ``n_valid`` carries the
# write mask: 0 = idle slot (nothing written, zero output), 1 = decode,
# ``1 + K`` = verify window, tail length = suffix prefill. Overflow rows
# land in the scratch page by the table-padding contract, which is
# exactly ``PagePool.write_table``'s rollback behaviour.

def make_fused_paged_decode_step(cfg: ModelConfig, page_size: int) -> Callable:
    """(params, pool, tokens(S,1,1), positions(S,), tables(S,T),
    n_valid(S,)) → (next_tokens(S,1), new pool). One fused kernel pass
    for every slot; greedy argmax fused into the step like
    ``make_paged_decode_step(return_tokens=True)``."""
    def step(params, pool, tokens, positions, tables, n_valid):
        logits, new_pool = lm.lm_paged_decode(
            params, tokens[:, 0, :], cfg, pool, positions, tables, n_valid,
            page_size=page_size)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        return nxt, new_pool
    return step


def make_fused_paged_verify_step(cfg: ModelConfig, page_size: int,
                                 n_draft: int) -> Callable:
    """Speculative verify on the fused kernel: ``(params, pool,
    tokens(S,1,1+K), positions(S,), tables(S,T), n_valid(S,))`` →
    ``(emitted(S,1+K), accepts(S,), new pool)``.

    Same accept semantics as ``make_paged_verify_step`` but with no
    ``write_tables`` operand at all: ``n_valid = 1 + k_live`` for live
    slots (0 idle) accept-masks the KV writes inside the kernel, and the
    gather table doubles as the write map (out-of-footprint rows fall in
    the scratch page). Rejected-but-written rows sit beyond the advancing
    causal horizon until the next verify window overwrites them."""
    K = n_draft

    def verify_step(params, pool, tokens, positions, tables, n_valid):
        tok = tokens[:, 0, :]                                # (S, 1+K)
        logits, new_pool = lm.lm_paged_decode(
            params, tok, cfg, pool, positions, tables, n_valid,
            page_size=page_size)
        emitted = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jnp.maximum(n_valid - 1, 0)                      # live drafts
        ok = ((tok[:, 1:] == emitted[:, :-1])
              & (jnp.arange(K, dtype=jnp.int32)[None, :] < k[:, None]))
        accepts = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        return emitted, accepts.astype(jnp.int32), new_pool
    return verify_step


def make_fused_paged_suffix_step(cfg: ModelConfig, page_size: int) -> Callable:
    """Chunked suffix prefill on the fused kernel: (params, pool,
    tokens(1,Sw), positions(1,), tables(1,T), n_valid(1,)) →
    (logits(1,Sw,V), new pool). ``Sw`` is the page-padded tail;
    ``n_valid`` its real length — padded rows are never written and the
    clamped causal horizon keeps them off unwritten positions, while
    shared prefix pages (positions < pos) are read, never written."""
    def suffix_step(params, pool, tokens, positions, tables, n_valid):
        return lm.lm_paged_decode(
            params, tokens, cfg, pool, positions, tables, n_valid,
            page_size=page_size)
    return suffix_step


def make_prefill_scatter(cfg: ModelConfig, page_size: int) -> Callable:
    """Blit a dense prefill cache into the pool: (pool, dense_cache,
    table(max_pages,)) → new pool. ``dense_cache`` leaves are
    (L, 1, max_pages*page_size, KV, hd); entry ``i`` of the table is the
    page receiving tokens [i*ps, (i+1)*ps) — null past the prompt."""
    def scatter(pool, dense_cache, table):
        def one(p, c):
            return p.at[:, table].set(_pool_pages(c[:, 0], page_size))
        return jax.tree_util.tree_map(one, pool, dense_cache)
    return scatter


def greedy_generate(cfg: ModelConfig, params, prompt: jax.Array,
                    n_tokens: int, max_cache_len: int) -> jax.Array:
    """Greedy decoding loop (exercised by examples/serve_batch)."""
    prefill = jax.jit(make_prefill_step(cfg, max_cache_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    logits, cache = prefill(params, {"tokens": prompt})
    pos = prompt.shape[1]
    out = [jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)]
    for i in range(n_tokens - 1):
        logits, cache = decode(params, cache, out[-1][:, None],
                               jnp.int32(pos + i))
        out.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
    return jnp.stack(out, axis=1)
