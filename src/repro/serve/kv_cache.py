"""Paged KV-cache block pool with prefix reuse.

Dense continuous batching still pads memory: every slot pre-allocates a
full ``max_cache_len`` KV cache, so batch capacity is bounded by the
worst-case sequence, not the actual ones (the same padding waste the
paper's §5.3 burst handling removes from the *scheduling* side). The
``PagePool`` removes it from the *memory* side:

* **block pool** — one device allocation of ``total_pages`` fixed-size
  pages per layer (``k``/``v``: ``(n_layers, total_pages + 1, kv_heads,
  page_size, head_dim)`` — the layout contract of
  ``kernels.paged_attention``); a request only holds pages proportional to its
  sequence, so the pool oversubscribes slots the way rtp-llm's block
  cache manager does. Index ``total_pages`` is a scratch ("null") page:
  page-table padding points at it, so idle slots and table tails
  read/write garbage there instead of needing dynamic shapes.
* **free-list allocation** — host-side free list + per-page refcounts.
  Allocation is worst-case at admission (``ceil((prompt + max_new) /
  page_size)`` pages), so decode never allocates mid-flight and can
  never OOM; capacity-deferred requests are requeued at the head of the
  batcher queue.
* **prefix reuse** — full prompt pages are content-hashed (the page's
  token prefix, chained from position 0). A new request whose prompt
  starts with an already-resident prefix maps those pages read-only
  (refcount++) and skips re-prefilling them. Sharing is restricted to
  *full, immutable* pages — the partially-filled tail page is always
  private — so the copy-on-write policy degenerates to "never write a
  shared page": every write (suffix prefill and decode both append at
  positions past the shared prefix) lands in pages the request owns.

All mutation happens on the engine loop thread (single-consumer, like
the slot state it feeds); no locking is needed here.
"""
from __future__ import annotations

import hashlib
import warnings
from functools import partial
from typing import Any, Dict, FrozenSet, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import DENSE, MOE, ModelConfig


def prefix_keys(tokens: Any, page_size: int,
                n_pages: Optional[int] = None) -> List[bytes]:
    """Chained per-page prefix digests of a token sequence — the content
    identity the prefix cache (and the router's affinity map) keys on.

    Key ``i`` hashes the sequence's first ``(i+1) * page_size`` tokens via
    one running sha256 — O(tokens), not O(tokens^2), and
    content-equivalent to hashing each prefix from scratch. Pure
    computation: needs no pool (the multi-replica router hashes prompts
    with it to find which replica already holds the pages), and two
    callers with the same ``page_size`` always derive the same keys for
    the same tokens.

    ``n_pages`` defaults to every *full* page of the sequence
    (``len(tokens) // page_size``).
    """
    toks = np.asarray(tokens, np.int32).reshape(-1)
    if n_pages is None:
        n_pages = len(toks) // int(page_size)
    keys: List[bytes] = []
    h = hashlib.sha256()
    for i in range(n_pages):
        h.update(toks[i * page_size:(i + 1) * page_size].tobytes())
        keys.append(h.digest())
    return keys


@jax.jit
def _page_slice(arr: jax.Array, page: jax.Array) -> jax.Array:
    """One page's KV out of a pool array: (L, P+1, KV, ps, hd) →
    (L, KV, ps, hd). ``page`` is traced, so every page id shares one
    compilation."""
    return jax.lax.dynamic_index_in_dim(arr, page, axis=1, keepdims=False)


@partial(jax.jit, donate_argnums=(0,))
def _page_install(arr: jax.Array, page: jax.Array,
                  data: jax.Array) -> jax.Array:
    """Install one page of KV into a (donated) pool array at a traced
    page index — the ingestion half of remote page shipping."""
    return jax.lax.dynamic_update_slice_in_dim(arr, data[:, None], page,
                                               axis=1)


def paged_supported(cfg: ModelConfig) -> bool:
    """Paged caching targets stacked full-attention KV caches: dense/MoE
    decoders with ``scan_layers`` and no sliding window (a SWA ring
    buffer re-keys slots by ``pos % window``, which a page table does not
    model; SSM/hybrid state is O(1) per slot and gains nothing)."""
    return (cfg.family in (DENSE, MOE) and cfg.scan_layers
            and not cfg.window)


def pages_for(n_tokens: int, page_size: int) -> int:
    return -(-int(n_tokens) // int(page_size))


class PagePool:
    """Fixed-size KV page pool: free-list + refcounts + prefix index.

    The device arrays live in ``arrays`` (``{"k", "v"}``, page axis 1)
    and are created lazily so constructing an engine never touches the
    device; the jitted steps donate them back and forth. This object
    owns only the host-side bookkeeping.
    """

    def __init__(self, cfg: ModelConfig, total_pages: int,
                 page_size: int, device: Optional[jax.Device] = None) -> None:
        if not paged_supported(cfg):
            raise ValueError(
                f"paged KV cache unsupported for family={cfg.family!r} "
                f"(scan_layers={cfg.scan_layers}, window={cfg.window})")
        self.cfg = cfg
        self.total_pages = int(total_pages)
        self.page_size = int(page_size)
        # the device the pool arrays live on: the owning engine's (None =
        # JAX's default placement)
        self.device = device
        self.null_page = self.total_pages      # scratch page, never owned
        self._free: List[int] = list(range(self.total_pages))
        self._ref = np.zeros(self.total_pages, np.int32)
        # prefix index: hash of the prompt's first (i+1)*page_size tokens
        # -> resident page holding page i of that prefix
        self._prefix: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        self.arrays: Optional[Dict[str, Any]] = None
        self.stats = {"allocated": 0, "released": 0, "prefix_hits": 0,
                      "prefix_tokens_reused": 0, "peak_in_use": 0,
                      "pages_exported": 0, "pages_imported": 0}

    # ------------------------------------------------------------- arrays
    def ensure_arrays(self) -> None:
        if self.arrays is not None:
            return
        cfg = self.cfg
        shape = (cfg.n_layers, self.total_pages + 1, cfg.padded_kv_heads,
                 self.page_size, cfg.resolved_head_dim)
        self.arrays = {"k": jnp.zeros(shape, cfg.dtype, device=self.device),
                       "v": jnp.zeros(shape, cfg.dtype, device=self.device)}

    @property
    def page_nbytes(self) -> int:
        """Wire size of one exported page (all layers, k + v)."""
        cfg = self.cfg
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return (2 * cfg.n_layers * self.page_size * cfg.padded_kv_heads
                * cfg.resolved_head_dim * itemsize)

    # ------------------------------------------------- remote page shipping
    def export_page(self, page: int) -> Dict[str, Any]:
        """Copy one resident page out of the pool: ``{"k", "v"}`` device
        arrays of shape ``(n_layers, kv_heads, page_size, head_dim)``.

        The slices are fresh buffers ordered after every write already
        dispatched against the pool (jax data dependency), so a prefill
        role can ship them over a transport — and later release the page
        — without synchronizing with in-flight device work."""
        self.ensure_arrays()
        self.stats["pages_exported"] += 1
        page_idx = jnp.int32(page)
        return {k: _page_slice(a, page_idx) for k, a in self.arrays.items()}

    def import_page(self, page: int, data: Dict[str, Any]) -> None:
        """Install shipped KV into an owned page (the ingestion side of
        ``export_page``). Dispatches asynchronously; any step reading the
        pool arrays afterwards is ordered behind the install by data
        dependency, so callers may seat the request immediately."""
        self.ensure_arrays()
        page_idx = jnp.int32(page)
        for k in self.arrays:
            self.arrays[k] = _page_install(
                self.arrays[k], page_idx, jax.device_put(data[k], self.device))
        self.stats["pages_imported"] += 1

    # ---------------------------------------------------------- free list
    @property
    def pages_in_use(self) -> int:
        return self.total_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages (refcount 1 each), or None if the pool can't
        cover them — the caller defers the request, never partial-allocs."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.stats["allocated"] += n
        self.stats["peak_in_use"] = max(self.stats["peak_in_use"],
                                        self.pages_in_use)
        return pages

    def retain(self, page: int) -> None:
        assert self._ref[page] > 0, "retain of a free page"
        self._ref[page] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; refcount-0 pages return to the
        free list and fall out of the prefix index."""
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                key = self._page_key.pop(p, None)
                if key is not None:
                    self._prefix.pop(key, None)
                self._free.append(p)
                self.stats["released"] += 1

    # ------------------------------------------------- speculative writes
    def write_table(self, page_ids: Sequence[int], pos: int,
                    width: int) -> np.ndarray:
        """Scatter targets for a verify step whose write window starts at
        ``pos`` and spans ``width`` pages (``1 + ceil(K / page_size)``
        for a K-draft verify): entry ``j`` receives the page holding
        positions ``(pos // page_size + j) * page_size ...``; entries
        past the request's reserved footprint map to the scratch page.

        This is the rollback half of speculative page writes: a request
        reserves ``ceil((prompt + max_new) / page_size)`` pages at
        admission, but a verify step may write up to ``n_draft``
        positions past the token budget (padded draft slots of a batch
        member that is nearly finished). Nulling those entries sends the
        out-of-footprint KV to the scratch page, so rejected tails can
        never land in — or leak — a real page; rejected writes *inside*
        the footprint are rolled back positionally (the engine advances
        ``pos`` only by the accepted run, and the next verify step
        overwrites them before the causal mask can expose them).
        """
        out = np.full(width, self.null_page, np.int32)
        first = int(pos) // self.page_size
        for j in range(width):
            if first + j < len(page_ids):
                out[j] = page_ids[first + j]
        return out

    # -------------------------------------------------------- prefix reuse
    def prefix_keys(self, tokens: Any,
                    n_pages: Optional[int] = None) -> List[bytes]:
        """Chained per-page prefix digests at this pool's ``page_size``
        (see the module-level :func:`prefix_keys`). Pure hash
        computation — touches no pool state."""
        return prefix_keys(tokens, self.page_size, n_pages)

    def _prefix_keys(self, prompt: Any, n_pages: int) -> List[bytes]:
        warnings.warn(
            "PagePool._prefix_keys is deprecated; use the public "
            "PagePool.prefix_keys (or serve.kv_cache.prefix_keys)",
            DeprecationWarning, stacklevel=2)
        return self.prefix_keys(prompt, n_pages)

    def match_prefix(self, prompt: Any) -> List[int]:
        """Longest chain of resident pages covering a page-aligned prompt
        prefix. Capped at ``len(prompt) - 1`` tokens so at least the last
        prompt token is always re-run — its logits produce the first
        generated token. Does NOT retain; the caller retains only once
        the rest of the admission (owned-page alloc) succeeds."""
        n = (len(np.asarray(prompt).reshape(-1)) - 1) // self.page_size
        matched: List[int] = []
        for key in self.prefix_keys(prompt, n):
            page = self._prefix.get(key)
            if page is None:
                break
            matched.append(page)
        return matched

    def resident_prefix_len(self, tokens: Any) -> int:
        """How many leading tokens of ``tokens`` are covered by resident
        shared pages right now (page-aligned; capped one token short of
        the full sequence, like :meth:`match_prefix`)."""
        return len(self.match_prefix(tokens)) * self.page_size

    def prefix_digests(self) -> FrozenSet[bytes]:
        """Snapshot of every resident prefix digest — what a replica
        gossips to the router so shared-prefix traffic can be routed to
        the pool that already holds the pages."""
        return frozenset(self._prefix)

    def register_prefix(self, prompt: Any, table: Sequence[int]) -> None:
        """Index every full prompt page of ``table`` for future sharing
        (first-registration wins; shared pages re-register as no-ops)."""
        n = len(np.asarray(prompt).reshape(-1)) // self.page_size
        for i, key in enumerate(self.prefix_keys(prompt, n)):
            if key not in self._prefix:
                self._prefix[key] = table[i]
                self._page_key[table[i]] = key

    def metrics(self) -> Dict[str, Any]:
        out = dict(self.stats)
        out["pages_in_use"] = self.pages_in_use
        out["total_pages"] = self.total_pages
        out["page_size"] = self.page_size
        return out
