"""Decoder-only language models: dense / MoE / SSM / hybrid / VLM.

Layer weights are *stacked* on a leading L axis regardless of application
style: ``cfg.scan_layers=True`` applies them via ``jax.lax.scan`` (small
HLO, fast compiles — production default), ``False`` unrolls a python loop
over indexed slices (exact per-layer HLO accounting for the roofline's
full-unroll mode). The hybrid (zamba2) family adds unstacked shared-block
weights and always unrolls (its pattern is heterogeneous).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import DENSE, HYBRID, MOE, SSM, VLM, ModelConfig
from repro.models.layers import (cross_entropy, embed_tokens, embedding_specs,
                                 init_embedding, init_mlp, init_rmsnorm,
                                 lm_logits, mlp, mlp_specs, rmsnorm,
                                 rmsnorm_specs, _init_dense)
from repro.sharding import constrain

# ============================================================ initialization

def _init_layer(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    if cfg.family in (SSM, HYBRID):
        return {"norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
                "ssm": ssm_mod.init_ssm(ks[0], cfg)}
    p = {"norm1": init_rmsnorm(cfg.d_model, cfg.param_dtype),
         "attn": attn_mod.init_attention(ks[0], cfg)}
    if not cfg.parallel_block:
        p["norm2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
    if cfg.family == MOE:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return p


def _layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family in (SSM, HYBRID):
        return {"norm": rmsnorm_specs(), "ssm": ssm_mod.ssm_specs()}
    p = {"norm1": rmsnorm_specs(), "attn": attn_mod.attention_specs(cfg)}
    if not cfg.parallel_block:
        p["norm2"] = rmsnorm_specs()
    if cfg.family == MOE:
        p["moe"] = moe_mod.moe_specs(cfg)
    else:
        p["mlp"] = mlp_specs()
    return p


def _stack_leading(tree):
    return jax.tree_util.tree_map(
        lambda spec: (None,) + tuple(spec), tree,
        is_leaf=lambda v: isinstance(v, tuple))


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    k_emb, k_layers, k_extra = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": init_embedding(k_emb, cfg),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }
    if cfg.family == HYBRID:
        w = 2 * cfg.d_model
        ks = jax.random.split(k_extra, 4)
        n_sites = max(1, cfg.n_layers // cfg.hybrid_attn_every)
        params["shared"] = {
            "norm1": init_rmsnorm(w, cfg.param_dtype),
            "attn": attn_mod.init_attention(ks[0], cfg, width=w),
            "norm2": init_rmsnorm(w, cfg.param_dtype),
            "mlp": init_mlp(ks[1], w, cfg.d_ff, cfg.param_dtype),
            # per-site output projectors 2d → d
            "proj": _init_dense(ks[2], (n_sites, w, cfg.d_model),
                                cfg.param_dtype),
        }
    if cfg.family == VLM or cfg.frontend_dim:
        params["frontend"] = {
            "proj": _init_dense(k_extra, (cfg.frontend_dim, cfg.d_model),
                                cfg.param_dtype)}
    return params


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "embed": embedding_specs(cfg),
        "layers": _stack_leading(_layer_specs(cfg)),
        "final_norm": rmsnorm_specs(),
    }
    if cfg.family == HYBRID:
        specs["shared"] = {
            "norm1": rmsnorm_specs(), "attn": attn_mod.attention_specs(cfg),
            "norm2": rmsnorm_specs(), "mlp": mlp_specs(),
            "proj": (None, "fsdp", "tp"),
        }
    if cfg.family == VLM or cfg.frontend_dim:
        specs["frontend"] = {"proj": ("fsdp", "tp")}
    return specs


# ================================================================== blocks

def _block_train(lp, x, cfg: ModelConfig):
    if cfg.family in (SSM, HYBRID):
        return x + ssm_mod.ssm_block(lp["ssm"], rmsnorm(lp["norm"], x, cfg.norm_eps), cfg)
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    a = attn_mod.attention_block(lp["attn"], h, cfg, causal=True)
    if cfg.parallel_block:     # command-r: attn + mlp share one pre-norm
        return x + a + mlp(lp["mlp"], h, cfg.gather_weights)
    x = x + a
    h2 = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == MOE:
        return x + moe_mod.moe_block(lp["moe"], h2, cfg)
    return x + mlp(lp["mlp"], h2, cfg.gather_weights)


def _block_decode(lp, x, cache, pos, cfg: ModelConfig):
    if cfg.family in (SSM, HYBRID):
        y, new_state = ssm_mod.ssm_decode_step(
            lp["ssm"], rmsnorm(lp["norm"], x, cfg.norm_eps), cfg, cache)
        return x + y, new_state
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    a, new_cache = attn_mod.decode_attention(lp["attn"], h, cfg, cache, pos)
    if cfg.parallel_block:
        return x + a + mlp(lp["mlp"], h, cfg.gather_weights), new_cache
    x = x + a
    h2 = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == MOE:
        return x + moe_mod.moe_block(lp["moe"], h2, cfg), new_cache
    return x + mlp(lp["mlp"], h2, cfg.gather_weights), new_cache


def _block_paged_decode(lp, x, k_pages, v_pages, cfg: ModelConfig,
                        positions, tables, n_valid, page_size: int):
    """`_block_decode` over the paged pool: attention is the fused paged
    kernel; the residual/mlp/moe structure is identical."""
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    a, nk, nv = attn_mod.paged_decode_attention(
        lp["attn"], h, cfg, k_pages, v_pages, positions, tables, n_valid,
        page_size=page_size)
    if cfg.parallel_block:
        return x + a + mlp(lp["mlp"], h, cfg.gather_weights), nk, nv
    x = x + a
    h2 = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == MOE:
        return x + moe_mod.moe_block(lp["moe"], h2, cfg), nk, nv
    return x + mlp(lp["mlp"], h2, cfg.gather_weights), nk, nv


def _block_prefill(lp, x, cache, cfg: ModelConfig):
    if cfg.family in (SSM, HYBRID):
        # chunked scan also yields the final SSD + conv state → decode cache
        y, state = ssm_mod.ssm_block(
            lp["ssm"], rmsnorm(lp["norm"], x, cfg.norm_eps), cfg,
            return_state=True)
        return x + y, state
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    a, new_cache = attn_mod.prefill_attention(lp["attn"], h, cfg, cache)
    if cfg.parallel_block:
        return x + a + mlp(lp["mlp"], h, cfg.gather_weights), new_cache
    x = x + a
    h2 = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == MOE:
        return x + moe_mod.moe_block(lp["moe"], h2, cfg), new_cache
    return x + mlp(lp["mlp"], h2, cfg.gather_weights), new_cache


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _apply_layers(params, x, cfg: ModelConfig, mode: str = "train",
                  cache=None, pos=None):
    """Run the stacked layers; returns (x, new_cache)."""
    layers = params["layers"]
    if cfg.family == HYBRID:
        return _apply_hybrid(params, x, cfg, mode, cache, pos)
    if mode == "train":
        body = _maybe_remat(lambda h, lp: (_block_train(lp, h, cfg), None), cfg)
        if cfg.scan_layers:
            x, _ = jax.lax.scan(body, x, layers)
        else:
            for i in range(cfg.n_layers):
                lp = jax.tree_util.tree_map(lambda a: a[i], layers)
                x, _ = body(x, lp)
        return x, None
    if mode == "decode":
        def body(h, inp):
            lp, lc = inp
            h, nc = _block_decode(lp, h, lc, pos, cfg)
            return h, nc
    else:
        def body(h, inp):
            lp, lc = inp
            h, nc = _block_prefill(lp, h, lc, cfg)
            return h, nc
    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(body, x, (layers, cache))
    else:
        # unrolled mode: the cache is a LIST of per-layer buffers — no
        # slice-of-stacked reads, and donated per-layer args update in
        # place (serving-system layout; also exact HLO accounting)
        ncs = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], layers)
            x, nc = body(x, (lp, cache[i]))
            ncs.append(nc)
        new_cache = ncs
    return x, new_cache


def _apply_hybrid(params, x, cfg: ModelConfig, mode, cache, pos):
    """zamba2: SSM backbone + shared attention block over concat(x, x0)
    every ``hybrid_attn_every`` layers (site-specific output projectors)."""
    sh = params["shared"]
    x0 = x
    site = 0
    new_cache: Dict[str, Any] = {"ssm": [], "kv": []} if cache is not None else None
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        if i % cfg.hybrid_attn_every == 0 and cfg.n_heads > 0:
            w_in = jnp.concatenate([x, x0], axis=-1)
            h = rmsnorm(sh["norm1"], w_in, cfg.norm_eps)
            if mode == "decode":
                a, nkv = attn_mod.decode_attention(sh["attn"], h, cfg,
                                                   cache["kv"][site], pos)
                new_cache["kv"].append(nkv)
            elif mode == "prefill":
                a, nkv = attn_mod.prefill_attention(sh["attn"], h, cfg,
                                                    cache["kv"][site])
                new_cache["kv"].append(nkv)
            else:
                a = attn_mod.attention_block(sh["attn"], h, cfg, causal=True)
            w_mid = w_in + a
            h2 = rmsnorm(sh["norm2"], w_mid, cfg.norm_eps)
            w_out = w_mid + mlp(sh["mlp"], h2, cfg.gather_weights)
            x = x + jnp.einsum("bsw,wd->bsd", w_out,
                               sh["proj"][site].astype(cfg.dtype))
            site += 1
        h = rmsnorm(lp["norm"], x, cfg.norm_eps)
        if mode == "decode":
            y, ns = ssm_mod.ssm_decode_step(lp["ssm"], h, cfg,
                                            cache["ssm"][i])
            new_cache["ssm"].append(ns)
            x = x + y
        elif mode == "prefill":
            y, ns = ssm_mod.ssm_block(lp["ssm"], h, cfg, return_state=True)
            new_cache["ssm"].append(ns)
            x = x + y
        else:
            x = x + ssm_mod.ssm_block(lp["ssm"], h, cfg)
    return x, new_cache


# ================================================================ embeddings

def _embed_inputs(params, batch: Dict[str, jax.Array], cfg: ModelConfig
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Returns (x, loss_mask). VLM prepends projected patch embeddings."""
    x = embed_tokens(params["embed"], batch["tokens"], cfg)
    mask = None
    if cfg.family == VLM:
        patches = batch["patches"].astype(cfg.dtype)
        px = jnp.einsum("bpf,fd->bpd", patches,
                        params["frontend"]["proj"].astype(cfg.dtype))
        x = jnp.concatenate([px, x], axis=1)
        B, S = batch["tokens"].shape
        mask = jnp.concatenate(
            [jnp.zeros((B, cfg.n_patches)), jnp.ones((B, S))], axis=1)
    return constrain(x, "batch", None, None), mask


# ============================================================ public forward

def lm_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig) -> jax.Array:
    """Next-token CE loss over the token positions."""
    x, mask = _embed_inputs(params, batch, cfg)
    x, _ = _apply_layers(params, x, cfg, mode="train")
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg)
    tokens = batch["tokens"]
    if cfg.family == VLM:
        labels = jnp.roll(tokens, -1, axis=1)
        token_logits = logits[:, cfg.n_patches:, :]
        valid = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
        return cross_entropy(token_logits, labels, valid)
    labels = jnp.roll(tokens, -1, axis=1)
    valid = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    return cross_entropy(logits, labels, valid)


def lm_forward(params, batch: Dict[str, jax.Array], cfg: ModelConfig
               ) -> jax.Array:
    """Logits for the whole sequence (tests / generation without cache)."""
    x, _ = _embed_inputs(params, batch, cfg)
    x, _ = _apply_layers(params, x, cfg, mode="train")
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    stacked = cfg.scan_layers
    if cfg.family == SSM:
        if stacked:
            return ssm_mod.init_ssm_state(cfg, batch, n_layers=cfg.n_layers)
        return [ssm_mod.init_ssm_state(cfg, batch)
                for _ in range(cfg.n_layers)]
    if cfg.family == HYBRID:   # always unrolled → per-layer/site lists
        n_sites = max(1, -(-cfg.n_layers // cfg.hybrid_attn_every))
        return {
            "ssm": [ssm_mod.init_ssm_state(cfg, batch)
                    for _ in range(cfg.n_layers)],
            "kv": [attn_mod.init_kv_cache(cfg, batch, max_len)
                   for _ in range(n_sites)],
        }
    if stacked:
        return attn_mod.init_kv_cache(cfg, batch, max_len,
                                      n_layers=cfg.n_layers)
    return [attn_mod.init_kv_cache(cfg, batch, max_len)
            for _ in range(cfg.n_layers)]


def cache_specs(cfg: ModelConfig) -> Any:
    stacked = cfg.scan_layers
    if cfg.family == SSM:
        one = ssm_mod.ssm_state_specs(layer_stacked=stacked)
        return one if stacked else [one] * cfg.n_layers
    if cfg.family == HYBRID:
        n_sites = max(1, -(-cfg.n_layers // cfg.hybrid_attn_every))
        return {
            "ssm": [ssm_mod.ssm_state_specs(False)] * cfg.n_layers,
            "kv": [attn_mod.kv_cache_specs(False, cfg)] * n_sites,
        }
    one = attn_mod.kv_cache_specs(layer_stacked=stacked, cfg=cfg)
    return one if stacked else [one] * cfg.n_layers


def lm_prefill(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
               cache: Any) -> Tuple[jax.Array, Any]:
    """Process the prompt; returns (last-position logits, primed cache)."""
    x, _ = _embed_inputs(params, batch, cfg)
    x, cache = _apply_layers(params, x, cfg, mode="prefill", cache=cache)
    x = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg), cache


def lm_decode_step(params, token: jax.Array, cfg: ModelConfig, cache: Any,
                   pos: jax.Array) -> Tuple[jax.Array, Any]:
    """One-token decode. token: (B, 1) int32; pos: scalar int32."""
    x = embed_tokens(params["embed"], token, cfg)
    x, cache = _apply_layers(params, x, cfg, mode="decode", cache=cache,
                             pos=pos)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg), cache


def lm_paged_decode(params, tokens: jax.Array, cfg: ModelConfig,
                    pool: Dict[str, jax.Array], positions: jax.Array,
                    tables: jax.Array, n_valid: jax.Array, *,
                    page_size: int) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused paged decode over ALL serve slots in one call.

    ``tokens (S, W)`` int32 — each slot's query window (W=1 decode,
    W=1+K verify, W=padded tail for suffix prefill); ``pool`` is the
    layer-stacked page pool ``{"k","v": (L, P+1, KVp, ps, hd)}``;
    ``positions``/``n_valid``: (S,) int32; ``tables``: (S, T) int32.

    Returns ``(logits (S, W, vocab), new_pool)``. Layers run under
    ``lax.scan`` with per-layer pool leaves as scanned inputs/outputs,
    so a donated pool updates in place layer by layer.
    """
    if cfg.family in (SSM, HYBRID):
        raise NotImplementedError("paged decode requires KV attention")
    if not cfg.scan_layers:
        raise NotImplementedError("fused paged decode requires scan_layers")
    x = embed_tokens(params["embed"], tokens, cfg)

    def body(h, inp):
        lp, kp, vp = inp
        h, nk, nv = _block_paged_decode(lp, h, kp, vp, cfg, positions,
                                        tables, n_valid, page_size)
        return h, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["layers"], pool["k"], pool["v"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["embed"], x, cfg), {"k": nk, "v": nv}
