"""The dense decoder (llama-style): weights, float32 reference and work
counts.

A configuration file names this module with ``"arch": "dense"``.

Weights (``make_params``): one jitted call makes the whole parameter
tree on the device, in the types the program serves (bf16 matrices,
float32 norm scales), laid out as the program's dense decoder reads it:

* ``embed``: ``tokens (Vp, d)``, and ``head (d, Vp)`` when untied;
* ``layers`` (stacked on a leading L axis): ``norm1/norm2.scale (d,)``,
  ``attn.wq (d, Hp, hd)``, ``attn.wk/wv (d, KV, hd)``, ``attn.wo (Hp, hd,
  d)``, ``mlp.wi`` (up) and ``mlp.wg`` (gate) ``(d, ff)``, ``mlp.wo
  (ff, d)``;
* ``final_norm.scale (d,)``.

``Hp`` is the program's padded query-head count: each kv head's group of
``Hp / KV`` slots holds its ``H / KV`` real heads first and zero heads
after them. ``Vp`` is the padded vocabulary; its rows past ``V`` are
never a token.

Reference (``Reference``): written from the architecture's published
description (RMSNorm, RoPE on the two halves of each head, grouped-query
attention in which query head ``j`` reads kv head ``j // (H / KV)``,
SwiGLU, tied or untied head) and nothing of the program: it reads the
weight tree above by name and its sizes from the configuration file,
runs every matmul at ``Precision.HIGHEST`` in float32, and keeps only the
real query heads and the real vocabulary. It runs one sequence at a
time, layer by layer, padded to one fixed length per cell so that it
compiles once: a causal model's rows do not see the padding after them.
``fp8=True`` is the control: every matrix is rounded to float8 e4m3 with
one scale per output channel, the step a weight-only fp8 path would take.

Work counts: operations and bytes from the published sizes (real query
heads, real vocabulary) and the positions of the tokens served, nothing
of the program's grid or padding. A decode token at position ``pos``
(its query is token ``pos``; the cache holds positions ``0 .. pos-1``)
attends over ``pos + 1`` keys.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from perf.model import real_heads, seed_key
from perf.reference import TeacherForced, fp8_round

BF16 = 2
SIZES = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def check(spec: Dict[str, Any]) -> None:
    missing = [k for k in SIZES if k not in spec]
    if missing:
        raise ValueError(f"the dense decoder needs {missing}")


# ------------------------------------------------------------------ weights

def make_params(spec: Dict[str, Any], cfg: Any, seed: int, device):
    """The parameter tree for ``spec`` from ``seed``, on ``device``;
    ``cfg`` is the program's ``ModelConfig`` (its padded head count and
    vocabulary)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    d = spec["hidden_size"]
    ff = spec["intermediate_size"]
    L = spec["num_hidden_layers"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    Hp, Vp = cfg.padded_heads, cfg.padded_vocab
    tied = spec["tie_word_embeddings"]
    mask = np.zeros(Hp, np.float32)
    mask[real_heads(H, KV, Hp)] = 1.0
    bf16 = jnp.bfloat16

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(bf16)

    def scale(key, n):
        return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)

    def layer(key):
        k = jax.random.split(key, 9)
        m = jnp.asarray(mask)
        return {
            "norm1": {"scale": scale(k[0], d)},
            "attn": {
                "wq": normal(k[1], (d, Hp, hd), d ** -0.5)
                * m[None, :, None].astype(bf16),
                "wk": normal(k[2], (d, KV, hd), d ** -0.5),
                "wv": normal(k[3], (d, KV, hd), d ** -0.5),
                "wo": normal(k[4], (Hp, hd, d), (H * hd) ** -0.5)
                * m[:, None, None].astype(bf16)},
            "norm2": {"scale": scale(k[5], d)},
            "mlp": {"wi": normal(k[6], (d, ff), d ** -0.5),
                    "wg": normal(k[7], (d, ff), d ** -0.5),
                    "wo": normal(k[8], (ff, d), ff ** -0.5)},
        }

    def make(key):
        k_emb, k_head, k_layers, k_norm = jax.random.split(key, 4)
        embed = {"tokens": normal(k_emb, (Vp, d), 0.02 if tied else 1.0)}
        if not tied:
            embed["head"] = normal(k_head, (d, Vp), d ** -0.5)
        # one layer at a time: temporaries stay one layer's size
        layers = jax.lax.map(layer, jax.random.split(k_layers, L))
        return {"embed": embed, "layers": layers,
                "final_norm": {"scale": scale(k_norm, d)}}

    return jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        seed_key(seed))


# ---------------------------------------------------------------- reference

class Reference(TeacherForced):
    """Teacher-forced logits of the reference over ``length`` positions."""

    def __init__(self, spec: Dict[str, Any], params: Any, length: int):
        import jax
        import jax.numpy as jnp
        super().__init__(length)
        P = jax.lax.Precision.HIGHEST
        self.spec, self.params = spec, params
        H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
        hd = spec["head_dim"]
        V = spec["vocab_size"]
        eps = float(spec["rms_norm_eps"])
        theta = float(spec["rope_theta"])
        tied = spec["tie_word_embeddings"]
        Hp = params["layers"]["attn"]["wq"].shape[2]
        real = jnp.asarray(real_heads(H, KV, Hp))
        rep = H // KV

        def f32(a):
            return a.astype(jnp.float32)

        def rms(x, s):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * s

        def rope(x, pos):                       # x (n, heads, hd)
            inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
            ang = pos[:, None].astype(jnp.float32) * inv[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)

        def layer(x, layers, i, fp8):
            lw = jax.tree_util.tree_map(lambda a: f32(a[i]), layers)
            a, m = lw["attn"], lw["mlp"]
            wq, wo = a["wq"][:, real], a["wo"][real]
            wk, wv = a["wk"], a["wv"]
            wi, wg, wd = m["wi"], m["wg"], m["wo"]
            if fp8:
                wq, wk, wv = (fp8_round(w, (0,)) for w in (wq, wk, wv))
                wo = fp8_round(wo, (0, 1))
                wi, wg, wd = (fp8_round(w, (0,)) for w in (wi, wg, wd))
            n = x.shape[0]
            pos = jnp.arange(n, dtype=jnp.int32)
            h = rms(x, lw["norm1"]["scale"])
            q = rope(jnp.einsum("nd,dhk->nhk", h, wq, precision=P), pos)
            k = rope(jnp.einsum("nd,dhk->nhk", h, wk, precision=P), pos)
            v = jnp.einsum("nd,dhk->nhk", h, wv, precision=P)
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
            s = jnp.einsum("qhk,thk->hqt", q, k, precision=P) * hd ** -0.5
            causal = pos[None, :, None] >= pos[None, None, :]
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hqt,thk->qhk", p, v, precision=P)
            x = x + jnp.einsum("qhk,hkd->qd", o, wo, precision=P)
            h = rms(x, lw["norm2"]["scale"])
            g = jnp.einsum("nd,df->nf", h, wg, precision=P)
            u = jnp.einsum("nd,df->nf", h, wi, precision=P)
            return x + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * u, wd,
                                  precision=P)

        def head(x, embed, final, fp8):
            x = rms(x, f32(final["scale"]))
            w = f32(embed["tokens"][:V]).T if tied \
                else f32(embed["head"][:, :V])
            if fp8:
                w = fp8_round(w, (0,))
            return jnp.einsum("nd,dv->nv", x, w, precision=P)

        self._embed = jax.jit(lambda e, t: f32(e["tokens"][t]))
        self._layer = jax.jit(layer, static_argnums=3)
        self._head = jax.jit(head, static_argnums=3)
        self.n_layers = spec["num_hidden_layers"]

    def logits(self, seq: np.ndarray, fp8: bool = False):
        """Logits (device array, ``(length, V)``) over ``seq`` padded."""
        import jax.numpy as jnp
        ids = np.zeros(self.length, np.int32)
        ids[:len(seq)] = seq
        p = self.params
        x = self._embed(p["embed"], jnp.asarray(ids))
        for i in range(self.n_layers):
            x = self._layer(x, p["layers"], np.int32(i), fp8)
        return self._head(x, p["embed"], p["final_norm"], fp8)


# -------------------------------------------------------------- work counts

def dims(spec: Dict[str, Any]) -> Dict[str, int]:
    return {"d": spec["hidden_size"], "ff": spec["intermediate_size"],
            "L": spec["num_hidden_layers"],
            "H": spec["num_attention_heads"],
            "KV": spec["num_key_value_heads"], "hd": spec["head_dim"],
            "V": spec["vocab_size"]}


def layer_matmul_params(spec: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one layer."""
    m = dims(spec)
    d, H, KV, hd = m["d"], m["H"], m["KV"], m["hd"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * m["ff"]


def attention_flops(spec: Dict[str, Any], keys: int) -> int:
    """QK and PV of one query over ``keys`` keys, all layers."""
    m = dims(spec)
    return 4 * m["L"] * m["H"] * m["hd"] * keys


def head_flops(spec: Dict[str, Any]) -> int:
    m = dims(spec)
    return 2 * m["d"] * m["V"]


def prompt_flops(spec: Dict[str, Any], plen: int) -> int:
    """Prefill of a ``plen``-token prompt: every layer on every token,
    causal attention at each token's own length, and the head on the
    last token only (the one whose logits give the first output)."""
    m = dims(spec)
    return (2 * m["L"] * layer_matmul_params(spec) * plen
            + attention_flops(spec, plen * (plen + 1) // 2)
            + head_flops(spec))


def decode_flops(spec: Dict[str, Any], pos: int) -> int:
    """One decode token at position ``pos``."""
    m = dims(spec)
    return (2 * m["L"] * layer_matmul_params(spec)
            + attention_flops(spec, pos + 1) + head_flops(spec))


def paged_attention_work(spec: Dict[str, Any], pos: int) -> Tuple[int, int]:
    """(flops, bytes) the paged-attention kernel needs for one decode
    token at ``pos``, all layers: read the ``pos`` cached k/v rows and
    the new k/v row, write the new row, read q and write o."""
    m = dims(spec)
    L, H, KV, hd = m["L"], m["H"], m["KV"], m["hd"]
    row = 2 * KV * hd * BF16                 # one position's k and v
    nbytes = L * (row * pos + 2 * row + 2 * H * hd * BF16)
    return attention_flops(spec, pos + 1), nbytes
