"""Plain float32 reference of the dense decoder, and its fp8 control.

Written from the architecture's published description (llama-style:
RMSNorm, RoPE on the two halves of each head, grouped-query attention in
which query head ``j`` reads kv head ``j // (H / KV)``, SwiGLU, tied or
untied head) and nothing of the program: it reads the benchmark's weight
tree by name (``perf.model``) and its sizes from the configuration file,
runs every matmul at ``Precision.HIGHEST`` in float32, and keeps only the
real query heads and the real vocabulary.

It runs one sequence at a time, layer by layer, padded to one fixed
length per cell so that it compiles once: a causal model's rows do not
see the padding after them.

``fp8=True`` is the control: every matrix is rounded to float8 e4m3 with
one scale per output channel, the step a weight-only fp8 path would take.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from perf.model import real_heads


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def fp8_round(w, contract: tuple):
    """``w`` through float8 e4m3, scaled per output channel (the max over
    the contracted axes maps to e4m3's largest finite value, 448)."""
    jax, jnp = _jax()
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Reference:
    """Teacher-forced logits of the reference over ``length`` positions."""

    def __init__(self, spec: Dict[str, Any], params: Any, length: int):
        jax, jnp = _jax()
        P = jax.lax.Precision.HIGHEST
        self.spec, self.params, self.length = spec, params, int(length)
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

        def gaps(ref, other_top, target):
            """Per row: how far the reference logit of ``target`` (and of
            ``other_top``) lies below the reference maximum."""
            best = jnp.max(ref, axis=-1)
            pick = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
            alt = jnp.take_along_axis(ref, other_top[:, None], -1)[:, 0]
            return best - pick, best - alt

        self._embed = jax.jit(lambda e, t: f32(e["tokens"][t]))
        self._layer = jax.jit(layer, static_argnums=3)
        self._head = jax.jit(head, static_argnums=3)
        self._gaps = jax.jit(gaps)
        self._argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(
            jnp.int32))
        self.n_layers = spec["num_hidden_layers"]

    def logits(self, seq: np.ndarray, fp8: bool = False):
        """Logits (device array, ``(length, V)``) over ``seq`` padded."""
        jax, jnp = _jax()
        ids = np.zeros(self.length, np.int32)
        ids[:len(seq)] = seq
        p = self.params
        x = self._embed(p["embed"], jnp.asarray(ids))
        for i in range(self.n_layers):
            x = self._layer(x, p["layers"], np.int32(i), fp8)
        return self._head(x, p["embed"], p["final_norm"], fp8)

    def served_gaps(self, prompt: np.ndarray, out: np.ndarray,
                    control: bool = False) -> Dict[str, float]:
        """Widest gap of a served token below the reference maximum over
        ``out`` (teacher-forced on prompt + out); with ``control``, also
        the widest gap of the token the fp8 control puts first."""
        jax, jnp = _jax()
        seq = np.concatenate([prompt, out]).astype(np.int32)
        if len(seq) > self.length:
            raise ValueError(f"sequence of {len(seq)} > {self.length}")
        target = np.zeros(self.length, np.int32)
        target[:len(seq) - 1] = seq[1:]
        ref = self.logits(seq)
        top = self._argmax(self.logits(seq, fp8=True)) if control \
            else jnp.zeros(self.length, jnp.int32)
        g_served, g_ctl = self._gaps(ref, top, jnp.asarray(target))
        rows = slice(len(prompt) - 1, len(seq) - 1)
        out_d = {"served": float(np.max(np.asarray(g_served)[rows]))}
        if control:
            out_d["control"] = float(np.max(np.asarray(g_ctl)[rows]))
        return out_d
