"""A test-only architecture: the parallel-block decoder (the program's
``parallel_block=True``), in which attention and the MLP read one
pre-norm and add to the residual together, so a layer has no ``norm2``.

It sits beside ``dense.py`` in the directory the loader reads and edits
nothing there: its weights are the dense tree without ``norm2``, its
reference is its own, and its work counts are the dense decoder's (the
same matmuls and attention a token).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from perf import model
from perf.model import real_heads
from perf.reference import TeacherForced, fp8_round


def _dense(spec: Dict[str, Any]):
    return model.arch(dict(spec, arch="dense"))


def check(spec: Dict[str, Any]) -> None:
    _dense(spec).check(spec)


def make_params(spec: Dict[str, Any], cfg: Any, seed: int, device):
    params = _dense(spec).make_params(spec, cfg, seed, device)
    del params["layers"]["norm2"]
    return params


class Reference(TeacherForced):
    """Teacher-forced float32 logits over ``length`` positions, the whole
    stack in one jitted call (a test-sized model)."""

    def __init__(self, spec: Dict[str, Any], params: Any, length: int):
        import jax
        import jax.numpy as jnp
        super().__init__(length)
        P = jax.lax.Precision.HIGHEST
        self.params = params
        H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
        hd, V, L = spec["head_dim"], spec["vocab_size"], \
            spec["num_hidden_layers"]
        eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
        tied = spec["tie_word_embeddings"]
        real = real_heads(H, KV, params["layers"]["attn"]["wq"].shape[2])

        def f32(a):
            return a.astype(jnp.float32)

        def rms(x, s):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * s

        def rope(x, pos):
            inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
            ang = pos[:, None].astype(jnp.float32) * inv[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)

        def mm(eq, a, w, fp8, contract=(0,)):
            return jnp.einsum(eq, a, fp8_round(w, contract) if fp8 else w,
                              precision=P)

        def forward(p, ids, fp8):
            x = f32(p["embed"]["tokens"][ids])
            pos = jnp.arange(x.shape[0], dtype=jnp.int32)
            causal = pos[None, :, None] >= pos[None, None, :]
            for i in range(L):
                lw = jax.tree_util.tree_map(lambda a: f32(a[i]), p["layers"])
                a, m = lw["attn"], lw["mlp"]
                h = rms(x, lw["norm1"]["scale"])
                q = rope(mm("nd,dhk->nhk", h, a["wq"][:, real], fp8), pos)
                k = rope(mm("nd,dhk->nhk", h, a["wk"], fp8), pos)
                v = mm("nd,dhk->nhk", h, a["wv"], fp8)
                k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
                s = jnp.einsum("qhk,thk->hqt", q, k, precision=P) * hd ** -0.5
                pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
                o = jnp.einsum("hqt,thk->qhk", pr, v, precision=P)
                u = jax.nn.silu(mm("nd,df->nf", h, m["wg"], fp8)) \
                    * mm("nd,df->nf", h, m["wi"], fp8)
                x = (x + mm("qhk,hkd->qd", o, a["wo"][real], fp8, (0, 1))
                     + mm("nf,fd->nd", u, m["wo"], fp8))
            x = rms(x, f32(p["final_norm"]["scale"]))
            w = f32(p["embed"]["tokens"][:V]).T if tied \
                else f32(p["embed"]["head"][:, :V])
            return mm("nd,dv->nv", x, w, fp8)

        self._forward = jax.jit(forward, static_argnums=2)

    def logits(self, seq: np.ndarray, fp8: bool = False):
        import jax.numpy as jnp
        ids = np.zeros(self.length, np.int32)
        ids[:len(seq)] = seq
        return self._forward(self.params, jnp.asarray(ids), fp8)


def prompt_flops(spec: Dict[str, Any], plen: int) -> int:
    return _dense(spec).prompt_flops(spec, plen)


def decode_flops(spec: Dict[str, Any], pos: int) -> int:
    return _dense(spec).decode_flops(spec, pos)


def paged_attention_work(spec: Dict[str, Any], pos: int) -> Tuple[int, int]:
    return _dense(spec).paged_attention_work(spec, pos)
