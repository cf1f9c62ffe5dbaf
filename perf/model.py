"""A configuration's file, and its weights made from the seed.

The weights are the benchmark's, not the program's: one jitted call makes
the whole parameter tree on the device, in the types the program serves
(bf16 matrices, float32 norm scales), laid out as the program's dense
decoder reads it:

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
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load(name: str) -> Dict[str, Any]:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def program_config(spec: Dict[str, Any]):
    """The system under test's ``ModelConfig`` for this file."""
    from repro.configs import get_config
    return get_config(spec["registry"], **spec["overrides"])


def real_heads(n_heads: int, n_kv: int, padded: int) -> np.ndarray:
    """Slot indices of the real query heads among ``padded`` slots, in
    head order: kv head ``k`` owns slots ``[k*g, (k+1)*g)``, g = padded /
    n_kv, and its real heads are the first ``n_heads / n_kv`` of them."""
    g = padded // n_kv
    per = n_heads // n_kv
    return np.asarray([k * g + j for k in range(n_kv) for j in range(per)])


def seed_key(seed: int):
    import jax
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def make_params(spec: Dict[str, Any], padded_heads: int, padded_vocab: int,
                seed: int, device):
    """The parameter tree for ``spec`` from ``seed``, on ``device``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    d = spec["hidden_size"]
    ff = spec["intermediate_size"]
    L = spec["num_hidden_layers"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    Hp, Vp = padded_heads, padded_vocab
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
