"""The dense decoder's float32 reference (``perf/arch/dense.py``)
against the program's own forward pass, on the CPU at toy size: a
reduced paper_demo (tied head, 4/2 heads), a reduced dsc33b_6l shape
(untied head, 14 query heads over 2 kv heads padded to 16 slots,
vocabulary 500 padded to 512) and a reduced danube3_4b shape (a head
size that is no power of two, 30 as 120 is, and the registry's sliding
window turned off)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import model
from perf.reference import fp8_round
from perf.tests import tiny

dense = model.arch({"arch": "dense"})

DSC_SMALL = {
    "name": "dsc_small", "arch": "dense", "registry": "deepseek_coder_33b",
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 14,
                  "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                  "vocab_size": 500, "norm_eps": 1e-06},
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 500, "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "tie_word_embeddings": False,
}

DANUBE_SMALL = {
    "name": "danube_small", "arch": "dense", "registry": "h2o_danube3_4b",
    "overrides": {"n_layers": 2, "d_model": 120, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 30, "d_ff": 256,
                  "vocab_size": 500, "window": 0, "rope_theta": 100000.0,
                  "norm_eps": 1e-05},
    "hidden_size": 120, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 30,
    "vocab_size": 500, "rms_norm_eps": 1e-05, "rope_theta": 100000,
    "tie_word_embeddings": False,
}


def _program_logits(spec, params, seq):
    from repro.kernels.impl import use_impl
    from repro.models import lm
    cfg = model.program_config(spec)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with use_impl("xla"), jax.default_matmul_precision("highest"):
        out = lm.lm_forward(p32, {"tokens": jnp.asarray(seq)[None]}, cfg32)
    return np.asarray(out)[0, :, :spec["vocab_size"]]


@pytest.mark.parametrize("spec", [tiny.SPEC, DSC_SMALL, DANUBE_SMALL],
                         ids=["paper_demo_small", "dsc33b_small",
                              "danube3_small"])
def test_reference_matches_program_forward(spec):
    cfg = model.program_config(spec)
    params = dense.make_params(spec, cfg, seed=3, device=jax.devices()[0])
    seq = np.random.default_rng(0).integers(0, spec["vocab_size"], 37)
    want = _program_logits(spec, params, seq)
    got = np.asarray(dense.Reference(spec, params, 48).logits(seq))[:37]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_padded_heads_are_zero_and_real_heads_are_not():
    cfg = model.program_config(DSC_SMALL)
    assert cfg.padded_heads == 16
    params = dense.make_params(DSC_SMALL, cfg, seed=1,
                               device=jax.devices()[0])
    wq = np.asarray(params["layers"]["attn"]["wq"], np.float32)
    real = model.real_heads(14, 2, 16)
    assert list(real) == [0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13, 14]
    pad = sorted(set(range(16)) - set(real))
    assert not np.any(wq[:, :, pad])
    assert np.all(np.abs(wq[:, :, real]).sum(axis=(1, 3)) > 0)


def test_served_gap_is_zero_for_the_reference_argmax():
    cfg = model.program_config(tiny.SPEC)
    params = dense.make_params(tiny.SPEC, cfg, seed=5,
                               device=jax.devices()[0])
    ref = dense.Reference(tiny.SPEC, params, 32)
    prompt = np.arange(1, 9, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):     # greedy decode by the reference itself
        seq.append(int(np.argmax(np.asarray(ref.logits(
            np.asarray(seq)))[len(seq) - 1])))
    gaps = ref.served_gaps(prompt, np.asarray(seq[8:], np.int32),
                           control=True)
    assert gaps["served"] == 0.0
    assert gaps["control"] >= 0.0
    wrong = np.asarray(seq[8:], np.int32) + 1
    assert ref.served_gaps(prompt, wrong)["served"] > 0.0


def test_fp8_round_keeps_scale_and_loses_precision():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.01
    q = fp8_round(w, (0,))
    rel = float(jnp.max(jnp.abs(q - w)) / jnp.max(jnp.abs(w)))
    assert 1e-4 < rel < 0.07
    np.testing.assert_allclose(jnp.max(jnp.abs(q), 0),
                               jnp.max(jnp.abs(w), 0), rtol=1e-6)
