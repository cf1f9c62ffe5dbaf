"""Compile the serve path's Pallas kernels for a TPU v5e without a chip.

Each case lowers a kernel (or one whole fused decode step) at real widths
against a described ``v5e:2x2`` topology and compiles it with the TPU
compiler that ships with jaxlib. Nothing runs: these tests catch what
interpret mode cannot — block shapes the (8, 128) tiling rule refuses,
kernel bodies Mosaic cannot lower, VMEM overruns. Widths are
``paper_demo``'s published ones (d_model 768, 12/4 heads, head_dim 64,
vocab 16384) and Mamba-2 370M's SSD shapes.

The topology is described only inside the module fixture below: only
one process at a time may load the TPU library, so describing it while
a module is imported would break every other test worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import impl as impl_mod
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.paged_attention import kernel as pa_kernel
from repro.kernels.rmsnorm import kernel as rn_kernel
from repro.kernels.ssd_scan import kernel as ssd_kernel
from repro.models import lm
from repro.serve.steps import make_fused_paged_decode_step

PS = 16          # serve page size
T = 128          # table width: max_seq_len 2048 / page size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (S, W, (H, KV, hd) or None for paper_demo's, table width). The benchmark
# cells' decode shapes: deepseek-coder-33b's 56 heads padded to 64 over 8
# kv heads of 128 with 4096 / 16 pages, h2o-danube3-4b's 32/8 heads of 120
# with 768 / 16; a 1+3 verify window; a 1024-row suffix window, which
# folds fewer kv heads a grid step (at 64/8 heads such a window outgrows
# VMEM even one kv head a step)
@pytest.mark.parametrize("S,W,heads,pages", [
    (8, 1, None, T), (1, 16, None, T),
    (32, 1, (64, 8, 128), 256), (40, 1, (32, 8, 120), 48),
    (32, 4, (64, 8, 128), 256), (1, 1024, (32, 8, 128), 256)],
    ids=["decode", "suffix", "dsc33b-decode", "danube3-decode",
         "dsc33b-verify", "suffix-1024"])
def test_paged_attention_compiles(one_chip, S, W, heads, pages):
    if heads is None:
        cfg = get_config("paper_demo")
        heads = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)
    H, KV, hd = heads
    kb = pa_kernel.kv_fold(KV, W, H // KV, hd, PS, 2)
    if W <= 4:
        assert kb == KV
    if W >= 1024:
        assert kb < KV
    bf = jnp.bfloat16
    args = (_spec((S, W, H, hd), bf, one_chip),
            _spec((S, W, KV, hd), bf, one_chip),
            _spec((S, W, KV, hd), bf, one_chip),
            _spec((S * pages + 1, KV, PS, hd), bf, one_chip),
            _spec((S * pages + 1, KV, PS, hd), bf, one_chip),
            _spec((S, pages), jnp.int32, one_chip),
            _spec((S,), jnp.int32, one_chip),
            _spec((S,), jnp.int32, one_chip))
    compiled = _compile(
        lambda *a: pa_kernel.paged_attention(*a, page_size=PS), *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [8, 13, 1009])
def test_rmsnorm_compiles(one_chip, rows):
    d = get_config("paper_demo").d_model
    compiled = _compile(lambda x, s: rn_kernel.rmsnorm(x, s),
                        _spec((rows, d), jnp.bfloat16, one_chip),
                        _spec((d,), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    cfg = get_config("paper_demo")
    H, KV, hd, S = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, 512
    compiled = _compile(
        lambda q, k, v: fa_kernel.flash_attention(q, k, v, causal=True),
        _spec((1, S, H, hd), jnp.bfloat16, one_chip),
        _spec((1, S, KV, hd), jnp.bfloat16, one_chip),
        _spec((1, S, KV, hd), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    cfg = get_config("mamba2_370m")
    s = cfg.ssm
    B, L, H, P, G, N = 1, 512, cfg.ssm_heads, s.head_dim, s.n_groups, \
        s.state_dim
    f32 = jnp.float32
    compiled = _compile(
        lambda *a: ssd_kernel.ssd_scan(*a, chunk=s.chunk_size),
        _spec((B, L, H, P), jnp.bfloat16, one_chip),
        _spec((B, L, H), f32, one_chip),
        _spec((H,), f32, one_chip),
        _spec((B, L, G, N), f32, one_chip),
        _spec((B, L, G, N), f32, one_chip),
        _spec((H,), f32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_paged_decode_step_compiles(one_chip):
    """The whole serve decode step at paper_demo width: 12 layers of the
    paged kernel plus the Pallas RMSNorm, one program."""
    cfg = get_config("paper_demo")
    S = 8
    shard = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    params = shard(jax.eval_shape(
        lambda key: lm.init_params(key, cfg), jax.random.PRNGKey(0)))
    pool_shape = (cfg.n_layers, S * T + 1, cfg.padded_kv_heads, PS,
                  cfg.resolved_head_dim)
    pool = {k: _spec(pool_shape, cfg.dtype, one_chip) for k in ("k", "v")}
    step = make_fused_paged_decode_step(cfg, PS)
    with impl_mod.use_impl("pallas"):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, pool,
            _spec((S, 1, 1), jnp.int32, one_chip),
            _spec((S,), jnp.int32, one_chip),
            _spec((S, T), jnp.int32, one_chip),
            _spec((S,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2     # paged attention + rmsnorm
    assert "paged_attention" in text
