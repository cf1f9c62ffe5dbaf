#!/usr/bin/env python3
"""Bring-up smoke test: the paged serve path on a TPU at paper_demo's
published widths (12 layers, d_model 768, 12/4 heads, head_dim 64, d_ff
2048, vocab 16384), with random weights from ``--seed``.

Run from the root of a checkout, in one process that owns the chip::

    python chip_smoke.py             # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: Router(n_replicas=4)
                                     # against one replica, nothing else

One-chip phases:

(a) device facts: platform, device kind, device count, jax and libtpu
    versions, compilation-cache directory;
(b) kernel parity on the device: the Pallas paged-attention kernel against
    ``kernels.paged_attention.ref`` for a decode batch and a suffix window
    (outputs within bf16 tolerance, pool pages bit-exact), and the Pallas
    RMSNorm against its reference at 13 and 1009 rows;
(c) serving through ``ServeClient`` (fused paged engine, ``max_batch`` 8,
    page size 16, ``max_seq_len`` 2048): 16 requests over three prompt
    lengths, one pair sharing a prefix so the fused suffix step runs; every
    stream must finish with its full token count and every page must come
    back. The compiled fused decode step must contain the Pallas kernel;
(d) reference check: a teacher-forced float32 forward (highest matmul
    precision, pure-jnp kernels) over prompt + served tokens for three
    requests; at every generated position the served token's reference
    logit must lie within ``MARGIN_TOL`` of the reference maximum;
(e) compile seconds, serve wall time and token counts, printed as
    information, not as claims.

``--chips 4`` checks that each replica's params and page pool sit on their
own chip, serves a shared-prefix burst through the router and the same
requests through one replica, and holds both to the phase (d) rule.

The last line of standard output is ``{"ok": true, "device": {...}}``.
A failed phase raises and the script exits non-zero without that line; so
does a run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Sequence

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

MODEL = "paper_demo"
PAGE_SIZE = 16
MAX_SEQ_LEN = 2048
MAX_BATCH = 8
MAX_TOKENS = 32
PROMPT_LENGTHS = (13, 100, 511)     # each distinct length compiles a prefill
N_REQUESTS = 16
# bf16 serving against a float32 forward: the served token's reference
# logit may trail the reference maximum by at most this much (logit units;
# paper_demo's random-weight logits have a standard deviation near 0.5)
MARGIN_TOL = 0.1
BF16_TOL = dict(atol=2e-2, rtol=2e-2)


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts: Any) -> None:
    print("chip_smoke:", *parts, flush=True)


# ------------------------------------------------------------- phase (a)
def device_facts(cache_dir: str) -> Dict[str, Any]:
    from importlib import metadata

    import jax
    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "jax": jax.__version__, "libtpu": libtpu,
            "compile_cache": cache_dir}


# ------------------------------------------------------------- phase (b)
def _paged_case(cfg, rng, *, n_slots: int, window: int, n_valid: Sequence[int],
                positions: Sequence[int], page_size: int, max_seq_len: int):
    """Random q/k/v and pool for ``n_slots`` slots, each owning the pages
    its positions need; table tails point at the null page."""
    import jax.numpy as jnp
    H, KV, hd = cfg.padded_heads, cfg.padded_kv_heads, cfg.resolved_head_dim
    T = -(-max_seq_len // page_size)
    n_pages = n_slots * T
    tables = np.full((n_slots, T), n_pages, np.int32)
    for s in range(n_slots):
        need = -(-(positions[s] + window) // page_size)
        tables[s, :need] = np.arange(s * T, s * T + need)
    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), cfg.dtype)

    return dict(q=rnd(n_slots, window, H, hd),
                k_new=rnd(n_slots, window, KV, hd),
                v_new=rnd(n_slots, window, KV, hd),
                k_pages=rnd(n_pages + 1, KV, page_size, hd),
                v_pages=rnd(n_pages + 1, KV, page_size, hd),
                tables=jnp.asarray(tables),
                positions=jnp.asarray(positions, jnp.int32),
                n_valid=jnp.asarray(n_valid, jnp.int32)), n_pages


def check_kernels(cfg, *, impl: str, seed: int, page_size: int = PAGE_SIZE,
                  max_seq_len: int = MAX_SEQ_LEN,
                  n_slots: int = MAX_BATCH) -> Dict[str, float]:
    """Phase (b): the ``impl`` kernels against their references. Returns
    the largest absolute output error of each case."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
    from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref

    rng = np.random.default_rng(seed)
    kernel = jax.jit(lambda a: pa_ops.paged_attention(
        **a, page_size=page_size, impl=impl))
    reference = jax.jit(lambda a: pa_ref.paged_attention(
        **a, page_size=page_size))
    top = max_seq_len - 2 * page_size
    decode_pos = rng.integers(0, top, n_slots)
    cases = {
        # one decode step for a full batch, slot 0 idle
        "paged_decode": dict(n_slots=n_slots, window=1, positions=decode_pos,
                             n_valid=[0] + [1] * (n_slots - 1)),
        # a prefix hit: 13 tail tokens padded to one page after 3 shared
        "paged_suffix": dict(n_slots=1, window=page_size,
                             positions=[3 * page_size], n_valid=[13]),
    }
    worst: Dict[str, float] = {}
    for name, case in cases.items():
        args, null = _paged_case(cfg, rng, page_size=page_size,
                                 max_seq_len=max_seq_len, **case)
        o_k, k_k, v_k = kernel(args)
        o_r, k_r, v_r = reference(args)
        o_k, o_r = np.asarray(o_k, np.float32), np.asarray(o_r, np.float32)
        np.testing.assert_allclose(o_k, o_r, **BF16_TOL, err_msg=name)
        for got, want in ((k_k, k_r), (v_k, v_r)):
            check(np.array_equal(np.asarray(got)[:null],
                                 np.asarray(want)[:null]),
                  f"{name}: pool pages differ from the reference")
        worst[name] = float(np.abs(o_k - o_r).max())
    d = cfg.d_model
    scale = jnp.asarray(rng.standard_normal(d), jnp.float32)
    for rows in (13, 1009):
        x = jnp.asarray(rng.standard_normal((rows, d)), cfg.dtype)
        got = np.asarray(jax.jit(lambda x, s: rn_ops.rmsnorm(
            x, s, impl=impl))(x, scale), np.float32)
        want = np.asarray(rn_ref.rmsnorm(x, scale), np.float32)
        np.testing.assert_allclose(got, want, **BF16_TOL,
                                   err_msg=f"rmsnorm rows={rows}")
        worst[f"rmsnorm_{rows}"] = float(np.abs(got - want).max())
    return worst


# ------------------------------------------------------------- phase (c)
def make_prompts(vocab: int, seed: int, *, n: int = N_REQUESTS,
                 lengths: Sequence[int] = PROMPT_LENGTHS,
                 page_size: int = PAGE_SIZE) -> List[np.ndarray]:
    """``n`` random prompts cycling over ``lengths``. The first two share
    every full page but the last of a ``lengths[1]``-token prompt, so the
    second is served by the fused suffix step."""
    rng = np.random.default_rng(seed)
    mid = lengths[1]
    first = rng.integers(0, vocab, mid, dtype=np.int32)
    shared = (mid - 1) // page_size * page_size
    second = np.concatenate(
        [first[:shared], rng.integers(0, vocab, mid - shared, dtype=np.int32)])
    rest = [rng.integers(0, vocab, lengths[i % len(lengths)], dtype=np.int32)
            for i in range(n - 2)]
    return [first, second] + rest


def serve(client, prompts: Sequence[np.ndarray], *, max_tokens: int,
          timeout: float = 900.0) -> List[List[int]]:
    """Submit every prompt through ``client`` and wait for all streams;
    each must finish with exactly ``max_tokens`` tokens."""
    streams = [client.generate(p, max_tokens=max_tokens) for p in prompts]
    outs = [s.result(timeout=timeout) for s in streams]
    for i, (s, toks) in enumerate(zip(streams, outs)):
        check(s.reason == "finished" and len(toks) == max_tokens,
              f"request {i}: {s.reason} with {len(toks)}/{max_tokens} tokens")
    return outs


def serve_phase(cfg, params, prompts: Sequence[np.ndarray], *,
                max_tokens: int = MAX_TOKENS, max_batch: int = MAX_BATCH,
                page_size: int = PAGE_SIZE, max_seq_len: int = MAX_SEQ_LEN
                ) -> Dict[str, Any]:
    """Phase (c): serve ``prompts`` through a fused paged ``ServeClient``;
    checks the prefix hit, the suffix step and that every page returned.
    ``close()`` re-raises any serve-loop error."""
    from repro.serve import ServeClient
    client = ServeClient(cfg, params, max_batch=max_batch,
                         page_size=page_size, max_seq_len=max_seq_len)
    try:
        check(client.serve.paged and client.serve.fused,
              "the default engine is not the fused paged one")
        t0 = time.perf_counter()
        outs = serve(client, prompts, max_tokens=max_tokens)
        wall = time.perf_counter() - t0
        m = client.metrics()
    finally:
        client.close()
    check(m["pages_in_use"] == 0, f"{m['pages_in_use']} pages leaked")
    check(m["prefix_hits"] >= 1 and m["suffix_steps"] >= 1,
          f"no prefix hit / suffix step (hits={m['prefix_hits']}, "
          f"suffix_steps={m['suffix_steps']})")
    return {"outputs": outs, "wall_s": wall,
            "tokens": sum(len(o) for o in outs), "steps": m["steps"],
            "prefix_hits": m["prefix_hits"],
            "suffix_steps": m["suffix_steps"]}


def compiled_decode_step(cfg, params, *, max_batch: int = MAX_BATCH,
                         page_size: int = PAGE_SIZE,
                         max_seq_len: int = MAX_SEQ_LEN):
    """Compile the engine's fused decode step at serving shapes; returns
    ``(seconds, hlo_text)``."""
    import jax
    import jax.numpy as jnp
    from repro.serve.steps import make_fused_paged_decode_step
    T = -(-max_seq_len // page_size)
    pool_shape = (cfg.n_layers, max_batch * T + 1, cfg.padded_kv_heads,
                  page_size, cfg.resolved_head_dim)
    spec = jax.ShapeDtypeStruct
    t0 = time.perf_counter()
    compiled = jax.jit(make_fused_paged_decode_step(cfg, page_size),
                       donate_argnums=(1,)).lower(
        params, {k: spec(pool_shape, cfg.dtype) for k in ("k", "v")},
        spec((max_batch, 1, 1), jnp.int32), spec((max_batch,), jnp.int32),
        spec((max_batch, T), jnp.int32),
        spec((max_batch,), jnp.int32)).compile()
    return time.perf_counter() - t0, compiled.as_text()


# ------------------------------------------------------------- phase (d)
def reference_margins(cfg, params, prompts: Sequence[np.ndarray],
                      outputs: Sequence[Sequence[int]]) -> List[float]:
    """Phase (d): teacher-forced float32 forward over each prompt + its
    served tokens; returns, per request, the largest gap between the
    reference maximum logit and the served token's reference logit."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.impl import use_impl
    from repro.models import lm

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    fwd = jax.jit(lambda p, t: lm.lm_forward(p, {"tokens": t}, cfg32))
    margins = []
    with use_impl("xla"), jax.default_matmul_precision("highest"):
        for prompt, out in zip(prompts, outputs):
            out = np.asarray(out, np.int32)
            seq = np.concatenate([np.asarray(prompt, np.int32), out[:-1]])
            logits = np.asarray(fwd(p32, jnp.asarray(seq)[None]))[0]
            rows = logits[len(prompt) - 1:]             # predicts out[i]
            gap = rows.max(axis=-1) - rows[np.arange(len(out)), out]
            margins.append(float(gap.max()))
    return margins


def check_margins(margins: Sequence[float], what: str) -> None:
    worst = max(margins)
    say(f"{what}: worst reference margin {worst!r} (tolerance {MARGIN_TOL})")
    check(worst <= MARGIN_TOL,
          f"{what}: a served token trails the float32 reference maximum "
          f"by {worst} > {MARGIN_TOL}")


# ------------------------------------------------------ four-chip path
def router_phase(cfg, params, n_replicas: int, seed: int) -> Dict[str, Any]:
    """Serve one shared-prefix burst through ``Router(n_replicas)`` and
    the same requests through a single replica; each replica's params
    and page pool must sit on their own device, and both runs must pass
    the reference-margin rule."""
    import jax
    from repro.serve import Router, ServeClient

    kw = dict(max_batch=MAX_BATCH, page_size=PAGE_SIZE,
              max_seq_len=MAX_SEQ_LEN)
    rng = np.random.default_rng(seed)
    # n_replicas prefix groups of two: 2 shared pages + an 8-token tail,
    # one prompt length for every request (one prefill compile per chip)
    prompts = []
    for _ in range(n_replicas):
        prefix = rng.integers(0, cfg.vocab_size, 2 * PAGE_SIZE,
                              dtype=np.int32)
        for _ in range(2):
            prompts.append(np.concatenate(
                [prefix, rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)]))

    router = Router(cfg, params, n_replicas=n_replicas, **kw)
    homes = []
    for w in router.workers:
        eng = w.core
        eng.pool.ensure_arrays()
        leaves = jax.tree_util.tree_leaves(eng.params) + \
            list(eng.pool.arrays.values())
        devs = {d for a in leaves for d in a.devices()}
        check(len(devs) == 1, f"replica {w.rank} spans devices {devs}")
        homes.append(devs.pop())
    check(len(set(homes)) == n_replicas,
          f"replicas share devices: {[str(d) for d in homes]}")
    client = ServeClient(engine=router)
    try:
        t0 = time.perf_counter()
        routed = serve(client, prompts, max_tokens=MAX_TOKENS)
        routed_wall = time.perf_counter() - t0
    finally:
        client.close()
    for w in router.workers:
        check(w.pool.pages_in_use == 0, f"replica {w.rank} leaked pages")

    single = ServeClient(cfg, params, **kw)
    try:
        t0 = time.perf_counter()
        alone = serve(single, prompts, max_tokens=MAX_TOKENS)
        alone_wall = time.perf_counter() - t0
    finally:
        single.close()
    check_margins(reference_margins(cfg, params, prompts, routed), "router")
    check_margins(reference_margins(cfg, params, prompts, alone),
                  "one replica")
    return {"devices": [str(d) for d in homes],
            "identical": sum(a == b for a, b in zip(routed, alone)),
            "requests": len(prompts), "routed_wall_s": routed_wall,
            "single_wall_s": alone_wall,
            "affinity_hits": router.stats["affinity_hits"]}


# ------------------------------------------------------------------ main
def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the Router(n_replicas=4) check")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax
    from repro.compile_cache import configure
    cache_dir = configure()
    facts = device_facts(cache_dir)
    if facts["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{facts['platform']!r} ({facts['kind']})", file=sys.stderr)
        return 1
    if facts["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{facts['count']} device(s)", file=sys.stderr)
        return 1
    say("device", json.dumps(facts))

    from repro.configs import get_config
    from repro.kernels import impl as impl_mod
    from repro.models import lm
    cfg = get_config(MODEL)
    check(impl_mod.resolve() == "pallas",
          f"kernel impl on the TPU is {impl_mod.resolve()!r}, not 'pallas'")
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)

    if args.chips == 4:
        res = router_phase(cfg, params, 4, args.seed)
        say("router", json.dumps(res))
    else:
        t0 = time.perf_counter()
        errs = check_kernels(cfg, impl="pallas", seed=args.seed)
        say("kernels", json.dumps(errs),
            f"({time.perf_counter() - t0!r} s incl. compile)")

        compile_s, hlo = compiled_decode_step(cfg, params)
        check("tpu_custom_call" in hlo,
              "the compiled fused decode step holds no Pallas kernel")
        say(f"fused decode step compiled in {compile_s!r} s with "
            f"{hlo.count('tpu_custom_call')} tpu_custom_call sites")

        prompts = make_prompts(cfg.vocab_size, args.seed)
        res = serve_phase(cfg, params, prompts)
        say(f"served {len(prompts)} requests, {res['tokens']} tokens in "
            f"{res['wall_s']!r} s wall incl. compile ({res['steps']} steps, "
            f"{res['prefix_hits']} prefix hits, {res['suffix_steps']} "
            "suffix steps)")

        # the shared-prefix pair and the first longest prompt
        picks = [0, 1, next(i for i, p in enumerate(prompts)
                            if len(p) == max(PROMPT_LENGTHS))]
        check_margins(reference_margins(
            cfg, params, [prompts[i] for i in picks],
            [res["outputs"][i] for i in picks]), "served")

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
