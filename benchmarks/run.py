"""Benchmark harness — one benchmark per paper table/figure.

Output: ``name,us_per_call,derived`` CSV rows (+ a context comment per
block). Mapping to the paper (DESIGN.md §7):

  notification.*   §5.1 PoC overhead — completion-notification latency and
                   throughput, continuations vs the application-space
                   Testsome-window manager (the paper's headline claim).
  zones.*          Fig. 2/3 — NPB BT-MZ analogue: fork-join vs
                   continuation-released zone tasks, uneven zones.
  dataflow.*       Fig. 6 — PaRSEC/DPLASMA analogue: tiled-Cholesky DAG
                   makespan + activation latency, per-class CRs vs Testsome.
  offload.*        Fig. 8/9 — ExaHyPE analogue: diffusive offloading
                   throughput and critical-path wait.
  loc.*            Table 3 — lines of code of the submit/progress paths.
  overlap.*        beyond-paper: continuation-driven trainer I/O overlap.
  scheduler.*      beyond-paper: fifo vs affinity ready-queue schedulers
                   under a multi-threaded completion storm.
  core.api.*       beyond-paper: the redesigned registration API —
                   per-registration flag overhead and awaitable-bridge
                   (``engine.wrap`` + asyncio) notification latency vs
                   the raw callback surface. Gated in CI (api block of
                   BENCH_serve.json).
  serve.*          beyond-paper: continuation-driven continuous batching vs
                   the synchronous static-batch ``greedy_generate`` loop,
                   bursty multi-request workload — tokens/s and p99 TTFT.
                   ``serve.paged.*`` adds dense vs paged-pool at equal cache
                   memory; ``serve.spec.*`` adds speculative (draft/verify)
                   vs plain paged decode on a repetition-friendly trace;
                   ``serve.stream.*`` adds the streaming session API
                   (per-token continuation delivery: TTFT speedup over
                   retirement delivery, inter-token p99, tokens/s
                   overhead); ``serve.disagg.*`` adds disaggregated
                   prefill/decode (role engines over the continuation
                   transport, per-block KV shipping) vs colocated. All
                   emitted machine-readable to BENCH_serve.json.

``--quick`` runs a CI-smoke subset (notification + scheduler + loc +
serve) at reduced sizes; ``--only BLOCK`` runs a single block by name.
"""
from __future__ import annotations

import argparse
import inspect
import json
import threading
import time
from typing import Callable, List

import numpy as np

ROWS: List[str] = []
QUICK = False
SAMPLES = 3          # measured samples per serve scenario (--samples)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    row = f"{name},{us_per_call:.3f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _append_block(block: str, payload: dict) -> None:
    """Merge one block into BENCH_serve.json (bench_serve creates it)."""
    try:
        with open("BENCH_serve.json") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        doc = {}
    doc[block] = payload
    with open("BENCH_serve.json", "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# appended {block} block to BENCH_serve.json", flush=True)


def _variance(samples: List[dict]) -> dict:
    """Per-metric {mean, cv, ci95, values} over per-sample dicts — the
    fields the variance-aware regression gate reads."""
    from repro.bench.stats import variance_fields
    return variance_fields(samples)


def _timeit(fn: Callable, n: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ===================================================== §5.1 notification
def bench_notification() -> None:
    from repro.core import Engine, Status, TestsomeManager
    from repro.core.completable import Completable

    class Op(Completable):
        def __init__(self):
            super().__init__()
            self.flag = False

        def trigger(self):
            self._complete(Status())

        def _poll(self):
            return self.flag

    # -- registration overhead (us/registration, incl. handle bookkeeping)
    eng = Engine()
    cr = eng.continue_init()

    def reg_continuation():
        op = Op()
        eng.continue_when(op, lambda st, d: None, cr=cr)
        op.trigger()

    n_reg = 600 if QUICK else 3000
    us = _timeit(reg_continuation, n_reg)
    emit("notification.register.continuation", us, "incl_trigger+run")
    cr.wait(timeout=10)

    mgr = TestsomeManager(window=32)

    def reg_testsome():
        op = Op()
        mgr.submit([op], lambda st, d: None)
        op.flag = True
        mgr.testsome()

    us = _timeit(reg_testsome, n_reg)
    emit("notification.register.testsome_w32", us, "incl_trigger+run")

    # -- notification latency. For testsome, K cold outstanding ops sit
    # ahead in the window (PaRSEC's promotion artifact): latency grows
    # with the backlog.
    def latency_continuation() -> float:
        eng2 = Engine()
        cr2 = eng2.continue_init()
        lat = []
        for _ in range(300):
            op = Op()
            t_done = [0.0]
            eng2.continue_when(
                op, lambda st, d: t_done.__setitem__(0, time.perf_counter()),
                cr=cr2)
            t0 = time.perf_counter()
            op.trigger()          # push: runs inline on this thread
            lat.append(t_done[0] - t0)
        eng2.shutdown()
        return float(np.mean(lat)) * 1e6

    emit("notification.latency.continuation", latency_continuation(),
         "push_inline")

    # a completed-but-recently-posted op is invisible until promoted into
    # the window; ``backlog`` older ops drain in bursts ahead of it
    # (the PaRSEC §5.3 completion-detection delay)
    for backlog in ((0, 64) if QUICK else (0, 64, 256)):
        lat = []
        for _ in range(15 if QUICK else 60):
            mgr2 = TestsomeManager(window=32)
            cold = [Op() for _ in range(backlog)]
            for c in cold:
                mgr2.submit([c], lambda st, d: None)
            op = Op()
            t_done = [0.0]
            mgr2.submit([op],
                        lambda st, d: t_done.__setitem__(0, time.perf_counter()))
            t0 = time.perf_counter()
            op.flag = True
            ci = 0
            while t_done[0] == 0.0:
                # older ops complete a few at a time while we poll
                for _ in range(4):
                    if ci < len(cold):
                        cold[ci].flag = True
                        ci += 1
                mgr2.testsome()
            lat.append(t_done[0] - t0)
        emit(f"notification.latency.testsome_backlog{backlog}",
             float(np.mean(lat)) * 1e6, "poll+promotion")

    # -- throughput: completions/s with many concurrent ops
    n = 4000 if QUICK else 20000
    eng3 = Engine()
    cr3 = eng3.continue_init({"mpi_continue_enqueue_complete": True})
    count = [0]
    ops = [Op() for _ in range(n)]
    for op in ops:
        eng3.continue_when(op, lambda st, d: count.__setitem__(0, count[0] + 1),
                           cr=cr3)
    t0 = time.perf_counter()
    for op in ops:
        op.trigger()
    while not cr3.test():
        pass
    dt = time.perf_counter() - t0
    emit("notification.throughput.continuation", dt / n * 1e6,
         f"{n / dt:.0f}_cb_per_s")
    eng3.shutdown()

    mgr3 = TestsomeManager(window=32)
    count2 = [0]
    ops = [Op() for _ in range(n)]
    for op in ops:
        mgr3.submit([op], lambda st, d: count2.__setitem__(0, count2[0] + 1))
    t0 = time.perf_counter()
    for op in ops:
        op.flag = True
    mgr3.drain()
    dt = time.perf_counter() - t0
    emit("notification.throughput.testsome_w32", dt / n * 1e6,
         f"{n / dt:.0f}_cb_per_s")
    eng.shutdown()


# ========================================================= Fig 2/3 zones
def bench_zones() -> None:
    from repro.zones.solver import distributed_solve, make_zones
    zones = make_zones(n_zones=8, ny=96, base_nx=16, max_ratio=20.0, seed=3)
    steps = 30
    results = {}
    for variant in ("fork_join", "continuations"):
        best = None
        for _ in range(3):
            z = [a.copy() for a in zones]
            _, timing = distributed_solve(z, n_ranks=4, timesteps=steps,
                                          variant=variant, smooth_iters=2)
            best = min(best, timing["elapsed"]) if best else timing["elapsed"]
        results[variant] = best
        emit(f"zones.{variant}", best / steps * 1e6, f"{steps}_steps_4_ranks")
    emit("zones.speedup", 0.0,
         f"{results['fork_join'] / results['continuations']:.3f}x")


# ======================================================= Fig 6 dataflow
def bench_dataflow() -> None:
    from repro.dataflow.cholesky import build_cholesky_graph, make_spd_matrix
    from repro.dataflow.runtime import (ContinuationBackend, TestsomeBackend,
                                        run_dataflow)
    nb, tile, ranks = 6, 64, 4
    A = make_spd_matrix(nb * tile, seed=5)
    results = {}
    for name, factory in (
            ("continuations", lambda eng: ContinuationBackend(eng)),
            ("testsome_w4", lambda eng: TestsomeBackend(4))):
        best, lat = None, 0.0
        for _ in range(3):
            graph, meta = build_cholesky_graph(A, nb, tile, ranks)
            _, stats = run_dataflow(graph, factory, timeout=120)
            if best is None or stats["makespan"] < best:
                best = stats["makespan"]
                lat = stats["mean_activation_latency"]
        results[name] = best
        emit(f"dataflow.cholesky.{name}", best * 1e6,
             f"act_lat_{lat * 1e6:.0f}us")
    emit("dataflow.speedup", 0.0,
         f"{results['testsome_w4'] / results['continuations']:.3f}x")


# ======================================================= Fig 8/9 offload
def _run_offload_backend(backend: str, iters: int = 8):
    import threading as th
    from repro.core import Engine, Transport
    from repro.runtime.offload import (ContinuationBackend, OffloadManager,
                                       TestsomeBackend)
    n_ranks, task_cost, imbalance = 4, 0.003, 6
    engine = Engine()
    tr = Transport(n_ranks, engine=engine)
    mk = (lambda: ContinuationBackend(engine)) if backend == "continuations" \
        else (lambda: TestsomeBackend(8))
    managers = [OffloadManager(r, n_ranks, tr, mk()) for r in range(n_ranks)]
    arrived = [0] * iters
    lock = th.Lock()
    wait_critical = [0.0]

    def barrier(mgr, it):
        with lock:
            arrived[it] += 1
        while True:
            with lock:
                if arrived[it] >= n_ranks:
                    return
            mgr.backend.progress()
            time.sleep(1e-4)

    def loop(rank):
        mgr = managers[rank]
        n_tasks = imbalance * 8 if rank == 0 else 8
        for it in range(iters):
            tasks = [mgr.new_task(task_cost) for _ in range(n_tasks)]
            pending = []
            loads = {r: (imbalance if r == 0 else 1.0)
                     for r in range(n_ranks)}
            budget = sum(mgr.quota.values())
            for t in tasks:
                target = mgr.pick_target(loads)
                if rank == 0 and target is not None and len(pending) < budget:
                    mgr.offload(t, target)
                    pending.append(t)
                    loads[target] += 1.0
                else:
                    time.sleep(task_cost)
                    t.done.set()
                mgr.backend.progress()
            missed = {}
            t_wait = time.monotonic()
            deadline = time.monotonic() + 5.0
            for t in pending:
                while not t.done.is_set() and time.monotonic() < deadline:
                    mgr.backend.progress()
                    time.sleep(5e-5)
                if not t.done.is_set():
                    missed[1] = True
            if rank == 0:
                wait_critical[0] += time.monotonic() - t_wait
            mgr.end_iteration(missed)
            barrier(mgr, it)
        mgr.stop()

    threads = [th.Thread(target=loop, args=(r,)) for r in range(n_ranks)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = time.monotonic() - t0
    offl = managers[0].stats["offloaded"]
    engine.shutdown()
    return total, offl, wait_critical[0]


def bench_offload() -> None:
    import examples.offload_lb as lb
    base, _ = lb.run(offloading=False, iters=8)
    results = {}
    for backend in ("continuations", "testsome"):
        t, offl, wait = _run_offload_backend(backend, iters=8)
        results[backend] = (t, offl)
        emit(f"offload.{backend}", t * 1e6,
             f"{offl}_offloaded_wait{wait * 1e3:.0f}ms")
    emit("offload.no_offloading", base * 1e6, "baseline")
    emit("offload.speedup_vs_baseline", 0.0,
         f"{base / results['continuations'][0]:.3f}x")


# ========================================================== Table 3 LoC
def bench_loc() -> None:
    """Measured LoC of the submit + progress paths in this repo."""
    from repro.core import engine as eng_mod
    from repro.core import testsome as ts_mod
    from repro.core.continuation import ContinuationRequest

    def loc(fn) -> int:
        src = inspect.getsource(fn)
        return sum(1 for line in src.splitlines()
                   if line.strip() and not line.strip().startswith(("#", '"')))

    emit("loc.submit.continuations", 0.0,
         f"{loc(eng_mod.Engine.continue_all)}_lines")
    emit("loc.submit.testsome", 0.0,
         f"{loc(ts_mod.TestsomeManager.submit)}_lines")
    emit("loc.progress.continuations", 0.0,
         f"{loc(ContinuationRequest.test)}_lines")
    emit("loc.progress.testsome", 0.0,
         f"{loc(ts_mod.TestsomeManager.testsome)}_lines")
    # application-side: one continue_all per group vs 3 parallel dicts
    emit("loc.app_parallel_structures.continuations", 0.0, "0_dicts")
    emit("loc.app_parallel_structures.testsome", 0.0,
         "3_dicts(op_group,groups,index)")


# =============================================== beyond paper: overlap
def bench_train_overlap() -> None:
    """Continuation-driven async checkpoint+prefetch vs blocking I/O."""
    import os
    import shutil
    import jax
    from repro.checkpoint.async_ckpt import AsyncCheckpointer
    from repro.configs import get_config
    from repro.core import Engine
    from repro.data.pipeline import PrefetchPipeline, SyntheticTokenSource
    from repro.optim import OptConfig
    from repro.train.train_step import init_train_state, make_train_step

    cfg = get_config("paper_demo", reduced=True)
    opt = OptConfig(lr=1e-3)
    steps, fill_latency = 12, 0.02
    step_fn = jax.jit(make_train_step(cfg, opt))

    def run_async() -> float:
        eng = Engine()
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        src = SyntheticTokenSource(cfg, 4, 64, fill_latency_s=fill_latency)
        pipe = PrefetchPipeline(src, eng, depth=2)
        ck = AsyncCheckpointer("/tmp/bench_ck_a", eng)
        jax.block_until_ready(step_fn(state, pipe.get_next())[0]["params"])
        t0 = time.perf_counter()
        handles = []
        for i in range(steps):
            batch = pipe.get_next()
            state, m = step_fn(state, batch)
            if (i + 1) % 4 == 0:
                handles.append(ck.save_async(i, state))
        jax.block_until_ready(state["params"])
        dt = time.perf_counter() - t0
        for h in handles:
            h.wait(timeout=60)
        pipe.close(); ck.close(); eng.shutdown()
        shutil.rmtree("/tmp/bench_ck_a", ignore_errors=True)
        return dt

    def run_blocking() -> float:
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        src = SyntheticTokenSource(cfg, 4, 64, fill_latency_s=fill_latency)
        jax.block_until_ready(step_fn(state, src.make_batch(0))[0]["params"])
        os.makedirs("/tmp/bench_ck_b", exist_ok=True)
        t0 = time.perf_counter()
        for i in range(steps):
            batch = src.make_batch(i)          # synchronous fill
            state, m = step_fn(state, batch)
            if (i + 1) % 4 == 0:               # synchronous save
                for j, leaf in enumerate(jax.tree_util.tree_leaves(state)):
                    np.save(f"/tmp/bench_ck_b/{j}.npy", np.asarray(leaf))
        jax.block_until_ready(state["params"])
        dt = time.perf_counter() - t0
        shutil.rmtree("/tmp/bench_ck_b", ignore_errors=True)
        return dt

    asy = min(run_async() for _ in range(2))
    blk = min(run_blocking() for _ in range(2))
    emit("overlap.trainer.async_continuations", asy / steps * 1e6, "")
    emit("overlap.trainer.blocking_reference", blk / steps * 1e6, "")
    emit("overlap.trainer.speedup", 0.0, f"{blk / asy:.3f}x")


# ==================================== scheduler: ready-queue contention
def bench_scheduler() -> None:
    """fifo (shared deque + one lock) vs affinity (per-thread queues with
    stealing) under a multi-threaded completion storm — the hot
    submit→inline-drain path the affinity scheduler optimizes."""
    from repro.core import Engine, Status
    from repro.core.completable import Completable

    class Op(Completable):
        @property
        def supports_push(self):
            return True

        def trigger(self):
            self._complete(Status())

    n_threads = 4
    per_thread = 2000 if QUICK else 10000
    results = {}
    for sched in ("fifo", "affinity"):
        eng = Engine(scheduler=sched)
        crs = [eng.continue_init() for _ in range(n_threads)]

        def worker(cr):
            for _ in range(per_thread):
                op = Op()
                eng.continue_when(op, lambda st, d: None, cr=cr)
                op.trigger()     # discover + execute on this thread

        threads = [threading.Thread(target=worker, args=(cr,))
                   for cr in crs]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for cr in crs:
            cr.wait(timeout=30)
        dt = time.perf_counter() - t0
        results[sched] = dt
        n_ops = n_threads * per_thread
        emit(f"scheduler.storm.{sched}", dt / n_ops * 1e6,
             f"{n_threads}_threads_{n_ops / dt:.0f}_cb_per_s")
        eng.shutdown()
    emit("scheduler.storm.affinity_speedup", 0.0,
         f"{results['fifo'] / results['affinity']:.3f}x")


# ====================================== beyond paper: continuous batching
def _serve_workload(n_requests: int, n_slots: int):
    """Bursty request trace: an initial burst of 2×slots, then stragglers.

    Output lengths vary ~4..28 tokens — the regime where continuous
    batching beats static batching (no padding to the longest member, no
    waiting for a batch to fill).
    """
    lengths = [(4 + 6 * (i % 5)) for i in range(n_requests)]       # 4..28
    burst = min(n_requests, 2 * n_slots)
    arrivals = [0.0] * burst + [0.03 * (i + 1)
                                for i in range(n_requests - burst)]
    return lengths, arrivals


def bench_serve() -> None:
    """Continuation-driven continuous batching vs synchronous static
    batching built on the same jitted prefill/decode steps (the
    ``greedy_generate`` loop, compile-warmed for fairness).

    The continuous side is driven through the ``repro.bench`` harness —
    the same bursty workload frozen into a seeded ``Trace`` and replayed
    ``SAMPLES`` times by a ``Replayer`` over the real ``ServeClient``
    streaming surface — so the headline ratios carry variance fields
    (mean/cv/ci95) instead of a single roll of the load dice.
    """
    import random as pyrandom

    import jax
    import jax.numpy as jnp
    from repro.bench import Replayer, Trace, TraceRequest
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ServeEngine
    from repro.serve.request import _percentile
    from repro.serve.steps import make_decode_step, make_prefill_step

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_slots, prompt_len, cache_len = 4, 8, 64
    n_requests = 8 if QUICK else 16
    lengths, arrivals = _serve_workload(n_requests, n_slots)
    seed = 1
    prng = pyrandom.Random(seed)
    trace = Trace(
        requests=tuple(TraceRequest(
            arrival_s=arrivals[i],
            prompt=tuple(prng.randrange(cfg.vocab_size)
                         for _ in range(prompt_len)),
            max_tokens=lengths[i]) for i in range(n_requests)),
        meta={"name": "serve_burst", "seed": seed,
              "vocab_size": cfg.vocab_size})
    prompts = jnp.asarray([list(r.prompt) for r in trace.requests],
                          dtype=jnp.int32)
    useful_tokens = sum(lengths)

    # ---- continuous batching (continuation-driven), via the harness ----
    # dense slots: this block isolates the scheduling win; the memory win
    # is measured separately by bench_serve_paged (dense vs paged pool)
    replayer = Replayer(ServeEngine(cfg, params, max_batch=n_slots,
                                    max_cache_len=cache_len, paged=False),
                        name="continuous")

    # ---- static batching (synchronous greedy_generate loop) ----
    prefill = jax.jit(make_prefill_step(cfg, cache_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    def static_generate(batch_prompts, n_tokens):
        """The greedy_generate loop body, on pre-jitted (warm) steps."""
        logits, cache = prefill(params, {"tokens": batch_prompts})
        pos = batch_prompts.shape[1]
        out = [jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)]
        for i in range(n_tokens - 1):
            logits, cache = decode(params, cache, out[-1][:, None],
                                   jnp.int32(pos + i))
            out.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
        return jnp.stack(out, axis=1)

    jax.block_until_ready(static_generate(prompts[:n_slots], 2))  # warm

    def static_trial():
        """One paced pass of the synchronous loop over the trace."""
        t0 = time.monotonic()
        static_ttft = []
        done = 0
        while done < n_requests:
            now = time.monotonic() - t0
            ready = [i for i in range(done, n_requests)
                     if arrivals[i] <= now]
            if not ready:
                time.sleep(1e-3)
                continue
            batch = ready[:n_slots]
            idx = list(batch) + [batch[-1]] * (n_slots - len(batch))  # pad
            n_steps = max(lengths[i] for i in batch)
            out = static_generate(prompts[jnp.asarray(idx)], n_steps)
            jax.block_until_ready(out)   # synchronous: block per batch
            t_end = time.monotonic() - t0
            # tokens observable only when the whole batch finishes
            static_ttft.extend(t_end - arrivals[i] for i in batch)
            done += len(batch)
        return time.monotonic() - t0, static_ttft

    def p99(vals):
        return _percentile(sorted(vals), 0.99)

    def p50(vals):
        return _percentile(sorted(vals), 0.50)

    static_trial()   # throwaway: full-trace Python-dispatch warm

    # interleave continuous/static samples (alternating order per sample)
    # so machine-load drift hits both variants alike; every sample of each
    # feeds the variance fields the regression gate reads
    cont_results, static_results = [], []
    for s in range(SAMPLES):
        pair = [lambda: cont_results.extend(replayer.run(trace, samples=1)),
                lambda: static_results.append(static_trial())]
        for f in (pair if s % 2 == 0 else reversed(pair)):
            f()
    replayer.close()

    per_sample = []
    for res, (s_mk, s_ttft) in zip(cont_results, static_results):
        m = res.metrics()
        s_tps = useful_tokens / s_mk
        per_sample.append({
            "continuous_tokens_per_s": m["tokens_per_s"],
            "continuous_makespan_s": m["makespan_s"],
            "continuous_ttft_p50_s": m["ttft_p50_s"],
            "continuous_ttft_p99_s": m["ttft_p99_s"],
            "static_tokens_per_s": s_tps,
            "static_makespan_s": s_mk,
            "static_ttft_p50_s": p50(s_ttft),
            "static_ttft_p99_s": p99(s_ttft),
            "speedup_tokens_per_s": m["tokens_per_s"] / s_tps,
            "ttft_p99_ratio": p99(s_ttft) / m["ttft_p99_s"],
        })
    var = _variance(per_sample)

    def mean(key):
        return var[key]["mean"]

    emit("serve.continuous_batching",
         mean("continuous_makespan_s") / useful_tokens * 1e6,
         f"{mean('continuous_tokens_per_s'):.0f}_tok_per_s_ttft_p99_"
         f"{mean('continuous_ttft_p99_s') * 1e3:.0f}ms")
    emit("serve.static_greedy",
         mean("static_makespan_s") / useful_tokens * 1e6,
         f"{mean('static_tokens_per_s'):.0f}_tok_per_s_ttft_p99_"
         f"{mean('static_ttft_p99_s') * 1e3:.0f}ms")
    emit("serve.speedup", 0.0,
         f"{mean('speedup_tokens_per_s'):.3f}x_cv_"
         f"{var['speedup_tokens_per_s']['cv']:.3f}")
    with open("BENCH_serve.json", "w") as f:
        json.dump({
            "workload": {"n_requests": n_requests, "n_slots": n_slots,
                         "prompt_len": prompt_len, "lengths": lengths,
                         "arrivals_s": arrivals, "trace_seed": seed,
                         "trace_name": trace.name},
            "samples": SAMPLES,
            "continuous": {
                "tokens_per_s": mean("continuous_tokens_per_s"),
                "makespan_s": mean("continuous_makespan_s"),
                "ttft_p50_s": mean("continuous_ttft_p50_s"),
                "ttft_p99_s": mean("continuous_ttft_p99_s")},
            "static_greedy": {
                "tokens_per_s": mean("static_tokens_per_s"),
                "makespan_s": mean("static_makespan_s"),
                "ttft_p50_s": mean("static_ttft_p50_s"),
                "ttft_p99_s": mean("static_ttft_p99_s")},
            "speedup_tokens_per_s": mean("speedup_tokens_per_s"),
            "variance": var,
        }, f, indent=2)
    print("# wrote BENCH_serve.json", flush=True)


# ============================= beyond paper: paged KV cache + prefix reuse
def bench_serve_paged() -> None:
    """Dense per-slot cache vs paged pool at EQUAL cache memory.

    Dense pre-allocates ``n_slots × cache_len`` tokens of KV; paged holds
    the same token budget as a shared page pool, so shorter-than-worst-case
    sequences and a shared prompt prefix translate into more concurrent
    slots (effective batch) and higher tokens/s on the same bursty trace.
    Appends a ``paged`` block to BENCH_serve.json.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Request, ServeEngine
    from repro.serve.request import _percentile

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    # workload: shared system prefix + unique 4-token tail per request
    # (the prefix-cache regime: every request after the first maps the
    # full shared page and runs only the tail through one chunked
    # suffix-prefill call). Bursty arrivals, varied output lengths. On
    # CPU decode compute scales linearly with batch, so tokens/s is
    # load-noisy around 1-2x — the stable structural win at equal cache
    # memory is the 2x effective batch (on accelerators, where batch
    # amortizes, the tokens/s follows it).
    n_requests = 8 if QUICK else 16
    page_size, prompt_len, shared_len = 8, 16, 12
    dense_slots, dense_cache_len = 4, 64              # 256 cached tokens
    paged_slots, total_pages, max_seq = 8, 31, 48     # 31+1 scratch = 256
    lengths = [(4 + 6 * (i % 5)) for i in range(n_requests)]      # 4..28
    burst = min(n_requests, 2 * dense_slots)
    arrivals = [0.0] * burst + [0.03 * (i + 1)
                                for i in range(n_requests - burst)]
    common = jax.random.randint(jax.random.PRNGKey(2), (shared_len,), 0,
                                cfg.vocab_size)
    tails = jax.random.randint(jax.random.PRNGKey(3),
                               (n_requests, prompt_len - shared_len), 0,
                               cfg.vocab_size)
    prompts = [jnp.concatenate([common, tails[i]]) for i in range(n_requests)]
    useful_tokens = sum(lengths)
    # warm prompts: same shapes, disjoint tokens (released pages drop out
    # of the prefix index, so the measured run still sees one cold miss)
    warm_prompts = jax.random.randint(jax.random.PRNGKey(4),
                                      (2, prompt_len), 0, cfg.vocab_size)

    def make_engine(**engine_kwargs):
        serve = ServeEngine(cfg, params, **engine_kwargs)
        warm = [Request(warm_prompts[0], 2),
                Request(jnp.concatenate([warm_prompts[0][:shared_len],
                                         warm_prompts[1][shared_len:]]), 2)]
        for r in warm:                      # warms prefill+decode+suffix
            serve.submit(r)
        serve._bench_done = len(warm)
        serve.run(until=lambda: len(serve.retired) == serve._bench_done,
                  timeout=120)
        return serve

    def measure(serve):
        # drop prior-phase counters (warmup, earlier samples) so the
        # reported metrics reflect only this sample's trace — released
        # pages fall out of the prefix index, so every sample sees the
        # same one-cold-miss-per-run structure
        serve.stats.update(max_active=0, deferred=0)
        if serve.paged:
            serve.pool.stats.update(prefix_hits=0, prefix_tokens_reused=0,
                                    peak_in_use=serve.pool.pages_in_use)

        reqs = [Request(prompts[i], lengths[i]) for i in range(n_requests)]
        t0 = time.monotonic()

        def submitter():
            for req, dt in zip(reqs, arrivals):
                now = time.monotonic() - t0
                if dt > now:
                    time.sleep(dt - now)
                req.arrival_time = time.monotonic()
                serve.submit(req)

        sub = threading.Thread(target=submitter)
        sub.start()
        serve._bench_done += n_requests
        serve.run(until=lambda: len(serve.retired) == serve._bench_done,
                  timeout=300)
        sub.join()
        makespan = max(r.finish_time for r in reqs) - t0
        out = {
            "tokens_per_s": useful_tokens / makespan,
            "makespan_s": makespan,
            "ttft_p50_s": _percentile(sorted(r.ttft for r in reqs), 0.50),
            "ttft_p99_s": _percentile(sorted(r.ttft for r in reqs), 0.99),
            "effective_batch": serve.stats["max_active"],
            "cached_tokens_budget": (dense_slots * dense_cache_len),
        }
        m = serve.metrics()
        if m.get("paged"):
            out.update({k: m[k] for k in ("prefix_hits",
                                          "prefix_tokens_reused",
                                          "peak_in_use", "total_pages",
                                          "page_size", "deferred")})
        return out

    # interleave dense/paged samples (alternating order) so load drift
    # hits both variants alike; headline dicts keep the best (min
    # makespan) sample, the variance fields carry all of them
    dense_eng = make_engine(max_batch=dense_slots,
                            max_cache_len=dense_cache_len, paged=False)
    paged_eng = make_engine(max_batch=paged_slots,
                            max_cache_len=dense_cache_len, paged=True,
                            page_size=page_size, max_seq_len=max_seq,
                            total_pages=total_pages)
    dense = paged = None
    per_sample = []
    for rep in range(SAMPLES):
        if rep % 2 == 0:
            d, p = measure(dense_eng), measure(paged_eng)
        else:
            p, d = measure(paged_eng), measure(dense_eng)
        per_sample.append({
            "dense_tokens_per_s": d["tokens_per_s"],
            "paged_tokens_per_s": p["tokens_per_s"],
            "speedup_tokens_per_s":
                p["tokens_per_s"] / d["tokens_per_s"],
            "effective_batch_ratio":
                p["effective_batch"] / d["effective_batch"],
        })
        if dense is None or d["makespan_s"] < dense["makespan_s"]:
            dense = d
        if paged is None or p["makespan_s"] < paged["makespan_s"]:
            paged = p
    dense_eng.shutdown()
    paged_eng.shutdown()
    var = _variance(per_sample)

    emit("serve.paged.dense_baseline",
         dense["makespan_s"] / useful_tokens * 1e6,
         f"{dense['tokens_per_s']:.0f}_tok_per_s_batch{dense['effective_batch']}")
    emit("serve.paged.paged_pool",
         paged["makespan_s"] / useful_tokens * 1e6,
         f"{paged['tokens_per_s']:.0f}_tok_per_s_batch{paged['effective_batch']}"
         f"_hits{paged['prefix_hits']}")
    emit("serve.paged.effective_batch", 0.0,
         f"{paged['effective_batch'] / dense['effective_batch']:.2f}x"
         f"_at_{dense_slots * dense_cache_len}_cached_tokens")
    emit("serve.paged.speedup", 0.0,
         f"{paged['tokens_per_s'] / dense['tokens_per_s']:.3f}x")

    _append_block("paged", {
        "workload": {"n_requests": n_requests, "prompt_len": prompt_len,
                     "shared_prefix_len": shared_len, "lengths": lengths,
                     "arrivals_s": arrivals,
                     "cached_tokens_budget": dense_slots * dense_cache_len},
        "samples": SAMPLES,
        "dense": dense, "paged": paged,
        "effective_batch_ratio":
            paged["effective_batch"] / dense["effective_batch"],
        "speedup_tokens_per_s":
            paged["tokens_per_s"] / dense["tokens_per_s"],
        "variance": var,
    })


# ==================== fused paged-attention kernel vs unfused steps
def bench_serve_kernel() -> None:
    """Fused paged-attention serving (ONE kernel: on-device page-table
    gather + flash-attend + accept-masked KV write) vs the unfused
    gather/scatter paged steps, at identical pool geometry and workload.

    Tokens/s ratio plus static ``cost_analysis`` (flops / bytes accessed)
    of the two compiled decode steps — the unfused step materializes a
    ``(S, max_pages*page_size, KV, hd)`` contiguous view per layer and
    scatters written pages back, traffic the fused kernel never emits.
    Also records ``fused_kernel_active``: whether this runner lowers the
    real Pallas kernel (TPU) or the jnp reference fallback (CPU CI) —
    the regression gate only enforces the fused >= dense floor when the
    real kernel ran. Appends a ``kernel`` block to BENCH_serve.json.
    """
    import jax
    import numpy as np_
    from repro.configs import get_config
    from repro.kernels import impl as impl_mod
    from repro.models import lm
    from repro.serve import Request, ServeEngine

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    n_requests = 6 if QUICK else 12
    n_slots, page_size, prompt_len, max_seq = 4, 8, 16, 64
    length = 24
    repeats = max(3, SAMPLES)
    useful_tokens = n_requests * length

    def make_engine(fused):
        eng = ServeEngine(cfg, params, max_batch=n_slots,
                          max_cache_len=max_seq, paged=True, fused=fused,
                          page_size=page_size, max_seq_len=max_seq)
        wbase = np_.arange(prompt_len) + 300
        warm = [Request(wbase, 4),
                Request(np_.concatenate([wbase[:8], np_.arange(8) + 400]),
                        4)]
        for r in warm:                # warms prefill, suffix, decode
            eng.submit(r)
        eng._bench_done = len(warm)
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=200)
        return eng

    def trial(eng, rep):
        prompts = [np_.arange(prompt_len) + 17 * rep + 31 * i
                   for i in range(n_requests)]
        reqs = [Request(p % (cfg.vocab_size - 1), length) for p in prompts]
        t0 = time.monotonic()
        for r in reqs:
            eng.submit(r)
        eng._bench_done += n_requests
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=300)
        return time.monotonic() - t0

    def step_cost(eng):
        """Static compiled-cost of one decode step (flops/bytes)."""
        import jax.numpy as jnp_
        args = [eng.params, eng.pool.arrays,
                jnp_.zeros((n_slots, 1, 1), jnp_.int32),
                jnp_.zeros((n_slots,), jnp_.int32),
                jnp_.zeros((n_slots, eng._table_pages), jnp_.int32)]
        if eng.fused:
            args.append(jnp_.ones((n_slots,), jnp_.int32))
        ca = eng._decode_fn.lower(*args).compile().cost_analysis()
        return {"flops": float(ca["flops"]),
                "bytes_accessed": float(ca["bytes accessed"])}

    fused_eng, unfused_eng = make_engine(True), make_engine(False)
    fused_best = unfused_best = None
    per_rep = []
    for rep in range(repeats):   # interleave so load drift hits both
        if rep % 2 == 0:
            f, u = trial(fused_eng, rep), trial(unfused_eng, rep)
        else:
            u, f = trial(unfused_eng, rep), trial(fused_eng, rep)
        per_rep.append({"fused_tokens_per_s": useful_tokens / f,
                        "unfused_tokens_per_s": useful_tokens / u,
                        "speedup_tokens_per_s": u / f})
        fused_best = f if fused_best is None else min(fused_best, f)
        unfused_best = u if unfused_best is None else min(unfused_best, u)
    var = _variance(per_rep)

    fused_cost = step_cost(fused_eng)
    unfused_cost = step_cost(unfused_eng)
    active = impl_mod.resolve() == "pallas"
    fused_eng.shutdown()
    unfused_eng.shutdown()

    fused_tps = useful_tokens / fused_best
    unfused_tps = useful_tokens / unfused_best
    emit("serve.kernel.fused", fused_best / useful_tokens * 1e6,
         f"{fused_tps:.0f}_tok_per_s_{'pallas' if active else 'xla_ref'}")
    emit("serve.kernel.unfused", unfused_best / useful_tokens * 1e6,
         f"{unfused_tps:.0f}_tok_per_s")
    emit("serve.kernel.speedup", 0.0,
         f"{fused_tps / unfused_tps:.3f}x_fused_vs_unfused")
    if unfused_cost["bytes_accessed"]:
        emit("serve.kernel.step_bytes_ratio", 0.0,
             f"{fused_cost['bytes_accessed'] / unfused_cost['bytes_accessed']:.3f}"
             "x_fused_vs_unfused")

    _append_block("kernel", {
        "workload": {"n_requests": n_requests, "n_slots": n_slots,
                     "prompt_len": prompt_len, "length": length,
                     "page_size": page_size, "max_seq_len": max_seq,
                     "repeats_best_of": repeats},
        "samples": repeats,
        "fused_kernel_active": active,
        "fused": {"tokens_per_s": fused_tps, "makespan_s": fused_best,
                  "step_cost": fused_cost},
        "unfused": {"tokens_per_s": unfused_tps,
                    "makespan_s": unfused_best,
                    "step_cost": unfused_cost},
        "speedup_tokens_per_s": fused_tps / unfused_tps,
        "variance": var,
    })


# ========================= beyond paper: self-speculative decoding
def bench_serve_spec() -> None:
    """Speculative (draft/verify) vs plain paged decode at EQUAL cache
    memory on a repetition-friendly workload (tiled-motif prompts whose
    greedy continuations settle into cycles — the regime prompt-lookup
    drafting targets). Tokens/s and accept rate; both engines share the
    same pool geometry, warmed through every shape (cold prefill, shared
    suffix, verify, retirement continuations) before timing. Appends a
    ``spec`` block to BENCH_serve.json.
    """
    import jax
    import numpy as np_
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Request, ServeEngine

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    n_requests = 6 if QUICK else 10
    n_slots, page_size, prompt_len, max_seq = 4, 8, 16, 64
    speculate, length = 4, 48
    repeats = max(3, SAMPLES)
    motif = np_.array([5, 11, 3, 7])
    useful_tokens = n_requests * length

    def make_engine(spec_k):
        eng = ServeEngine(cfg, params, max_batch=n_slots,
                          max_cache_len=max_seq, paged=True,
                          page_size=page_size, max_seq_len=max_seq,
                          speculate=spec_k)
        # warm every shape the trace hits: cold prefill, shared-prefix
        # suffix, decode/verify, retirement continuations. Token ids stay
        # inside the reduced vocab (512) and clear of the measured
        # prompts' range (< ~220), so warm pages can never alias them.
        wbase = np_.arange(prompt_len) + 300
        warm = [Request(wbase, 6),
                Request(np_.concatenate([wbase[:12], np_.arange(4) + 400]),
                        6),
                Request(np_.arange(prompt_len) + 450, 6)]
        for r in warm:
            eng.submit(r)
        eng._bench_done = len(warm)
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=200)
        # drop warm-phase counters so the reported (and gated) accept
        # rate / step counts reflect only the measured trace
        eng.stats.update(steps=0, verify_steps=0, slot_steps=0,
                         padded_steps=0, spec_tokens=0, draft_proposed=0,
                         draft_accepted=0)
        return eng

    def trial(eng, rep):
        # shift token values per repeat: fresh pages, no stale
        # prefix-cache hits inflating later repeats
        prompts = [np_.tile(np_.roll(motif, i % 4), prompt_len // 4)
                   + 101 * rep + i // 4 for i in range(n_requests)]
        reqs = [Request(p, length) for p in prompts]
        t0 = time.monotonic()
        for r in reqs:
            eng.submit(r)
        eng._bench_done += n_requests
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=300)
        return time.monotonic() - t0

    def summarize_variant(eng, best):
        m = eng.metrics()
        out = {
            "tokens_per_s": useful_tokens / best,
            "makespan_s": best,
            "verify_steps": m["verify_steps"],
            "steps": m["steps"],
            "accept_rate": m.get("accept_rate_engine", 0.0),
            "draft_proposed": m["draft_proposed"],
            "draft_accepted": m["draft_accepted"],
        }
        eng.shutdown()
        return out

    # interleave baseline/speculative trials (alternating order each
    # repeat) so machine-load drift hits both variants alike; report
    # each variant's best repeat
    base_eng, spec_eng = make_engine(0), make_engine(speculate)
    base_best = spec_best = None
    per_rep = []
    for rep in range(repeats):
        if rep % 2 == 0:
            b, s = trial(base_eng, rep), trial(spec_eng, rep)
        else:
            s, b = trial(spec_eng, rep), trial(base_eng, rep)
        per_rep.append({"baseline_tokens_per_s": useful_tokens / b,
                        "spec_tokens_per_s": useful_tokens / s,
                        "speedup_tokens_per_s": b / s})
        base_best = b if base_best is None else min(base_best, b)
        spec_best = s if spec_best is None else min(spec_best, s)
    var = _variance(per_rep)
    base = summarize_variant(base_eng, base_best)
    spec = summarize_variant(spec_eng, spec_best)

    emit("serve.spec.paged_baseline",
         base["makespan_s"] / useful_tokens * 1e6,
         f"{base['tokens_per_s']:.0f}_tok_per_s")
    emit("serve.spec.speculative",
         spec["makespan_s"] / useful_tokens * 1e6,
         f"{spec['tokens_per_s']:.0f}_tok_per_s"
         f"_accept{spec['accept_rate']:.2f}")
    emit("serve.spec.accept_rate", 0.0,
         f"{spec['accept_rate']:.3f}"
         f"_{spec['draft_accepted']}of{spec['draft_proposed']}")
    emit("serve.spec.speedup", 0.0,
         f"{spec['tokens_per_s'] / base['tokens_per_s']:.3f}x")

    _append_block("spec", {
        "workload": {"n_requests": n_requests, "n_slots": n_slots,
                     "prompt_len": prompt_len, "length": length,
                     "page_size": page_size, "max_seq_len": max_seq,
                     "speculate": speculate, "repeats_best_of": repeats},
        "samples": repeats,
        "paged_baseline": base,
        "speculative": spec,
        "speedup_tokens_per_s":
            spec["tokens_per_s"] / base["tokens_per_s"],
        "variance": var,
    })


# ===================== beyond paper: streaming session API (per-token)
def bench_serve_stream() -> None:
    """Streaming (per-token continuation delivery through ``TokenStream``)
    vs retirement delivery (``submit()``: tokens observable only when the
    request finishes) on the same engine geometry and workload.

    The streaming claims, measured as ratios so CI can gate them
    hardware-portably:

    * ``ttft_speedup`` — mean time to the first *observable* token:
      retirement-mode first-observable (= request latency) over streaming
      TTFT. First tokens must arrive well before retirement.
    * ``tokens_per_s_ratio`` — streaming tokens/s over retirement
      tokens/s: the inter-token overhead of per-token delivery. On CPU
      this reads ~0.8-1.0 — each token wakes a consumer thread, and the
      GIL handoff steals cycles from the Python-heavy dispatch path —
      while the decode loop itself never blocks on a consumer (the
      failure mode the gate exists for, which lands at 0.1-0.3x).

    Inter-token p99 gap is recorded (ms, informational). Appends a
    ``stream`` block to BENCH_serve.json.
    """
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import GenerationConfig, Request, ServeClient, \
        ServeEngine
    from repro.serve.request import _percentile

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # one request per slot: no admission queueing, so the measured TTFT
    # gap is purely delivery timing (first step completion vs retirement)
    # — the serve.* block already measures batching under oversubscription
    n_requests = n_slots = 4
    prompt_len, length = 8, 32
    max_seq = prompt_len + length
    repeats = max(3 if QUICK else 5, SAMPLES)
    prompts = jax.random.randint(jax.random.PRNGKey(5),
                                 (n_requests, prompt_len), 0, cfg.vocab_size)
    useful_tokens = n_requests * length

    def make_engine():
        eng = ServeEngine(cfg, params, max_batch=n_slots,
                          max_cache_len=max_seq)
        warm = [Request(prompts[0], 2), Request(prompts[1], 2)]
        for r in warm:
            eng.submit(r)
        eng._bench_done = len(warm)
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=200)
        return eng

    def batch_trial(eng):
        """Retirement delivery: tokens observable at finish only."""
        reqs = [Request(prompts[i], GenerationConfig(max_tokens=length))
                for i in range(n_requests)]
        t0 = time.monotonic()
        for r in reqs:
            r.arrival_time = time.monotonic()
            eng.submit(r)
        eng._bench_done += n_requests
        eng.run(until=lambda: len(eng.retired) == eng._bench_done,
                timeout=300)
        first_observable = [r.finish_time - r.arrival_time for r in reqs]
        return max(r.finish_time for r in reqs) - t0, first_observable

    def stream_trial(client):
        """Per-token delivery: consumers time every token's arrival."""
        session = client.session(max_tokens=length)
        times = [[] for _ in range(n_requests)]
        streams = [None] * n_requests

        def consume(i):
            for _ in streams[i]:
                times[i].append(time.monotonic())

        t0 = time.monotonic()
        threads = []
        for i in range(n_requests):
            streams[i] = session.generate(prompts[i])
            threads.append(threading.Thread(target=consume, args=(i,)))
            threads[-1].start()
        for t in threads:
            t.join()
        makespan = max(ts[-1] for ts in times) - t0
        ttfts = [ts[0] - s.request.arrival_time
                 for ts, s in zip(times, streams)]
        gaps = [b - a for ts in times for a, b in zip(ts, ts[1:])]
        return makespan, ttfts, gaps

    # interleave the two variants (alternating order per repeat) so
    # machine-load drift hits both alike; report each variant's best
    batch_eng = make_engine()
    stream_client = ServeClient(engine=make_engine())
    batch_best = stream_best = None
    batch_first, stream_ttfts, stream_gaps = [], [], []
    per_rep = []
    for rep in range(repeats):
        if rep % 2 == 0:
            b = batch_trial(batch_eng)
            s = stream_trial(stream_client)
        else:
            s = stream_trial(stream_client)
            b = batch_trial(batch_eng)
        per_rep.append({
            "ttft_speedup": (sum(b[1]) / len(b[1]))
            / (sum(s[1]) / len(s[1])),
            "tokens_per_s_ratio": b[0] / s[0],
        })
        if batch_best is None or b[0] < batch_best:
            batch_best, batch_first = b
        if stream_best is None or s[0] < stream_best:
            stream_best, stream_ttfts, stream_gaps = s
    var = _variance(per_rep)
    batch_eng.shutdown()
    stream_client.close()

    batch_tps = useful_tokens / batch_best
    stream_tps = useful_tokens / stream_best
    ttft_stream = sum(stream_ttfts) / len(stream_ttfts)
    ttft_batch = sum(batch_first) / len(batch_first)
    inter_p99 = _percentile(sorted(stream_gaps), 0.99)
    ttft_speedup = ttft_batch / ttft_stream
    tps_ratio = stream_tps / batch_tps

    emit("serve.stream.stream_delivery", stream_best / useful_tokens * 1e6,
         f"{stream_tps:.0f}_tok_per_s_ttft_{ttft_stream * 1e3:.0f}ms")
    emit("serve.stream.retirement_baseline",
         batch_best / useful_tokens * 1e6,
         f"{batch_tps:.0f}_tok_per_s_first_observable_"
         f"{ttft_batch * 1e3:.0f}ms")
    emit("serve.stream.ttft_speedup", 0.0, f"{ttft_speedup:.3f}x")
    emit("serve.stream.inter_token_p99", inter_p99 * 1e6, "per_gap")
    emit("serve.stream.tokens_per_s_ratio", 0.0,
         f"{tps_ratio:.3f}x_vs_retirement")

    _append_block("stream", {
        "workload": {"n_requests": n_requests, "n_slots": n_slots,
                     "prompt_len": prompt_len, "length": length,
                     "repeats_best_of": repeats},
        "samples": repeats,
        "streaming": {"tokens_per_s": stream_tps,
                      "makespan_s": stream_best,
                      "ttft_mean_s": ttft_stream,
                      "inter_token_p99_s": inter_p99},
        "retirement": {"tokens_per_s": batch_tps,
                       "makespan_s": batch_best,
                       "first_observable_mean_s": ttft_batch},
        "ttft_speedup": ttft_speedup,
        "tokens_per_s_ratio": tps_ratio,
        "variance": var,
    })


# ==================== beyond paper: disaggregated prefill/decode roles
def bench_serve_disagg() -> None:
    """Disaggregated prefill/decode (role engines connected by the
    continuation transport, KV pages shipped per-block as chunked prefill
    produces them) vs the colocated paged engine on the same workload and
    decode geometry.

    Reported as a ratio so CI stays hardware-portable:

    * ``tokens_per_s_ratio`` — disaggregated tokens/s over colocated.
      Recorded (not gated): in-process the transport hop is pure
      overhead — export slices, typed messages, per-block install — so
      the interesting signal is how CLOSE the role split stays to
      colocated (~0.7-1.0x on CPU), i.e. the price of an honest
      transport boundary before multi-host shipping makes it pay.
    * TTFT mean for both: the prefill role delivers the first token
      itself, so disaggregation must not regress time-to-first-token.
    * ``bytes_shipped_per_request`` — KV actually crossing the boundary
      (prompt pages × page_nbytes), from the transport's per-tag
      accounting.

    Appends a ``disagg`` block to BENCH_serve.json.
    """
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Request, serve_requests
    from repro.serve.disagg import DisaggServer

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_requests = 6 if QUICK else 12
    prompt_len, length = 12, 24
    page_size = 4
    max_seq = prompt_len + length
    key = jax.random.PRNGKey(11)
    prompts = jax.random.randint(key, (n_requests, prompt_len), 0,
                                 cfg.vocab_size)
    useful_tokens = n_requests * length

    def mk_reqs():
        rs = [Request(prompts[i], length) for i in range(n_requests)]
        for r in rs:
            r.arrival_time = time.monotonic()
        return rs

    def colocated_trial():
        reqs = mk_reqs()
        t0 = time.monotonic()
        serve_requests(cfg, params, reqs, max_batch=4,
                       max_cache_len=max_seq, paged=True,
                       page_size=page_size, max_seq_len=max_seq,
                       timeout=600)
        dt = time.monotonic() - t0
        ttfts = [r.ttft for r in reqs if r.ttft is not None]
        return dt, sum(ttfts) / len(ttfts)

    def disagg_trial():
        reqs = mk_reqs()
        srv = DisaggServer(cfg, params, max_batch=4, max_cache_len=max_seq,
                           page_size=page_size, max_seq_len=max_seq,
                           chunk_pages=1)
        t0 = time.monotonic()
        try:
            for r in reqs:
                srv.submit(r)
            srv.close_intake()
            srv.run(timeout=600)
            dt = time.monotonic() - t0
            m = srv.metrics()
        finally:
            srv.shutdown()
        ttfts = [r.ttft for r in reqs if r.ttft is not None]
        return dt, sum(ttfts) / len(ttfts), m

    # warm both compile caches, then best-of-N with interleaved order
    colocated_trial()
    disagg_trial()
    repeats = max(2 if QUICK else 3, SAMPLES)
    colo_best = dis_best = None
    colo_ttft = dis_ttft = 0.0
    dis_metrics = {}
    per_rep = []
    for rep in range(repeats):
        trials = (colocated_trial, disagg_trial) if rep % 2 == 0 \
            else (disagg_trial, colocated_trial)
        rep_colo = rep_dis = None
        for t in trials:
            if t is colocated_trial:
                dt, ttft = t()
                rep_colo = dt
                if colo_best is None or dt < colo_best:
                    colo_best, colo_ttft = dt, ttft
            else:
                dt, ttft, m = t()
                rep_dis = dt
                if dis_best is None or dt < dis_best:
                    dis_best, dis_ttft, dis_metrics = dt, ttft, m
        per_rep.append({
            "colocated_tokens_per_s": useful_tokens / rep_colo,
            "disagg_tokens_per_s": useful_tokens / rep_dis,
            "tokens_per_s_ratio": rep_colo / rep_dis,
        })
    var = _variance(per_rep)

    colo_tps = useful_tokens / colo_best
    dis_tps = useful_tokens / dis_best
    tps_ratio = dis_tps / colo_tps
    bytes_per_req = dis_metrics["bytes_shipped_per_request"]

    emit("serve.disagg.disaggregated", dis_best / useful_tokens * 1e6,
         f"{dis_tps:.0f}_tok_per_s_ttft_{dis_ttft * 1e3:.0f}ms")
    emit("serve.disagg.colocated_baseline",
         colo_best / useful_tokens * 1e6,
         f"{colo_tps:.0f}_tok_per_s_ttft_{colo_ttft * 1e3:.0f}ms")
    emit("serve.disagg.tokens_per_s_ratio", 0.0,
         f"{tps_ratio:.3f}x_vs_colocated")
    emit("serve.disagg.bytes_shipped_per_request", 0.0,
         f"{bytes_per_req:.0f}B_{dis_metrics['blocks_shipped']}_blocks")

    _append_block("disagg", {
        "workload": {"n_requests": n_requests, "prompt_len": prompt_len,
                     "length": length, "page_size": page_size,
                     "chunk_pages": 1, "repeats_best_of": repeats},
        "samples": repeats,
        "disaggregated": {"tokens_per_s": dis_tps, "makespan_s": dis_best,
                          "ttft_mean_s": dis_ttft},
        "colocated": {"tokens_per_s": colo_tps, "makespan_s": colo_best,
                      "ttft_mean_s": colo_ttft},
        "tokens_per_s_ratio": tps_ratio,
        "bytes_shipped_per_request": bytes_per_req,
        "blocks_shipped": dis_metrics["blocks_shipped"],
        "variance": var,
    })


# ==================== beyond paper: multi-replica front door (router)
def bench_serve_router() -> None:
    """The multi-replica front door (prefix-affinity routing, tenant
    fairness, heartbeat failover) over 2 replicas vs one colocated engine
    on a shared-prefix trace, plus a failover drill.

    * ``affinity_hit_rate`` — fraction of dispatches routed by prefix
      affinity on the shared-prefix trace (4 prefix groups; each group's
      first request is an unavoidable miss, the rest must follow their
      prefix). GATED: deterministic by construction (optimistic digest
      insert at dispatch), so the floor sits just above the 0.8 design
      target.
    * ``tokens_per_s_ratio`` — router-over-2-replicas tokens/s over one
      colocated engine. Recorded only: in one process the replicas share
      the CPU, so this is the price of the routing/control plane
      (~0.7-1.0x), not a throughput win.
    * failover drill — a second wave on the same router; one replica is
      killed mid-decode and the heartbeat sweep must requeue its work
      with zero requests lost and token-identical greedy output
      (also enforced by tests/serve/test_router.py in CI).

    Appends a ``router`` block to BENCH_serve.json.
    """
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Request, Router, serve_requests

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    per_group = 6 if QUICK else 10
    n_groups, shared_len, length = 4, 8, 12
    page_size = 4
    max_seq = shared_len + 1 + length
    prompts = [list(range(1 + 10 * g, 1 + 10 * g + shared_len)) + [200 + i]
               for g in range(n_groups) for i in range(per_group)]
    n_requests = len(prompts)
    useful_tokens = n_requests * length
    kw = dict(max_batch=4, max_cache_len=max_seq, paged=True,
              page_size=page_size, max_seq_len=max_seq)

    def mk_reqs():
        rs = [Request(p, length) for p in prompts]
        for r in rs:
            r.arrival_time = time.monotonic()
        return rs

    def colocated_trial():
        reqs = mk_reqs()
        t0 = time.monotonic()
        serve_requests(cfg, params, reqs, timeout=600, **kw)
        dt = time.monotonic() - t0
        ttfts = [r.ttft for r in reqs if r.ttft is not None]
        return dt, sum(ttfts) / len(ttfts), \
            {tuple(p): list(r.tokens) for p, r in zip(prompts, reqs)}

    colo_best, colo_ttft, expected = colocated_trial()
    dt, ttft, _ = colocated_trial()        # best-of-2, first warms compile
    if dt < colo_best:
        colo_best, colo_ttft = dt, ttft

    # saturation >= trace size: the bench measures AFFINITY, so the
    # fallback path (covered by tests) must not add timing-dependent
    # misses — exactly one miss per prefix group remains
    router = Router(cfg, params, n_replicas=2, saturation=n_requests,
                    heartbeat_timeout_s=0.1, sweep_interval_s=0.01, **kw)
    # untimed warmup: compile both replicas' step functions. Prompts are
    # disjoint from the trace prefixes so the affinity measurement keeps
    # its exactly-one-miss-per-group structure.
    warm = [Request(list(range(400 + 10 * i, 400 + 10 * i + shared_len)), 2)
            for i in range(4)]
    for r in warm:
        router.submit(r)
    router.run(timeout=600, until=lambda: len(router.retired) == len(warm))
    hits0, routed0 = (router.stats["affinity_hits"],
                      router.stats["routed"])
    reqs = mk_reqs()
    t0 = time.monotonic()
    for r in reqs:
        router.submit(r)
    router.run(timeout=600,
               until=lambda: len(router.retired) == len(warm) + n_requests)
    rout_best = time.monotonic() - t0
    hit_rate = (router.stats["affinity_hits"] - hits0) \
        / (router.stats["routed"] - routed0)
    ttfts = [r.ttft for r in reqs if r.ttft is not None]
    rout_ttft = sum(ttfts) / len(ttfts)

    # failover drill: same router (warm compile caches), second wave;
    # kill whichever replica is observed mid-decode first
    wave = mk_reqs()
    for r in wave:
        router.submit(r)
    victim, deadline = None, time.monotonic() + 300
    while victim is None and time.monotonic() < deadline:
        router.step()
        for t in router._tracked.values():
            if t.rank is not None and t.original.delivered >= 2:
                victim = t.rank
                break
    router.kill_replica(victim)
    router.close_intake()
    router.run(timeout=600)
    zero_loss = sum(1 for r in wave if r.req_state.value == "finished") \
        == n_requests
    identical = all(r.tokens == expected[tuple(p)]
                    for p, r in zip(prompts, wave))
    m2 = router.metrics()
    router.shutdown()

    colo_tps = useful_tokens / colo_best
    rout_tps = useful_tokens / rout_best
    tps_ratio = rout_tps / colo_tps

    emit("serve.router.routed", rout_best / useful_tokens * 1e6,
         f"{rout_tps:.0f}_tok_per_s_ttft_{rout_ttft * 1e3:.0f}ms")
    emit("serve.router.colocated_baseline",
         colo_best / useful_tokens * 1e6,
         f"{colo_tps:.0f}_tok_per_s_ttft_{colo_ttft * 1e3:.0f}ms")
    emit("serve.router.affinity_hit_rate", 0.0,
         f"{hit_rate:.3f}_over_{n_requests}_requests")
    emit("serve.router.failover", 0.0,
         f"zero_loss_{zero_loss}_identical_{identical}_requeued_"
         f"{m2['requeued']}")

    _append_block("router", {
        "workload": {"n_requests": n_requests, "prefix_groups": n_groups,
                     "shared_len": shared_len, "length": length,
                     "page_size": page_size, "n_replicas": 2},
        "affinity_hit_rate": hit_rate,
        "tokens_per_s_ratio": tps_ratio,
        "router": {"tokens_per_s": rout_tps, "makespan_s": rout_best,
                   "ttft_mean_s": rout_ttft},
        "colocated": {"tokens_per_s": colo_tps, "makespan_s": colo_best,
                      "ttft_mean_s": colo_ttft},
        "failover": {"zero_loss": zero_loss, "token_identical": identical,
                     "failovers": m2["failovers"],
                     "requeued": m2["requeued"]},
    })


# ================== beyond paper: trace-replay harness over every tier
def bench_serve_trace() -> None:
    """One seeded mixed workload — bursty on/off arrivals, heavy-tailed
    output lengths, shared-prefix groups, two tenants, two priorities,
    per-request deadlines — replayed through ALL three serving tiers
    (colocated ``ServeEngine``, disaggregated ``DisaggServer``,
    multi-replica ``Router``) by the ``repro.bench`` harness, ``SAMPLES``
    samples each, reported as SLO verdicts (goodput under deadline,
    p50/p99/p99.9 TTFT and inter-token latency, mean/cv/ci95 per metric).

    Then a saturation sweep on the colocated engine: binary-search the
    max offered QPS at which the SLO still holds, rescaling the SAME
    trace (same prompts, same ordering — only the arrival clock moves).
    Appends a ``trace`` block to BENCH_serve.json.
    """
    import jax
    from repro.bench import (Replayer, SLO, slo_report, sweep_tier,
                             synthetic_trace)
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Router, ServeEngine
    from repro.serve.disagg import DisaggServer

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_requests = 8 if QUICK else 16
    prompt_len, max_seq = 12, 64
    kw = dict(max_batch=4, max_cache_len=max_seq, page_size=8,
              max_seq_len=32)
    trace = synthetic_trace(
        n_requests, seed=1009, vocab_size=cfg.vocab_size,
        arrival="onoff", rate_qps=40.0, mean_burst=4.0, mean_off_s=0.15,
        prompt_len=(prompt_len, prompt_len), output_len=(4, 16),
        output_alpha=1.2, n_prefix_groups=2, shared_len=8,
        tenants={"alpha": 2.0, "beta": 1.0}, priorities={0: 3.0, 1: 1.0},
        deadline_s=30.0, name="serve_mix")
    # correctness-shaped SLO: everything must finish inside its deadline
    # and first tokens must land in single-digit seconds even on a
    # throttled CI runner — a hung tier or admission bug fails it
    slo = SLO(ttft_p99_s=10.0, min_finished_frac=1.0,
              min_deadline_met_frac=1.0)

    tiers = (
        ("engine", lambda: ServeEngine(cfg, params, paged=True, **kw)),
        ("disagg", lambda: DisaggServer(cfg, params, chunk_pages=1, **kw)),
        ("router", lambda: Router(cfg, params, n_replicas=2,
                                  saturation=2 * n_requests, paged=True,
                                  **kw)),
    )
    # the sweep needs enough offered work that overload visibly queues:
    # same shapes as the main trace (no fresh compiles on the warm
    # replayer) but more, longer requests and a TTFT bound that holds at
    # trickle rates and breaks once arrivals outrun decode capacity
    sweep_trace = synthetic_trace(
        32 if QUICK else 48, seed=1013, vocab_size=cfg.vocab_size,
        arrival="poisson", rate_qps=20.0, prompt_len=(prompt_len,
                                                      prompt_len),
        output_len=(12, 16), output_alpha=1.2, n_prefix_groups=2,
        shared_len=8, name="serve_sweep")
    sweep_slo = SLO(ttft_p99_s=0.15, min_finished_frac=1.0)
    reports = {}
    sweep_doc = None
    for name, factory in tiers:
        with Replayer(factory, name=name) as rp:
            results = rp.run(trace, samples=SAMPLES, timeout=600)
            rep = slo_report(results, slo)
            reports[name] = rep
            m = rep["metrics"]
            tok = max(1.0, m["tokens_per_s"]["mean"])
            emit(f"serve.trace.{name}", 1e6 / tok,
                 f"{m['tokens_per_s']['mean']:.0f}_tok_per_s_goodput_"
                 f"{m['goodput_tokens_per_s']['mean']:.0f}_ttft_p99_"
                 f"{m['ttft_p99_s']['mean'] * 1e3:.0f}ms_slo_"
                 f"{'ok' if rep['slo']['ok'] else 'VIOLATED'}")
            if name == "engine":
                # saturation sweep on the warm colocated engine
                sweep = sweep_tier(rp, sweep_trace, sweep_slo,
                                   lo_qps=8.0, hi_qps=150.0,
                                   iters=2 if QUICK else 3)
                sweep_doc = dict(sweep.to_dict(),
                                 slo=sweep_slo.to_dict(),
                                 trace=sweep_trace.meta)
                mq = sweep.max_qps
                emit("serve.trace.sweep_max_qps", 0.0,
                     f"{'none' if mq is None else f'{mq:.1f}'}_qps_"
                     f"{len(sweep.points)}_probes"
                     f"{'_range_saturated' if sweep.saturated_range else ''}")

    _append_block("trace", {
        "workload": dict(trace.meta, n_requests=n_requests),
        "samples": SAMPLES,
        "slo": slo.to_dict(),
        "tiers": reports,
        "sweep": sweep_doc,
    })


# ======================= beyond paper: observability overhead (obs)
def bench_serve_obs() -> None:
    """Tracing overhead on the colocated serving path: one seeded trace
    replayed on the same warm engine with tracing off vs sampled-on
    (``Recorder(sample=0.5)``), interleaved off/on per sample so
    machine-load drift hits both modes alike. The gated claim: tokens/s
    with tracing on stays >= 0.95x of tracing off.

    Side effects: writes ``trace.json`` (the Chrome/Perfetto export of
    the traced samples — the CI artifact next to BENCH_serve.json) and
    appends an ``obs`` block with the overhead ratio plus the recorder's
    SLO cause attribution (queue delay vs compute vs shipping vs
    notification latency).
    """
    import jax
    from repro.bench import Replayer, synthetic_trace
    from repro.configs import get_config
    from repro.models import lm
    from repro.obs import Recorder
    from repro.serve import ServeEngine

    cfg = get_config("paper_demo", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_requests = 8 if QUICK else 16
    trace = synthetic_trace(
        n_requests, seed=1021, vocab_size=cfg.vocab_size,
        arrival="poisson", rate_qps=50.0, prompt_len=(12, 12),
        output_len=(8, 16), output_alpha=1.2, n_prefix_groups=2,
        shared_len=8, name="serve_obs")
    rec = Recorder(sample=0.5)
    offs, ons, ratios = [], [], []
    with Replayer(lambda: ServeEngine(cfg, params, paged=True,
                                      max_batch=4, max_cache_len=64,
                                      page_size=8, max_seq_len=32),
                  name="engine") as rp:
        for _ in range(SAMPLES):
            off = rp.run(trace, samples=1, timeout=600)[0]
            rp.recorder = rec       # traced window: this sample only
            on = rp.run(trace, samples=1, timeout=600)[0]
            rp.recorder = None
            off_tok = off.metrics()["tokens_per_s"]
            on_tok = on.metrics()["tokens_per_s"]
            offs.append(off_tok)
            ons.append(on_tok)
            ratios.append({"trace_overhead_tokens_per_s":
                           on_tok / max(off_tok, 1e-9)})
    var = _variance(ratios)
    ratio = var["trace_overhead_tokens_per_s"]["mean"]
    rec.write("trace.json")
    cause = rec.cause_summary()
    emit("serve.obs.off", 0.0,
         f"{sum(offs) / len(offs):.0f}_tok_per_s_untraced")
    emit("serve.obs.on", 0.0,
         f"{sum(ons) / len(ons):.0f}_tok_per_s_sample_{rec.sample:g}")
    emit("serve.obs.overhead", 0.0,
         f"{ratio:.3f}x_on_vs_off_{cause['events']}_events_"
         f"{cause['dropped']}_dropped")
    _append_block("obs", {
        "workload": dict(trace.meta, n_requests=n_requests),
        "samples": SAMPLES,
        "sample_rate": rec.sample,
        "off_tokens_per_s": sum(offs) / len(offs),
        "on_tokens_per_s": sum(ons) / len(ons),
        "trace_overhead_tokens_per_s": ratio,
        "cause": cause,
        "trace_json": "trace.json",
        "variance": var,
    })


# ========================= beyond paper: API layer (flags + await bridge)
def bench_api() -> None:
    """Per-registration flag overhead and awaitable-bridge notification
    latency vs the raw ``cb(statuses, cb_data)`` surface.

    * ``core.api.flags.*`` — registration+trigger+run cost with and
      without a per-registration ``ContinueFlags`` override (the price of
      resolving flags at registration).
    * ``core.api.notify.*`` — time from completion to handler for a batch
      of K in-flight ops: raw inline callbacks vs ``await
      asyncio.gather(*map(engine.wrap, ops))``. The gated claim: the
      awaitable bridge costs <= 25% over raw callbacks (loop-thread
      resolutions set futures directly; no call_soon hop).

    Appends an ``api`` block to BENCH_serve.json for the regression gate.
    """
    import asyncio
    from repro.core import ContinueFlags, Engine, Status
    from repro.core.completable import Completable

    class Op(Completable):
        @property
        def supports_push(self):
            return True

        def trigger(self):
            self._complete(Status())

    eng = Engine()
    cr = eng.continue_init()
    n_reg = 600 if QUICK else 3000

    def reg_plain():
        op = Op()
        eng.continue_when(op, lambda st, d: None, cr=cr)
        op.trigger()

    flags = ContinueFlags(enqueue_complete=False, on_error="raise")

    def reg_flagged():
        op = Op()
        eng.continue_when(op, lambda st, d: None, cr=cr, flags=flags)
        op.trigger()

    us_plain = _timeit(reg_plain, n_reg)
    us_flagged = _timeit(reg_flagged, n_reg)
    cr.wait(timeout=10)
    flags_ratio = us_flagged / us_plain
    emit("core.api.flags.register_plain", us_plain, "incl_trigger+run")
    emit("core.api.flags.register_flagged", us_flagged,
         f"{flags_ratio:.3f}x_vs_plain")

    # -- notification latency at batch K: completion -> handler ran
    K = 128
    rounds = 40 if QUICK else 80

    def raw_batch() -> float:
        ops = [Op() for _ in range(K)]
        done = []
        for op in ops:
            eng.continue_when(op, lambda st, d: done.append(d), cr=cr)
        t0 = time.perf_counter()
        for op in ops:
            op.trigger()          # push discovery -> inline callback
        while len(done) < K:
            eng.tick()
        return (time.perf_counter() - t0) / K * 1e6

    async def await_batch() -> float:
        ops = [Op() for _ in range(K)]
        proms = [eng.wrap(op) for op in ops]
        t0 = time.perf_counter()
        for op in ops:
            op.trigger()          # resolution inline on the loop thread
        for p in proms:
            await p               # direct __await__: no per-promise Task
        return (time.perf_counter() - t0) / K * 1e6

    async def gather_batch() -> float:
        # informational: asyncio.gather wraps each awaitable in a Task —
        # fan-in machinery on top of the bridge, not the bridge itself
        ops = [Op() for _ in range(K)]
        proms = [eng.wrap(op) for op in ops]
        t0 = time.perf_counter()
        for op in ops:
            op.trigger()
        await asyncio.gather(*proms)
        return (time.perf_counter() - t0) / K * 1e6

    # interleave raw / direct-await / gather rounds so machine-load drift
    # hits all three alike; report each variant's best (min) round — the
    # ratio of minima is the load-independent cost comparison the CI gate
    # needs on shared runners
    async def interleaved():
        raws, directs, gathers = [], [], []
        for _ in range(rounds):
            raws.append(raw_batch())
            directs.append(await await_batch())
            gathers.append(await gather_batch())
        return raws, directs, gathers

    raws, directs, gathers = asyncio.run(interleaved())
    raw_us, await_us, gather_us = min(raws), min(directs), min(gathers)
    var = _variance([{"raw_vs_await_ratio": r / d}
                     for r, d in zip(raws, directs)])
    eng.shutdown()

    emit("core.api.notify.raw_callback", raw_us, "us_per_completion")
    emit("core.api.notify.await_bridge", await_us,
         f"{await_us / raw_us:.3f}x_vs_raw")
    emit("core.api.notify.await_overhead", 0.0,
         f"{(await_us / raw_us - 1.0) * 100:.1f}pct")
    emit("core.api.notify.gather_bridge", gather_us,
         f"{gather_us / raw_us:.3f}x_vs_raw_incl_task_wrap")

    _append_block("api", {
        "flags_register_plain_us": us_plain,
        "flags_register_flagged_us": us_flagged,
        "flags_overhead_ratio": flags_ratio,
        "notify_batch": K,
        "samples": rounds,
        "raw_callback_us": raw_us,
        "await_bridge_us": await_us,
        "gather_bridge_us": gather_us,
        "await_vs_raw_ratio": await_us / raw_us,
        # gated form: higher is better, floor 0.8 == "<= 25% overhead"
        "raw_vs_await_ratio": raw_us / await_us,
        "variance": var,
    })


# bench_api must run after bench_serve: bench_serve (re)creates
# BENCH_serve.json from scratch; api/paged/spec blocks append to it
ALL_BENCHES = (bench_notification, bench_scheduler, bench_zones,
               bench_dataflow, bench_offload, bench_loc,
               bench_train_overlap, bench_serve, bench_serve_paged,
               bench_serve_kernel, bench_serve_spec, bench_serve_stream,
               bench_serve_disagg, bench_serve_router,
               bench_serve_trace, bench_serve_obs, bench_api)
QUICK_BENCHES = (bench_notification, bench_scheduler, bench_loc,
                 bench_serve, bench_serve_paged, bench_serve_kernel,
                 bench_serve_spec, bench_serve_stream,
                 bench_serve_disagg, bench_serve_router,
                 bench_serve_trace, bench_serve_obs, bench_api)


def _append_history(args: argparse.Namespace) -> None:
    """One compact record per invocation into benchmarks/history.jsonl —
    git SHA, timestamp, gated/recorded metric values, sample count — so
    metric drift is greppable across commits without digging through CI
    artifacts. Best-effort: a partial run records what it measured."""
    import datetime
    import os
    import subprocess

    try:
        with open("BENCH_serve.json") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return                       # no serve blocks ran (e.g. --only zones)
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    try:
        import check_regression      # benchmarks/ is sys.path[0]
    except ImportError:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_regression",
            os.path.join(bench_dir, "check_regression.py"))
        check_regression = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(check_regression)
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=bench_dir,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    recorded = {}
    for name, fn in check_regression.RECORDED.items():
        try:
            recorded[name] = round(float(fn(doc)), 4)
        except (KeyError, TypeError, ZeroDivisionError):
            pass
    record = {
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "git_sha": sha,
        "quick": bool(args.quick),
        "only": args.only,
        "samples": SAMPLES,
        "metrics": {k: round(v, 4)
                    for k, v in check_regression.extract(doc).items()},
        "recorded": recorded,
    }
    path = os.path.join(bench_dir, "history.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")
    print(f"# appended run record to {path}", flush=True)


def main() -> None:
    global QUICK, SAMPLES
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke subset at reduced sizes")
    ap.add_argument("--only", default=None, metavar="BLOCK",
                    help="run a single block (e.g. 'serve', 'dataflow')")
    ap.add_argument("--samples", type=int, default=3, metavar="N",
                    help="measured samples per serve scenario; feeds the "
                    "mean/cv/ci95 variance fields in BENCH_serve.json")
    args = ap.parse_args()
    from repro.compile_cache import configure
    configure()
    QUICK = args.quick
    SAMPLES = max(1, args.samples)
    benches = QUICK_BENCHES if args.quick else ALL_BENCHES
    if args.only:
        benches = [b for b in ALL_BENCHES
                   if b.__name__ == f"bench_{args.only}"]
        if not benches:
            raise SystemExit(f"unknown block {args.only!r}")
    print("# name,us_per_call,derived")
    for bench in benches:
        print(f"# --- {bench.__name__} ---", flush=True)
        bench()
    _append_history(args)


if __name__ == "__main__":
    main()
