"""One run of one cell: build, warm up, measure a window, check, report.

``run`` takes the cell's entry and files already loaded; ``perf/run.py``
is the command-line entry around it. Everything here is the benchmark's:
the program under test is reached only through its serving tiers
(``ServeEngine`` per replica, ``Router`` over several, ``ServeClient`` in
front) and read only through its counters (engine ``stats``, page-pool
``metrics()``, the ``obs`` histograms) and the device trace.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from perf import drive, generate, model, reduce_trace, work

METRICS_DIR = Path(__file__).resolve().parent / "metrics"
# the profiler traces this long inside the window, starting this far in
TRACE_SECONDS = 3.0
TRACE_START = 0.3
# the compared number when no request finished: fails every limit
NO_ANSWER = 1e9


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Compiles:
    """Counts programs lowered or compiled while ``counting`` is set."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax
        self.counting = False
        self.seen: Dict[str, int] = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw: Any) -> None:
        if self.counting and event in self.seen:
            self.seen[event] += 1


class Collections:
    """Python's garbage collections while ``counting`` is set, each as
    ``(generation, seconds, monotonic end)``: a pause of the whole
    process that the serve loop waits out."""

    def __init__(self) -> None:
        self.counting = False
        self.seen: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.counting:
            return
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        else:
            self.seen.append((info["generation"], now - self._t,
                              time.monotonic()))


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def by_name(name: str, known) -> Optional[str]:
    """The longest dotted prefix of ``name`` in ``known``: a metric kept
    apart per cell (``itl_p50_ms.chat``) reads as its quantity does
    (``itl_p50_ms``)."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        if ".".join(parts[:k]) in known:
            return ".".join(parts[:k])
    return None


def reader(name: str) -> Callable[[Any], Optional[float]]:
    """The per-layer metric's reader, ``perf/metrics/<prefix>.py`` for
    the longest dotted prefix of ``name`` that has a file."""
    known = {p.stem for p in METRICS_DIR.glob("*.py")}
    stem = by_name(name, known)
    if stem is None:
        raise FileNotFoundError(f"no reader for {name} in {METRICS_DIR}")
    spec = importlib.util.spec_from_file_location(f"perf_metric_{stem}",
                                                  METRICS_DIR / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def serve_rounds(eng: Any, reqs: List[generate.Req]) -> None:
    """Serve ``reqs`` on one replica's engine from this thread, until
    every one has finished."""
    from repro.serve import Request
    live = [Request(r.prompt, r.max_tokens) for r in reqs]
    for q in live:
        eng.submit(q)
    while not all(q.is_terminal for q in live):
        eng.step()
    bad = [q.req_state.value for q in live
           if q.req_state.value != "finished"]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")


def warm_up(eng: Any, traffic: Dict[str, Any], vocab: int,
            seed: int) -> None:
    """Compile every shape the mix can reach on one replica: the
    generator's rounds (every prompt length; every suffix behind a shared
    prefix); then every slot filled at once, so each slot index and the
    full-batch paths run; then the page-table row update the engine
    issues for each count of changed rows."""
    import jax
    rng = np.random.default_rng([seed, 1])
    rounds = generate.warm_up(traffic, vocab, rng)
    for reqs in rounds:
        serve_rounds(eng, reqs)
    n = min(generate.shapes(traffic)["prompt_len"])
    serve_rounds(eng, [generate.Req(-1, rng.integers(0, vocab, n,
                                                     dtype=np.int32), 16,
                                    None) for _ in range(eng.max_batch)])
    S, T = eng.max_batch, -(-eng.max_seq_len // eng.page_size)
    tab = jax.device_put(np.zeros((S, T), np.int32), eng.device)
    for n in range(1, S + 1):
        tab = tab.at[jax.device_put(np.arange(n, dtype=np.int32),
                                    eng.device)].set(
            jax.device_put(np.zeros((n, T), np.int32), eng.device))
    tab.block_until_ready()


def sample_finished(records: List[drive.Record], seed: int,
                    check: Dict[str, Any]) -> List[drive.Record]:
    """Finished requests for the reference: the longest, then others in
    an order drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in records if r.request.req_state.value == "finished"
            and len(r.request.tokens) == r.req.max_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + r.req.max_tokens,
                                       -r.req.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    picked, tokens = [longest], longest.req.max_tokens
    for i in order:
        if tokens >= check["min_tokens"] or \
                len(picked) >= check["max_requests"]:
            break
        picked.append(rest[i])
        tokens += rest[i].req.max_tokens
    return picked


class RunData:
    """What a per-layer reader may read."""

    def __init__(self, **kw: Any) -> None:
        self.__dict__.update(kw)

    def tokens_in(self, a: float, b: float):
        """(record, token index) of every token delivered in [a, b)."""
        for r in self.records:
            for i, t in enumerate(list(r.request.token_times)):
                if a <= t < b:
                    yield r, i


class Served:
    """The program under test for one cell: weights from the seed, the
    serving topology the mix states (``serve.replicas`` engines, one per
    chip, behind the program's ``Router`` when there are more than one),
    warm, driven by one ``ServeClient``, and measured windows of the
    cell's traffic."""

    def __init__(self, cell: Dict[str, Any], spec: Dict[str, Any],
                 traffic: Dict[str, Any], seed: int, *,
                 require_tpu: bool = True, kind: Optional[str] = None,
                 fault: Optional[str] = None) -> None:
        from repro.core import Engine
        from repro.serve import Router, ServeClient, ServeEngine
        t = time.monotonic()
        self.cell, self.spec, self.traffic = cell, spec, traffic
        self.devs = devices(cell["chips"], require_tpu)
        self.dev = self.devs[0]
        self.peak = work.peaks(kind or self.dev.device_kind)
        self.cfg = model.program_config(spec)
        settings = dict(traffic["serve"])
        n = int(settings.pop("replicas", 1))
        self.placed = [self.devs[i % cell["chips"]] for i in range(n)]
        self.params = self._params(seed)
        core = Engine()
        self.engines = [ServeEngine(self.cfg, p, engine=core, **settings)
                        for p in self._placed_params()]
        if fault:
            from perf import faults
            for eng in self.engines:
                faults.install(eng, fault)
        t_weights = time.monotonic()
        for eng in self.engines:
            warm_up(eng, traffic, spec["vocab_size"], seed)
        tier = self.engines[0] if n == 1 else \
            Router(replicas=self.engines, engine=core)
        self.client = ServeClient(engine=tier)
        self.compiles = Compiles()
        self.collections = Collections()
        self.setup_split = {"weights_s": t_weights - t,
                            "warm_up_s": time.monotonic() - t_weights,
                            "ramp_s": float(traffic["ramp_s"])}

    def _params(self, seed: int):
        import jax
        p = model.arch(self.spec).make_params(self.spec, self.cfg, seed,
                                              self.dev)
        return jax.block_until_ready(p)

    def _placed_params(self) -> List[Any]:
        """One copy of the weights per replica, on its chip."""
        import jax
        return [self.params if d == self.dev else
                jax.block_until_ready(jax.device_put(self.params, d))
                for d in self.placed]

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` for the next window (the engines are
        idle; their old weights go first, so two sets never coexist)."""
        for eng in self.engines:
            eng.params = None
        self.params = None
        gc.collect()
        self.params = self._params(seed)
        for eng, p in zip(self.engines, self._placed_params()):
            eng.params = p

    def stats(self) -> Dict[str, int]:
        """The engines' counters, summed over the replicas."""
        out: Dict[str, int] = {}
        for eng in self.engines:
            for k, v in eng.stats.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        return out

    def pool(self) -> Dict[str, Any]:
        """The fullest replica's page-pool counters."""
        pools = [eng.pool.metrics() for eng in self.engines]
        return max(pools, key=lambda p: p["peak_in_use"] / p["total_pages"])

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in set(self.placed))

    def window(self, seed: int, seconds: float, *, trace: bool = False,
               t_proc: Optional[float] = None,
               traffic: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """The ramp, then a window of ``seconds`` of ``traffic`` (the
        cell's by default); the requests still open at its end are
        cancelled and the engines drained."""
        traffic = traffic or self.traffic
        t_start = time.monotonic()
        ws = t_start + float(traffic["ramp_s"])
        we = ws + float(seconds)
        load = drive.Load(self.client, traffic, generate.requests(
            traffic, seed, self.spec["vocab_size"]), t_start, we)
        self.compiles.counting = self.collections.counting = True
        load.start()
        _sleep_until(ws)
        w: Dict[str, Any] = {"ws": ws, "we": we, "stats0": self.stats(),
                             "setup_s": None if t_proc is None
                             else time.monotonic() - t_proc,
                             "obs": None, "prof": {}}
        if trace:
            from repro import obs
            w["obs"] = obs.start()
            w["prof"] = _profile(ws + TRACE_START * seconds,
                                 min(TRACE_SECONDS, 0.4 * seconds))
        _sleep_until(we)
        records = load.stop()
        self.compiles.counting = self.collections.counting = False
        if trace:
            from repro import obs
            obs.stop()
            _reduce_profile(w["prof"])
        w.update(records=records, stats1=self.stats(), pool=self.pool(),
                 failed=sum(1 for r in records if ws <= r.due < we and
                            r.request.req_state.value in ("expired",
                                                          "cancelled")),
                 memory_peak_bytes=self.memory_peak_bytes())
        for r in records:
            if not r.request.is_terminal:
                r.request.cancel()
        while not self.client.serve.idle:
            time.sleep(0.01)
        return w

    def free_pool(self) -> None:
        """Drop the KV pools' device arrays (an engine makes new ones when
        it next admits), so the reference has the memory."""
        for eng in self.engines:
            eng.pool.arrays = None
        gc.collect()

    def close(self) -> None:
        self.client.close(timeout=60.0)
        self.free_pool()


def run(bench: Dict[str, Any], cell: Dict[str, Any], spec: Dict[str, Any],
        traffic: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        t_proc: float, require_tpu: bool = True,
        kind: Optional[str] = None,
        fault: Optional[str] = None, log=print) -> Dict[str, Any]:
    """One run; returns the result line's object."""
    sv = Served(cell, spec, traffic, seed, require_tpu=require_tpu,
                kind=kind, fault=fault)
    w = sv.window(seed, seconds, trace=trace, t_proc=t_proc)
    log(f"perf: compiles inside the window {json.dumps(sv.compiles.seen)}")
    log(f"perf: set-up split {json.dumps(sv.setup_split)}")
    sv.close()
    records, ws, we = w["records"], w["ws"], w["we"]
    window = drive.window_metrics(records, ws, we)
    log("perf: slowest first tokens (ms, prompt, output, due s into the "
        f"window) {json.dumps(_slowest(records, ws, we))}")
    log("perf: longest pause between token deliveries (s, from s into the "
        f"window) {json.dumps(_longest_pause(records, ws, we))}")
    log("perf: garbage collections inside the window (generation, s, at s "
        f"into the window) {json.dumps(_collections(sv.collections, ws))}")
    checks = {"max_logit_gap": {
        "value": max_gap(spec, traffic, sv.params, records, seed, log=log),
        "limit": traffic["check"]["max_gap"]}}
    out: Dict[str, Any] = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": window["due"], "failed": w["failed"]}
    device = {"platform": sv.dev.platform, "kind": sv.dev.device_kind,
              "count": len(sv.devs),
              "memory_peak_bytes": w["memory_peak_bytes"]}
    unit = {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}
    values: Dict[str, float] = {}
    if not trace:
        e2e = dict(drive.end_to_end(window), setup_s=w["setup_s"])
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                values[m["name"]] = e2e[by_name(m["name"], e2e)]
    else:
        prof = w["prof"]
        data = RunData(spec=spec, traffic=traffic, peak=sv.peak,
                       records=records, ws=ws, we=we,
                       stats0=w["stats0"], stats1=w["stats1"],
                       pool=w["pool"], chips=cell["chips"],
                       serve=sv.client.serve, engines=sv.engines,
                       trace=prof["reduced"], ta=prof["ta"], tb=prof["tb"],
                       obs=w["obs"], work=work)
        for m in bench["per_layer"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                v = reader(m["name"])(data)
                if v is not None:
                    values[m["name"]] = v
        red = prof["reduced"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    out["metrics"] = {k: {"value": v, "unit": unit[k]}
                      for k, v in values.items()}
    out["device"] = device
    if trace:
        out["breakdown"] = {"device_ops": reduce_trace.top_ops(red),
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out


def _slowest(records: List[drive.Record], ws: float, we: float,
             n: int = 8) -> List[List[float]]:
    """The requests due in the window with the longest time to first
    token, to tell what a tail is made of."""
    rows = []
    for r in records:
        if ws <= r.due < we:
            first = r.request.token_times[:1]
            t = first[0] if first and first[0] < we else we
            rows.append([round(1e3 * (t - r.due), 1), len(r.req.prompt),
                         r.req.max_tokens, round(r.due - ws, 2)])
    return sorted(rows, reverse=True)[:n]


def _longest_pause(records: List[drive.Record], ws: float,
                   we: float) -> List[float]:
    """The longest stretch of the window in which no request got a
    token: a stall of the whole serve loop, not of one request."""
    times = sorted([ws, we] + [t for r in records
                               for t in list(r.request.token_times)
                               if ws <= t < we])
    gap, at = max((b - a, a) for a, b in zip(times, times[1:]))
    return [round(gap, 4), round(at - ws, 3)]


def _collections(c: Collections, ws: float) -> List[List[float]]:
    """The generation-2 collections and the longest of any kind."""
    rows = [[g, round(s, 4), round(t - s - ws, 3)] for g, s, t in c.seen]
    full = [r for r in rows if r[0] == 2]
    longest = max(rows, key=lambda r: r[1]) if rows else None
    return full + ([longest] if longest and longest not in full else [])


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def _profile(t_begin: float, seconds: float) -> Dict[str, Any]:
    """Trace the device from ``t_begin`` for ``seconds``. Two host
    annotations mark the window's ends on the trace's own clock; the
    trace is read after the measured window (``_reduce_profile``), so
    reading it never stalls the load."""
    import jax
    _sleep_until(t_begin)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(reduce_trace.MARK):
        ta = time.monotonic()
    _sleep_until(ta + seconds)
    with jax.profiler.TraceAnnotation(reduce_trace.MARK):
        tb = time.monotonic()
    jax.profiler.stop_trace()
    return {"dir": log_dir, "ta": ta, "tb": tb}


def _reduce_profile(prof: Dict[str, Any]) -> None:
    log_dir = prof.pop("dir")
    try:
        events = reduce_trace.load(reduce_trace.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    t0, t1 = reduce_trace.marked_window(events)
    prof["reduced"] = reduce_trace.reduce(events, t0, t1)


def max_gap(spec, traffic, params, records, seed, *, control=False,
            log=print):
    """Served tokens against the float32 reference: the widest gap by
    which a served token's reference logit lies below the reference's
    best, over a sample of finished requests (none finished: fails).
    With ``control``, returns ``(served, control)`` gaps instead."""
    picked = sample_finished(records, seed, traffic["check"])
    log(f"perf: comparing {sum(len(r.request.tokens) for r in picked)} "
        f"served tokens of {len(picked)} requests")
    if not picked:
        return (NO_ANSWER, NO_ANSWER) if control else NO_ANSWER
    ref = model.arch(spec).Reference(spec, params,
                                     generate.longest(traffic) + 1)
    gaps = [ref.served_gaps(r.req.prompt,
                            np.asarray(r.request.tokens, np.int32),
                            control=control) for r in picked]
    served = max(g["served"] for g in gaps)
    if control:
        return served, max(g["control"] for g in gaps)
    return served
