"""From a profiler trace to the device numbers the metrics read.

``load`` turns an ``.xplane.pb`` into plain event lists: device events
``(plane, line, name, start_ns, dur_ns)`` from the planes named
``/device:...`` and host events ``(line, name, start_ns, dur_ns)`` from
``/host:CPU``. ``reduce`` then works on those lists alone, clipped to a
window ``[t0_ns, t1_ns)`` of the trace's clock:

* ``busy_s``: the union of the op intervals on each device, averaged over
  the devices, and ``idle_share`` = 1 - busy / window;
* ``ops``: device seconds per op name (``name.N`` folded into ``name``;
  an op nested in another, as a kernel in a ``while`` body, counts in
  both);
* ``programs``: device seconds and launch count per jitted program, from
  the module line (``jit_step(123)`` folded into ``jit_step``);
* ``idle_gaps``: the longest gaps in the union, each named by what the
  host was doing in it (the innermost host event covering half of it).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# host annotation the harness opens at each end of the traced window
MARK = "perf.window"

DevEvent = Tuple[str, str, str, float, float]
HostEvent = Tuple[str, str, float, float]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    return paths[0]


def load(path: str) -> Dict[str, List]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev: List[DevEvent] = []
    host: List[HostEvent] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                for e in line.events:
                    dev.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append((line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    return {"device": dev, "host": host}


def marked_window(events: Dict[str, List]) -> Tuple[float, float]:
    """The trace-clock span between the first and last ``MARK``."""
    marks = sorted(start for _line, name, start, _dur in events["host"]
                   if name == MARK)
    if len(marks) < 2:
        raise ValueError(f"{len(marks)} window marks in the trace")
    return marks[0], marks[-1]


def fold(name: str) -> str:
    """Op or program name without its instance suffix. Op events on a TPU
    carry the instruction's HLO text (``%paged_attention.4 = (...)
    custom-call(...)``); their name is the instruction's."""
    m = re.match(r"%?([\w.\-]+) = ", name)
    if m:
        name = m.group(1)
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"\.\d+$", "", name)


def _clip(start: float, dur: float, t0: float, t1: float):
    a, b = max(start, t0), min(start + dur, t1)
    return (a, b) if b > a else None


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(events: Dict[str, List], t0_ns: float, t1_ns: float,
           n_gaps: int = 10) -> Dict[str, Any]:
    planes: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for plane, line, name, start, dur in events["device"]:
        iv = _clip(start, dur, t0_ns, t1_ns)
        if iv is None:
            continue
        if line == OP_LINE:
            planes[plane].append(iv)
            ops[fold(name)] += (iv[1] - iv[0]) * 1e-9
        elif line == MODULE_LINE:
            p = programs[fold(name)]
            p[0] += (iv[1] - iv[0]) * 1e-9
            p[1] += 1
    window_s = (t1_ns - t0_ns) * 1e-9
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "idle_share": 1.0,
                "ops": {}, "programs": {}, "idle_gaps": [], "devices": 0}
    busy = {p: union(iv) for p, iv in planes.items()}
    busy_s = sum(sum(b - a for a, b in u) for u in busy.values()) \
        * 1e-9 / len(busy)
    gaps = []
    for u in busy.values():
        edges = [t0_ns] + [x for iv in u for x in iv] + [t1_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = events["host"]
    named = [[_busiest_host(host, a, b), (b - a) * 1e-9]
             for a, b in gaps[:n_gaps]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "ops": dict(ops),
            "programs": {k: {"seconds": v[0], "launches": v[1]}
                         for k, v in programs.items()},
            "idle_gaps": named, "devices": len(busy)}


def _busiest_host(host: Sequence[HostEvent], a: float, b: float) -> str:
    """What the host was doing in ``[a, b)``: the shortest host event
    that covers at least half of it (the innermost span), else the one
    that covers most of it."""
    half = (b - a) / 2
    inner, inner_dur = None, float("inf")
    most, most_cover = "no host event", 0.0
    for _line, name, start, dur in host:
        iv = _clip(start, dur, a, b)
        if iv is None:
            continue
        cover = iv[1] - iv[0]
        if cover >= half and dur < inner_dur:
            inner, inner_dur = name, dur
        if cover > most_cover:
            most, most_cover = name, cover
    return inner if inner is not None else most


def top_ops(reduced: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(reduced["ops"].items(),
                                      key=lambda kv: -kv[1])[:n]]
