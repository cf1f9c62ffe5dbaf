"""The serve loop's own record (``repro.obs``) beside the device trace.

``loop_view`` lays the loop's spans on the trace's clock through the
``obs.anchor`` stamps (``Tracer.anchor``, taken inside a profiler
annotation of the same name while the trace runs): each decode launch
beside its dispatch, its continuation and its commit, and the device's
idle time split by loop phase. It works on ``reduce_trace.load``'s event
lists and the tracer's drained events; ``harness.run`` keeps neither the
raw events nor anchors, so no per-layer metric reads it yet.

``queue_waits`` reads each request's arrival-to-placement span from a
run's tracer, for the queue-wait readers in ``perf/metrics/``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perf.reduce_trace import MODULE_LINE, OP_LINE, _clip, fold, union

# the serve loop's own record (``repro.obs``): the annotation
# ``Tracer.anchor`` opens, and the span kinds read below
ANCHOR = "obs.anchor"
LOOP_KINDS = {"serve.admit": "admit", "serve.dispatch": "dispatch",
              "serve.progress": "progress", "serve.commit": "commit"}
OUTSIDE = "outside"
DECODE = "jit_step"
# how much earlier than the host launch that caused it a device event
# may read: v5e traces put a program's start up to 1.7 ms before the
# host call that launched it on an idle chip
CLOCK_SLACK_NS = 5e6


def anchor_map(events: Dict[str, List], anchors: Sequence[float]):
    """``(offset_ns, residual_us)``: trace ns = obs seconds * 1e9 +
    offset, from the first ``ANCHOR`` annotation and the obs stamp taken
    inside it; the residual is how far the last pair lies off that map.
    None without two anchors on both sides."""
    marks = sorted(start for _line, name, start, _dur in events["host"]
                   if name == ANCHOR)
    if len(marks) < 2 or len(marks) != len(anchors):
        return None
    offsets = [m - a * 1e9 for m, a in zip(marks, sorted(anchors))]
    return offsets[0], (offsets[-1] - offsets[0]) * 1e-3


def paint(spans: Sequence[Tuple[float, float, str]]
          ) -> List[Tuple[float, float, str]]:
    """Disjoint, ordered ``(a, b, label)`` pieces of ``spans``: where
    spans overlap, the one begun last holds the piece, so of nested
    spans the innermost does."""
    pieces: List[Tuple[float, float, str]] = []
    for a, b, label in sorted(spans):
        if b <= a:
            continue
        left, right = [], []
        while pieces and pieces[-1][1] > a:
            pa, pb, pl = pieces.pop()
            if pa < a:
                left.append((pa, a, pl))
            if pb > b:
                right.append((max(pa, b), pb, pl))
        pieces += left[::-1] + [(a, b, label)] + right[::-1]
    return pieces


def split_by(intervals: Sequence[Tuple[float, float]],
             pieces: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of the ordered, disjoint ``intervals`` under each
    piece's label; what no piece covers goes to ``OUTSIDE``."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, label = pieces[k]
            over = min(b, pb) - max(a, pa)
            if over > 0:
                out[label] += over
                covered += over
            k += 1
        out[OUTSIDE] += (b - a) - covered
    return dict(out)


def pair_launches(steps: Sequence[Tuple[int, float, Any]],
                  launches: Sequence[Tuple[float, float]],
                  commits: Dict[int, float],
                  slack: float = CLOCK_SLACK_NS
                  ) -> Optional[List[Tuple[Tuple[int, float, Any], float,
                                           float]]]:
    """``(step, start, end)`` for each launch: its device interval beside
    its decode step, one of ``steps``.

    ``steps`` is every decode dispatch the record holds, ``(seq, began,
    key)`` in dispatch order, and ``commits`` the start of the commit of
    each sequence number. One stream runs launches in dispatch order, so
    the launches are consecutive steps from some offset on. Under the
    right offset each launch starts after its step's dispatch began and
    ends before that step's commit began, within ``slack`` (the device's
    clock may read early). An offset one step late passes those too
    where each launch starts within ``slack`` of the next dispatch (a
    poll lag that short): of the offsets that pass, only the right one
    has each launch end after the commit of the sequence number before
    its own began. (A host stall can break that for the right offset;
    the late one then fails the first test at the stall.) None unless
    exactly one offset passes."""
    launches = sorted(launches)
    if not launches:
        return []

    def holds(o: int, strict: bool) -> bool:
        for (seq, began, _key), (start, end) in zip(steps[o:], launches):
            own, before = commits.get(seq), commits.get(seq - 1)
            if start < began - slack or \
                    (own is not None and end > own + slack) or \
                    (strict and before is not None and end <= before + slack):
                return False
        return True

    held = [o for o in range(len(steps) - len(launches) + 1)
            if holds(o, False)]
    if len(held) > 1:
        held = [o for o in held if holds(o, True)]
    if len(held) != 1:
        return None
    return [(step, start, end)
            for step, (start, end) in zip(steps[held[0]:], launches)]


def loop_view(events: Dict[str, List], anchors: Sequence[float],
              obs_events: Sequence[Any], t0_ns: float,
              t1_ns: float) -> Optional[Dict[str, Any]]:
    """The serve loop's ``obs`` spans laid on the device trace's clock,
    in the window ``[t0_ns, t1_ns)``:

    * ``launches``: device ``(start, end)`` ns of each launch per program
      (module line, folded names);
    * ``decode``: each decode dispatch that began in the window, with
      its ``jit_step`` launch (every decode dispatch the record holds
      paired against the launches, ``pair_launches``), its
      continuation's ``cont.ready`` and ``cont.ran`` stamps and the
      start of its ``serve.commit``, all in trace ns (None where absent);
      None where the launches pair no one way, or lie on several
      devices;
    * ``idle_by_phase``: device idle seconds (as ``reduce`` counts them)
      split by the innermost loop span that covered the host then, and
      ``OUTSIDE`` where none did; averaged over devices, so with
      ``busy_s`` it sums to ``window_s``.

    None where the trace holds no anchors to map ``obs`` time with."""
    amap = anchor_map(events, anchors)
    if amap is None:
        return None
    offset, residual_us = amap

    def at(ts: float) -> float:
        return ts * 1e9 + offset

    spans, steps, commit = [], [], {}
    posted, ready, ran = [], {}, {}
    for ev in obs_events:
        phase = LOOP_KINDS.get(ev.kind)
        if phase is not None:
            a = at(ev.ts)
            spans.append((a, a + ev.dur * 1e9, phase))
            if phase == "dispatch" and ev.meta and ev.meta[1] == DECODE:
                steps.append((ev.meta[0], a, ev))
            elif phase == "commit":
                commit.setdefault(ev.meta, a)
        elif ev.kind == "cont.posted":
            posted.append(ev)
        elif ev.kind == "cont.ready":
            ready[ev.rid] = at(ev.ts)
        elif ev.kind == "cont.ran":
            ran[ev.rid] = at(ev.ts)

    planes: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    launches: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    decode_planes = set()
    for plane, line, name, start, dur in events["device"]:
        if line == OP_LINE:
            iv = _clip(start, dur, t0_ns, t1_ns)
            if iv is not None:
                planes[plane].append(iv)
        elif line == MODULE_LINE and start < t1_ns and start + dur > t0_ns:
            launches[fold(name)].append((start, start + dur))
            if fold(name) == DECODE:
                decode_planes.add(plane)

    pairs = pair_launches(sorted(steps, key=lambda st: st[0]),
                          launches.get(DECODE, []), commit) \
        if len(decode_planes) <= 1 else None
    decode = None
    if pairs is not None:
        decode = []
        for (seq, t, span), start, end in pairs:
            if not t0_ns <= t < t1_ns:
                continue
            cont = _posted_in(posted, span)
            decode.append({"seq": seq, "dispatch": t, "start": start,
                           "end": end, "ready": ready.get(cont),
                           "ran": ran.get(cont), "commit": commit.get(seq)})

    pieces = paint(spans)
    idle: Dict[str, float] = defaultdict(float)
    busy_s = 0.0
    for iv in planes.values():
        u = union(iv)
        busy_s += sum(b - a for a, b in u) * 1e-9
        edges = [t0_ns] + [x for a, b in u for x in (a, b)] + [t1_ns]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for label, ns in split_by(gaps, pieces).items():
            idle[label] += ns * 1e-9
    n = max(len(planes), 1)
    return {"residual_us": residual_us, "window_s": (t1_ns - t0_ns) * 1e-9,
            "busy_s": busy_s / n, "devices": len(planes),
            "idle_by_phase": {k: v / n for k, v in idle.items()},
            "launches": dict(launches), "decode": decode}


def _posted_in(posted: Sequence[Any], span: Any) -> Optional[int]:
    """The continuation a dispatch span registered: the last
    ``cont.posted`` its thread recorded inside the span."""
    found = None
    for ev in posted:
        if ev.tid == span.tid and span.ts <= ev.ts <= span.ts + span.dur:
            found = ev.rid
    return found


def queue_waits(run: Any) -> Optional[List[float]]:
    """Arrival to placement (s) of each request due in the window that
    was placed: its ``req.admit`` span in the run's tracer. None without
    a tracer, or where it dropped events, so that a partial record never
    reads as whole."""
    if run.obs is None or run.obs.dropped:
        return None
    due = {r.request.req_id: r.due for r in run.records}
    return [ev.dur for ev in run.obs.drain() if ev.kind == "req.admit"
            and run.ws <= due.get(ev.rid, -1.0) < run.we]
