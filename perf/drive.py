"""Load from one thread through ``ServeClient.generate``, and what the
window's end-to-end metrics make of it.

Open loop: each request is due at its scheduled instant, sent then (or
as soon as the thread can), and its time to first token counts from the
schedule, so a stall's queueing stays in the tail. Closed loop: each
client sends its next request when its last one completes (a ready hook
on the ``Request`` wakes the thread); time to first token counts from the
send. The thread stops sending at the window's end.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from perf.generate import Req


@dataclasses.dataclass
class Record:
    req: Req
    client: int
    due: float          # scheduled (open) or send (closed) instant
    sent: float         # when generate() was called
    request: Any        # the program's Request


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile, ``q`` in [0, 1]."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] * (1.0 - (pos - lo)) + s[hi] * (pos - lo))


class Load:
    """Sends the requests ``draw(client)`` gives through ``client`` from
    one thread, starting at ``t0`` and stopping at ``t_end`` (monotonic
    seconds)."""

    def __init__(self, client: Any, spec: Dict[str, Any],
                 draw: Callable[[int], Req], t0: float,
                 t_end: float) -> None:
        self.client, self.spec, self.draw = client, spec, draw
        self.t0, self.t_end = t0, t_end
        self.records: List[Record] = []
        self.error: Optional[BaseException] = None
        self._session = client.session()
        self._stop = threading.Event()
        self._done: "queue.Queue[int]" = queue.Queue()
        self._thread = threading.Thread(target=self._run, name="perf-load",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> List[Record]:
        self._stop.set()
        self._done.put(-1)
        self._thread.join()
        if self.error is not None:
            raise self.error
        return list(self.records)

    def _send(self, r: Req, client: int, due: float) -> None:
        stream = self._session.generate(r.prompt, max_tokens=r.max_tokens)
        rec = Record(r, client, due, time.monotonic(), stream.request)
        self.records.append(rec)
        if self.spec["loop"] == "closed":
            stream.request.add_ready_hook(
                lambda op, status, c=client: self._done.put(c))

    def _run(self) -> None:
        try:
            if self.spec["loop"] == "open":
                self._open()
            else:
                self._closed()
        except BaseException as exc:   # surfaced by stop()
            self.error = exc

    def _sleep_until(self, t: float) -> bool:
        """False when told to stop first."""
        return not self._stop.wait(max(0.0, t - time.monotonic()))

    def _open(self) -> None:
        while True:
            r = self.draw(0)
            due = self.t0 + r.arrival_s
            if due >= self.t_end or not self._sleep_until(due):
                return
            self._send(r, 0, due)

    def _closed(self) -> None:
        if not self._sleep_until(self.t0):
            return
        for c in range(int(self.spec["clients"])):
            self._send(self.draw(c), c, time.monotonic())
        while True:
            c = self._done.get()
            if c < 0 or self._stop.is_set():
                return
            now = time.monotonic()
            if now >= self.t_end:
                return
            self._send(self.draw(c), c, now)


def window_metrics(records: Sequence[Record], ws: float,
                   we: float) -> Dict[str, Any]:
    """End-to-end numbers over the window ``[ws, we)``.

    * TTFT of every request due in the window: first token minus due, or
      the window's end minus due when none came in the window;
    * every gap between two consecutive tokens of a request that were
      both delivered in the window;
    * tokens delivered in the window over its length.
    """
    ttft, gaps = [], []
    tokens = 0
    due = [r for r in records if ws <= r.due < we]
    for r in records:
        times = [t for t in list(r.request.token_times) if ws <= t < we]
        tokens += len(times)
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    for r in due:
        first = r.request.token_times[:1]
        if first and first[0] < we:
            ttft.append(first[0] - r.due)
        else:
            ttft.append(we - r.due)
    return {"ttft_s": ttft, "gaps_s": gaps, "tokens": tokens,
            "due": len(due), "window_s": we - ws}


def end_to_end(w: Dict[str, Any]) -> Dict[str, float]:
    return {
        "ttft_p50_ms": 1e3 * percentile(w["ttft_s"], 0.50),
        "ttft_p95_ms": 1e3 * percentile(w["ttft_s"], 0.95),
        "itl_p50_ms": 1e3 * percentile(w["gaps_s"], 0.50),
        "itl_p95_ms": 1e3 * percentile(w["gaps_s"], 0.95),
        "tokens_per_s": w["tokens"] / w["window_s"],
    }
