"""The general generator: requests whose sizes come from fixed sets.

A mix (``perf/traffic/<name>.json`` with ``"generator": "sized"``) gives
prompt and output lengths as fixed value sets with weights, the loop
(``closed`` with a client count, or ``open`` with a Poisson rate), a ramp
before the window, and optionally shared prefixes. Every seed draws the
same multiset of sizes and of inter-arrival gaps, in another order:

* lengths come in blocks of ``block`` requests, each holding every value
  exactly ``weight * block`` times, prompt and output lengths permuted
  independently per block;
* open-loop gaps come in blocks of ``arrival_block``, each holding the
  exponential distribution's quantiles at ``(j + 0.5) / arrival_block``,
  permuted per block;
* a closed loop starts near its steady state: each client's first
  request keeps the fraction ``(c + 0.5) / clients`` of its output
  length (at least 2 tokens), the fractions permuted per seed, as if
  every client were part-way through a request when the ramp begins;
* with ``"prefix": {"groups": g, "len": n}``, group ``k`` has its own
  ``n``-token prefix drawn from the seed (an application's system
  prompt), client ``c`` belongs to group ``c % g`` (open loop: request
  ``i`` to group ``i % g``), and each prompt is its group's prefix
  followed by a tail whose length ``prompt_len`` gives.

So two seeds differ in order and in token ids, never in the amount of
work, and only the fixed set of shapes ever reaches the program.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from perf.generate import Req


def check(spec: Dict[str, Any]) -> None:
    if spec["loop"] not in ("open", "closed"):
        raise ValueError("loop must be open or closed")
    for key in ("prompt_len", "output_len"):
        counts(spec[key], spec["block"])
    if spec["loop"] == "open" and not spec.get("rate_per_s"):
        raise ValueError("an open loop needs rate_per_s")


def counts(dist: Dict[str, List[float]], block: int) -> List[int]:
    """How often each value occurs in one block; the weights must give
    whole counts that fill the block."""
    out = []
    for w in dist["weights"]:
        c = w * block
        if abs(c - round(c)) > 1e-9:
            raise ValueError(f"weight {w} x block {block} is not whole")
        out.append(int(round(c)))
    if sum(out) != block:
        raise ValueError(f"counts {out} do not fill a block of {block}")
    return out


def _block_values(dist: Dict[str, List[float]], block: int) -> np.ndarray:
    return np.repeat(np.asarray(dist["values"], np.int64),
                     counts(dist, block))


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` quantiles of an exponential of rate ``rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _prefix_len(spec: Dict[str, Any]) -> int:
    return int(spec["prefix"]["len"]) if "prefix" in spec else 0


def shapes(spec: Dict[str, Any]) -> Dict[str, List[int]]:
    """Every prompt length, tail length and output length the mix sends."""
    tails = sorted(spec["prompt_len"]["values"])
    return {"prompt_len": [_prefix_len(spec) + t for t in tails],
            "tail_len": tails if "prefix" in spec else [],
            "output_len": sorted(spec["output_len"]["values"])}


def longest(spec: Dict[str, Any]) -> int:
    """Longest prompt plus output the mix sends."""
    s = shapes(spec)
    return max(s["prompt_len"]) + max(s["output_len"]) - 1


def mean_request(spec: Dict[str, Any]) -> Dict[str, float]:
    """Mean prompt and output length of the mix."""
    def mean(d):
        return float(np.dot(d["values"], d["weights"]))
    return {"prompt_len": _prefix_len(spec) + mean(spec["prompt_len"]),
            "output_len": mean(spec["output_len"])}


def warm_up(spec: Dict[str, Any], vocab: int,
            rng: np.random.Generator) -> List[List[Req]]:
    """Rounds of requests that reach every shape the mix can send, each
    round sent at once and served to its end: every prompt length cold;
    with shared prefixes, one prefix cold and, admitted behind it while
    it still holds its pages, every tail on a prefix hit."""
    def req(prompt, n):
        return Req(-1, np.asarray(prompt, np.int32), n, None)

    def tokens(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    s = shapes(spec)
    rounds = [[req(tokens(n), 2) for n in s["prompt_len"]]]
    if s["tail_len"]:
        prefix = tokens(_prefix_len(spec))
        rounds.append([req(np.concatenate([prefix, tokens(t)]), 2)
                       for t in [s["tail_len"][0]] + s["tail_len"]])
    return rounds


def requests(spec: Dict[str, Any], seed: int,
             vocab: int) -> Callable[[int], Req]:
    """The mix's request stream for ``seed``: ``draw(client)`` gives the
    next request, for ``client`` (0 in an open loop). The stream is
    endless; the load thread stops at the window's end. A closed loop's
    first ``clients`` requests are its clients' first, cut to their
    residual length."""
    rng = np.random.default_rng(int(seed))
    block = int(spec["block"])
    plens = _block_values(spec["prompt_len"], block)
    olens = _block_values(spec["output_len"], block)
    open_loop = spec["loop"] == "open"
    groups = int(spec["prefix"]["groups"]) if "prefix" in spec else 0
    prefixes = [rng.integers(0, vocab, _prefix_len(spec), dtype=np.int32)
                for _ in range(groups)]
    first: List[float] = []
    if not open_loop:
        n = int(spec["clients"])
        first = list(rng.permutation((np.arange(n) + 0.5) / n))
    if open_loop:
        gap_set = exp_gaps(float(spec["rate_per_s"]),
                           int(spec.get("arrival_block", 100)))
    state = {"i": 0, "t": 0.0, "gaps": [], "bp": None, "bo": None}

    def draw(client: int) -> Req:
        i = state["i"]
        j = i % block
        if j == 0:
            state["bp"], state["bo"] = rng.permutation(plens), \
                rng.permutation(olens)
        arrival = None
        if open_loop:
            if not state["gaps"]:
                state["gaps"] = list(rng.permutation(gap_set))
            arrival = state["t"]
            state["t"] += state["gaps"].pop()
        prompt = rng.integers(0, vocab, int(state["bp"][j]), dtype=np.int32)
        if groups:
            group = (i if open_loop else client) % groups
            prompt = np.concatenate([prefixes[group], prompt])
        out = int(state["bo"][j])
        if first:
            out = max(2, int(np.ceil(out * first.pop())))
        state["i"] = i + 1
        return Req(i, prompt, out, arrival)

    return draw
