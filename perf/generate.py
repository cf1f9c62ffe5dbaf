"""Traffic by name: a mix's data file, and the generator it names.

A mix is ``perf/traffic/<mix>.json``: the parameters of one generator,
which its ``"generator"`` key names, ``perf/traffic/<generator>.py``.
The mix also gives ``ramp_s`` (load sent before the window), ``serve``
(the serving topology: ``replicas``, one per chip, and each replica's
engine settings) and ``check`` (the sample of finished requests the
reference compares, and its limit). A generator module provides:

* ``check(spec)``: raise ``ValueError`` on parameters it cannot serve;
* ``requests(spec, seed, vocab)``: ``draw(client) -> Req``, the endless
  stream for ``seed`` (the same seed gives the same requests);
* ``warm_up(spec, vocab, rng)``: rounds of ``Req`` that reach every
  shape the mix can send;
* ``shapes``, ``longest`` and ``mean_request``: the sizes it sends.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Req:
    index: int
    prompt: np.ndarray           # int32 token ids
    max_tokens: int
    arrival_s: Optional[float]   # offset from the traffic start (open loop)


_MODULES: Dict[Path, Any] = {}


def generator(spec: Dict[str, Any]):
    """The module of the generator ``spec`` names."""
    name = spec["generator"]
    path = TRAFFIC_DIR / f"{name}.py"
    if path in _MODULES:
        return _MODULES[path]
    mod_spec = importlib.util.spec_from_file_location(
        f"perf_traffic_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def load(name: str) -> Dict[str, Any]:
    """The mix's parameters, checked by its generator."""
    spec = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    try:
        generator(spec).check(spec)
    except ValueError as exc:
        raise ValueError(f"{name}: {exc}") from exc
    return spec


def requests(spec: Dict[str, Any], seed: int,
             vocab: int) -> Callable[[int], Req]:
    return generator(spec).requests(spec, seed, vocab)


def warm_up(spec: Dict[str, Any], vocab: int,
            rng: np.random.Generator) -> List[List[Req]]:
    return generator(spec).warm_up(spec, vocab, rng)


def shapes(spec: Dict[str, Any]) -> Dict[str, List[int]]:
    return generator(spec).shapes(spec)


def longest(spec: Dict[str, Any]) -> int:
    return generator(spec).longest(spec)


def mean_request(spec: Dict[str, Any]) -> Dict[str, float]:
    return generator(spec).mean_request(spec)
