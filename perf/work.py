"""Operations and bytes the served work needs, and the chip's peaks.

The counts come from the configuration's architecture module
(``perf/arch/<arch>.py``, named by its ``"arch"``), from the published
sizes and the positions of the tokens served; nothing here reads the
program's grid or padding. The per-layer readers reach them as
``run.work``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

from perf import model

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    """A device kind with no row in ``peaks.json``."""


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]


def prompt_flops(spec: Dict[str, Any], plen: int) -> int:
    """Prefill of a ``plen``-token prompt, up to its first output."""
    return model.arch(spec).prompt_flops(spec, plen)


def decode_flops(spec: Dict[str, Any], pos: int) -> int:
    """One decode token at position ``pos``."""
    return model.arch(spec).decode_flops(spec, pos)


def paged_attention_work(spec: Dict[str, Any], pos: int) -> Tuple[int, int]:
    """(flops, bytes) the paged-attention kernel needs for one decode
    token at ``pos``, all layers."""
    return model.arch(spec).paged_attention_work(spec, pos)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time for the work over the time it took, in percent."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
