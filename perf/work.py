"""Operations and bytes the served work needs, from the configuration's
published sizes (real query heads, real vocabulary) and the positions of
the tokens served. Nothing here reads the program's grid or padding.

A decode token at position ``pos`` (its query is token ``pos``; the
cache holds positions ``0 .. pos-1``) attends over ``pos + 1`` keys.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Tuple

BF16 = 2
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


def dims(spec: Dict[str, Any]) -> Dict[str, int]:
    return {"d": spec["hidden_size"], "ff": spec["intermediate_size"],
            "L": spec["num_hidden_layers"],
            "H": spec["num_attention_heads"],
            "KV": spec["num_key_value_heads"], "hd": spec["head_dim"],
            "V": spec["vocab_size"]}


def layer_matmul_params(spec: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one layer."""
    m = dims(spec)
    d, H, KV, hd = m["d"], m["H"], m["KV"], m["hd"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * m["ff"]


def attention_flops(spec: Dict[str, Any], keys: int) -> int:
    """QK and PV of one query over ``keys`` keys, all layers."""
    m = dims(spec)
    return 4 * m["L"] * m["H"] * m["hd"] * keys


def head_flops(spec: Dict[str, Any]) -> int:
    m = dims(spec)
    return 2 * m["d"] * m["V"]


def prompt_flops(spec: Dict[str, Any], plen: int) -> int:
    """Prefill of a ``plen``-token prompt: every layer on every token,
    causal attention at each token's own length, and the head on the
    last token only (the one whose logits give the first output)."""
    m = dims(spec)
    return (2 * m["L"] * layer_matmul_params(spec) * plen
            + attention_flops(spec, plen * (plen + 1) // 2)
            + head_flops(spec))


def decode_flops(spec: Dict[str, Any], pos: int) -> int:
    """One decode token at position ``pos``."""
    m = dims(spec)
    return (2 * m["L"] * layer_matmul_params(spec)
            + attention_flops(spec, pos + 1) + head_flops(spec))


def paged_attention_work(spec: Dict[str, Any], pos: int) -> Tuple[int, int]:
    """(flops, bytes) the paged-attention kernel needs for one decode
    token at ``pos``, all layers: read the ``pos`` cached k/v rows and
    the new k/v row, write the new row, read q and write o."""
    m = dims(spec)
    L, H, KV, hd = m["L"], m["H"], m["KV"], m["hd"]
    row = 2 * KV * hd * BF16                 # one position's k and v
    nbytes = L * (row * pos + 2 * row + 2 * H * hd * BF16)
    return attention_flops(spec, pos + 1), nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> float:
    """Least time for the work over the time it took, in percent."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds

