"""A configuration's file, and the architecture module it names.

A configuration is ``perf/configs/<name>.json``: the published sizes,
the program's registry entry and overrides, and ``"arch"``, which names
``perf/arch/<arch>.py`` as a mix's ``"generator"`` names its generator.
The architecture module holds everything of one layer stack that the
benchmark needs, from the seed and the published description alone:

* ``check(spec)``: raise ``ValueError`` if a size it needs is missing;
* ``make_params(spec, cfg, seed, device)``: the weights, made on
  ``device`` from ``seed`` in one jitted call, laid out as the program
  reads them (``cfg`` is the program's ``ModelConfig``);
* ``Reference(spec, params, length)``: the float32 reference, a
  ``perf.reference.TeacherForced`` with ``logits(seq, fp8)``;
* ``prompt_flops(spec, plen)``, ``decode_flops(spec, pos)`` and
  ``paged_attention_work(spec, pos)``: the work counts the per-layer
  readers take through ``perf/work.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"
ARCH_DIR = Path(__file__).resolve().parent / "arch"

_MODULES: Dict[Path, Any] = {}


def load(name: str) -> Dict[str, Any]:
    """The configuration's file, checked by its architecture module."""
    path = CONFIG_DIR / f"{name}.json"
    spec = json.loads(path.read_text())
    try:
        arch(spec).check(spec)
    except ValueError as exc:
        raise ValueError(f"{path}: {exc}") from exc
    return spec


def arch(spec: Dict[str, Any]):
    """The module of the architecture ``spec`` names."""
    name = spec.get("arch")
    if not name:
        raise ValueError(f"no \"arch\" key naming a module in {ARCH_DIR}")
    path = ARCH_DIR / f"{name}.py"
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise ValueError(f"no architecture module {path}")
    mod_spec = importlib.util.spec_from_file_location(f"perf_arch_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def program_config(spec: Dict[str, Any]):
    """The system under test's ``ModelConfig`` for this file."""
    from repro.configs import get_config
    return get_config(spec["registry"], **spec["overrides"])


def real_heads(n_heads: int, n_kv: int, padded: int) -> np.ndarray:
    """Slot indices of the real query heads among ``padded`` slots, in
    head order: kv head ``k`` owns slots ``[k*g, (k+1)*g)``, g = padded /
    n_kv, and its real heads are the first ``n_heads / n_kv`` of them."""
    g = padded // n_kv
    per = n_heads // n_kv
    return np.asarray([k * g + j for k in range(n_kv) for j in range(per)])


def seed_key(seed: int):
    import jax
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
