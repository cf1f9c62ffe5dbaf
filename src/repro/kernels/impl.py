"""Kernel implementation selection.

Models call kernel ``ops`` wrappers; the active implementation is resolved
per-call → :func:`use_impl` override → backend default:

* ``"pallas"``           — real TPU lowering (the deployment target, and
                           the default wherever JAX's backend is a TPU),
* ``"pallas_interpret"`` — kernel body interpreted on CPU (tests),
* ``"xla"``              — the pure-jnp reference path (the CPU default,
                           and the dry-run/roofline compiles, which target
                           the CPU backend where TPU Pallas cannot lower).
"""
from __future__ import annotations

import contextlib

import jax

VALID = ("xla", "pallas", "pallas_interpret")
_override: str | None = None


def backend_default() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve(impl: str | None = None) -> str:
    if impl is None:
        impl = _override or backend_default()
    if impl not in VALID:
        raise ValueError(f"unknown kernel impl {impl!r}; expected {VALID}")
    return impl


@contextlib.contextmanager
def use_impl(impl: str):
    """Override the implementation for every thread while the block runs:
    a ``ServeClient`` traces its steps on its own loop thread, which a
    thread-local override would miss."""
    global _override
    prev, _override = _override, resolve(impl)
    try:
        yield
    finally:
        _override = prev
