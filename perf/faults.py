"""Faults planted under the timed path, for the tests that show a broken
program comes out ``correct: false``. Each replaces the engine's jitted
decode step with a broken copy of the program's own fused step:

* ``stale_state``: the step returns the KV pool it was given, so no
  token's keys and values are ever written;
* ``token``: every token is altered where the step produces it;
* ``half_batch``: the second half of the slots gets the first half's
  tokens, as if half the batch had been left out.

A one-chip serve cell has no exchange between chips to leave out.
"""
from __future__ import annotations

from typing import Any

FAULTS = ("stale_state", "token", "half_batch")


def install(engine: Any, name: str) -> None:
    import jax
    from repro.serve.steps import make_fused_paged_decode_step

    step = make_fused_paged_decode_step(engine.cfg, engine.page_size)
    vocab = engine.cfg.vocab_size

    def stale_state(params, pool, *args):
        nxt, _ = step(params, pool, *args)
        return nxt, pool

    def token(params, pool, *args):
        nxt, new = step(params, pool, *args)
        return (nxt + 1) % vocab, new

    def half_batch(params, pool, *args):
        nxt, new = step(params, pool, *args)
        h = nxt.shape[0] // 2
        return nxt.at[nxt.shape[0] - h:].set(nxt[:h]), new

    fn = {"stale_state": stale_state, "token": token,
          "half_batch": half_batch}[name]
    engine._decode_fn = jax.jit(fn)
