"""What every architecture's float32 reference shares: the fp8 rounding
of its control, and the gaps of served tokens below the reference's best.

An architecture module (``perf/arch/<arch>.py``) gives a ``Reference``
that subclasses ``TeacherForced`` and provides ``logits(seq, fp8)``:
teacher-forced logits ``(length, V)`` over ``seq`` padded to ``length``,
in float32 from the published architecture, and with ``fp8`` its control
(every matrix through ``fp8_round``). ``served_gaps`` then compares
served tokens against it, the same way for every architecture.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def fp8_round(w, contract: tuple):
    """``w`` through float8 e4m3, scaled per output channel (the max over
    the contracted axes maps to e4m3's largest finite value, 448)."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class TeacherForced:
    """A reference over ``length`` positions; a subclass gives
    ``logits(seq, fp8=False)``."""

    def __init__(self, length: int):
        import jax
        import jax.numpy as jnp
        self.length = int(length)

        def gaps(ref, other_top, target):
            """Per row: how far the reference logit of ``target`` (and of
            ``other_top``) lies below the reference maximum."""
            best = jnp.max(ref, axis=-1)
            pick = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
            alt = jnp.take_along_axis(ref, other_top[:, None], -1)[:, 0]
            return best - pick, best - alt

        self._gaps = jax.jit(gaps)
        self._argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(
            jnp.int32))

    def logits(self, seq: np.ndarray, fp8: bool = False):
        raise NotImplementedError

    def served_gaps(self, prompt: np.ndarray, out: np.ndarray,
                    control: bool = False) -> Dict[str, float]:
        """Widest gap of a served token below the reference maximum over
        ``out`` (teacher-forced on prompt + out); with ``control``, also
        the widest gap of the token the fp8 control puts first."""
        import jax.numpy as jnp
        seq = np.concatenate([prompt, out]).astype(np.int32)
        if len(seq) > self.length:
            raise ValueError(f"sequence of {len(seq)} > {self.length}")
        target = np.zeros(self.length, np.int32)
        target[:len(seq) - 1] = seq[1:]
        ref = self.logits(seq)
        top = self._argmax(self.logits(seq, fp8=True)) if control \
            else jnp.zeros(self.length, jnp.int32)
        g_served, g_ctl = self._gaps(ref, top, jnp.asarray(target))
        rows = slice(len(prompt) - 1, len(seq) - 1)
        out_d = {"served": float(np.max(np.asarray(g_served)[rows]))}
        if control:
            out_d["control"] = float(np.max(np.asarray(g_ctl)[rows]))
        return out_d
