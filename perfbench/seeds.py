"""Keys from ``--seed``: any whole number up to 2**64."""

from __future__ import annotations

import numpy as np


def words(seed: int) -> np.ndarray:
    """The seed as two uint32 words (a traced argument of the jitted
    generators, so every seed reuses one compiled program)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def key(w, stream: int):
    """A PRNG key for one named stream of the seed (inside jit)."""
    import jax

    k = jax.random.PRNGKey(stream)
    k = jax.random.fold_in(k, w[0])
    return jax.random.fold_in(k, w[1])
