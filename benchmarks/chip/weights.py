"""Seeded random weights: what every family's ``make`` shares.

A family (``families/<family>.py``) lays out its weights in a plain layout
of its own and draws them in one jitted call with ``draw``, from a key that
``key_of`` makes from the run's seed; ``to_program`` hands the same arrays
to the program in its parameter tree, and the family's reference reads the
plain layout.  Both sides make their weights from the seed, so the
reference takes nothing the program made.
"""
from __future__ import annotations

import jax

NORM_STD = 0.1      # norm weights are 1 + w, w ~ N(0, NORM_STD)


def key_of(seed: int):
    """A PRNG key for any whole-number seed, also those past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(key, shapes: dict, dtype) -> dict:
    """``{name: N(0, 1) * scale}`` in ``dtype`` for ``shapes`` of ``{name:
    (shape, scale)}``: one key split per name, in the names' sorted order.
    Call it inside the family's jitted ``make``."""
    keys = jax.random.split(key, len(shapes))
    return {name: (jax.random.normal(k, shape, dtype) * scale).astype(dtype)
            for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items()))}
