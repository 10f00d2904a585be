"""Shared varying-manual-axes (vma) helpers for shard_map scan carries.

A scan carry's vma type must be stable across iterations: after one step
the online state varies over every axis the inputs vary over, so initial
zeros must be pcast up to the union of the inputs' vma sets.
"""

from __future__ import annotations

import jax
from jax import lax


def vma_of(x) -> set:
    """The value's varying-manual-axes set (empty outside shard_map and
    under ``check_vma=False``)."""
    return set(jax.typeof(x).vma)


def pin_to(target: set):
    """Returns f(x) that pcasts ``x`` up to vary over ``target`` (no-op on
    axes it already varies over, and outside a mesh)."""
    def _pin(x):
        missing = tuple(sorted(target - vma_of(x)))
        if not missing:
            return x
        return lax.pcast(x, missing, to="varying")
    return _pin
