"""What an adapter hands the harness: one cell's program and yardstick."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax


@dataclasses.dataclass
class Cell:
    """Built by ``perfbench.adapters.<kind>.build(config, mix, mesh)``.

    Nothing here is a device array: the state and the batch pool are made
    by :attr:`make` from the seed, so the same cell can be lowered from
    :attr:`state_shapes` and :attr:`batch_shapes` alone, for a chip that is
    described and not attached (``perfbench/tests/test_chip_compile.py``).
    """

    # step(*state, *batch) -> (*state, loss); jitted, state donated.
    step: Callable
    # ShapeDtypeStructs, with shardings, of the state and of one batch.
    state_shapes: Tuple[Any, ...]
    batch_shapes: Tuple[Any, ...]
    # make(seed, pool) -> (state, [batch] * pool): one jitted call that
    # makes weights and batches on the device, in the step's shardings.
    make: Callable[[int, int], Tuple[Tuple[Any, ...], list]]
    # Model FLOPs of one global step (forward + backward, no recompute).
    flops_per_step: float
    # What a step consumes, over all chips: ("tokens", 32768).
    item: str
    items_per_step: int
    # First step from zero momentum: gradient = (new - old) * this.
    grad_per_delta: float
    # checked(state) -> {name: array}: the leaves check (b) looks at.
    checked: Callable[[Tuple[Any, ...]], Dict[str, Any]]
    # reference(state, batch) -> (loss, {name: gradient}) by the plain
    # float32 reference, of the global batch mean.
    reference: Callable[[Tuple[Any, ...], Tuple[Any, ...]],
                        Tuple[Any, Dict[str, Any]]]
    # The step's Pallas kernels, per step and chip: {name: {"match":
    # [substrings of the trace events' text], "flops": ..., "bytes": ...}}.
    # Where there is one, the lowered step must hold a Mosaic custom call.
    kernels: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)


def seed_key(seed: int):
    """The key a cell's weights and batches are drawn from.  The ``rbg``
    generator uses the chip's own random-bit instruction: the program that
    fills 1.4 G weights compiles and runs in a fraction of the default
    threefry's time (PERF.md, PR 22), and set-up is most of what a check
    costs.  Same seed, same chip kind and same shardings: same numbers."""
    return jax.random.key(seed, impl="rbg")


def seeded(make_arrays, state_shardings, batch_shardings):
    """``(make, state_shapes, batch_shapes)`` of a :class:`Cell` from
    ``make_arrays(key, pool) -> (state, [batch] * pool)``, which is traced,
    never called: ``make(seed, pool)`` runs it as one jitted call whose
    outputs land in the given shardings (each a tree, or a prefix of one,
    as ``jax.jit`` takes them)."""

    def make(seed: int, pool: int):
        jitted = jax.jit(functools.partial(make_arrays, pool=pool),
                         out_shardings=(state_shardings,
                                        [batch_shardings] * pool))
        return jitted(seed_key(seed))

    def with_shardings(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda sh, sub: jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh), sub),
            shardings, shapes,
            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))

    state, batches = jax.eval_shape(
        functools.partial(make_arrays, pool=1), seed_key(0))
    return (make, with_shardings(state, state_shardings),
            with_shardings(batches[0], batch_shardings))


def on_first_device(tree, mesh):
    """``tree`` on the mesh's first device, where the reference runs: a
    replicated array's own shard (no copy of gigabytes of weights),
    anything else gathered there."""
    device = mesh.devices.flat[0]

    def pick(x):
        if x.is_fully_replicated:
            return next(s.data for s in x.addressable_shards
                        if s.device == device)
        return jax.device_put(x, device)

    return jax.tree_util.tree_map(pick, tree)
