"""In-kernel multi-level queue: scan-compaction plus the queued drain loop.

The paper's GPU kernels (arXiv:1209.3314 §4) owe their speedup to a
multi-level queue: each thread block keeps a local queue of active pixels in
fast memory and only touches those, instead of sweeping the whole tile every
iteration.  This module is the TPU-native analogue used by the queued
variants of the Pallas tile solvers (DESIGN.md §2.5):

* :func:`compact_mask` — the scan-compaction primitive.  A prefix sum over
  the active mask assigns each active pixel a queue slot; a single scatter
  packs the flattened pixel indices into a fixed-capacity queue.  This is
  the vector formulation of the paper's warp-level prefix-sum queue insert
  (its Figure 7), with the capacity overflow reported instead of hidden.
* :func:`compact_flags` — the same packing for an index list that is
  *already small*: the queued rounds below produce per-contribution
  ``(target index, improved?)`` pairs of length ``F * capacity`` (F =
  neighbor count), so their compaction never touches an O(block) array.
* :func:`dilate` — one step of mask dilation (the candidate set of a
  mask-based round: last round's improved pixels plus their neighbors).
  Kept as the reference formulation; the production drain below is
  push-based and never materializes this mask.
* :func:`queued_fixed_point` — the drain loop, *push* formulation.  One
  unconditional dense round seeds the queue with the improved pixels (the
  paper's raster-init building the initial queue); every later round either
  pushes each queued pixel's value to its neighbors — touching only
  O(capacity) memory — or, when the queue overflowed, *spills* to one dense
  full-block sweep.  Spilling never drops work: the dense round is a
  superset of any queued round, so overflow costs time, not correctness.

Because IWPP updates are commutative and monotone (DESIGN.md §1), enqueuing
a pixel that cannot improve (a duplicate, or an over-eager candidate) is
idempotent: the extra evaluation recomputes the same value.  That is what
makes both the overflow/spill contract and the push rounds' duplicate
targets (two sources improving a common neighbor enqueue it twice) safe.

Everything here runs inside Pallas kernel bodies: index vectors are built
with ``broadcasted_iota`` (1-D ``iota`` does not lower on TPU) and the
compaction is one cumsum + one scatter, both vector-unit friendly.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.pattern import shiftnd
from repro.kernels import resolve_interpret

QUEUE_LOWERING_GAP = (
    "the queued tile kernels (kernel_queue=True, DESIGN.md §2.5) do not "
    "compile for a TPU yet: Mosaic has no lowering for cumsum, which "
    "compact_mask/compact_flags use (kernels/queue.py); drain with the "
    "dense kernels (kernel_queue=False) when Pallas runs compiled")


def queued_interpret(interpret: Optional[bool]) -> bool:
    """Resolve ``interpret`` for a queued kernel; raise
    ``NotImplementedError`` naming the lowering gap when it would compile."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(QUEUE_LOWERING_GAP)
    return True


def _iota1d(n: int) -> jnp.ndarray:
    """1-D [0..n) index vector via 2-D iota (TPU cannot lower 1-D iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).reshape(n)


def dilate(mask: jnp.ndarray, offsets: Sequence[Tuple[int, ...]]) -> jnp.ndarray:
    """Pixels adjacent (under ``offsets``) to a set pixel.

    Every ``Neighborhood`` offset table is symmetric, so shifting the mask
    by each offset covers both "my neighbor changed" directions.  The result
    does *not* include ``mask`` itself — callers union it in explicitly.
    """
    out = jnp.zeros_like(mask)
    for off in offsets:
        out = out | shiftnd(mask, off, fill=False)
    return out


def compact_mask(mask: jnp.ndarray, capacity: int):
    """Pack the flat indices of set pixels into a fixed-capacity queue.

    Returns ``(queue, count, overflow)``:

    * ``queue`` — int32[capacity]; the first ``min(count, capacity)`` slots
      hold the flattened indices of set pixels in raster order, remaining
      slots hold ``-1`` (the dead-slot marker).
    * ``count`` — total number of set pixels (may exceed ``capacity``).
    * ``overflow`` — ``count > capacity``; when true, indices past the
      capacity were not enqueued and the caller must fall back to a dense
      round (:func:`queued_fixed_point` does exactly that).

    ``count == capacity`` packs every index with no overflow — the boundary
    is exact.
    """
    flat = mask.reshape(-1)
    n = flat.shape[0]
    act = flat.astype(jnp.int32)
    # Exclusive prefix sum = the queue slot each active pixel would take.
    pos = jnp.cumsum(act) - act
    count = jnp.sum(act)
    idx = _iota1d(n)
    # Inactive pixels and past-capacity actives target slot `capacity`,
    # which is out of range for the queue and dropped by the scatter.
    slot = jnp.where(flat & (pos < capacity), pos, capacity)
    queue = jnp.full((capacity,), -1, jnp.int32).at[slot].set(idx, mode="drop")
    return queue, count, count > capacity


def compact_flags(indices: jnp.ndarray, flags: jnp.ndarray, capacity: int):
    """:func:`compact_mask` for an explicit (small) index list.

    Packs ``indices[i]`` for every set ``flags[i]`` into a
    ``capacity``-slot queue, preserving order; same return contract as
    :func:`compact_mask`.  Duplicate indices are packed as-is — the queued
    rounds rely on duplicate enqueues being idempotent, and ``count``
    therefore counts contributions, not distinct pixels (a conservative
    overflow trigger).
    """
    act = flags.astype(jnp.int32)
    pos = jnp.cumsum(act) - act
    count = jnp.sum(act)
    slot = jnp.where(flags & (pos < capacity), pos, capacity)
    queue = jnp.full((capacity,), -1, jnp.int32).at[slot].set(
        indices.astype(jnp.int32), mode="drop")
    return queue, count, count > capacity


def fit_seed(indices: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Statically resize a resident-queue index vector to ``capacity`` slots.

    Seeds use the :func:`compact_mask` layout — live flat indices first,
    ``-1`` dead slots after — so padding appends dead slots and truncation
    only ever drops dead ones *provided the live count fits the capacity*;
    a count above capacity makes the first drain round spill to a dense
    sweep anyway (:func:`queued_fixed_point`), so nothing is lost either
    way.
    """
    idx = indices.astype(jnp.int32).reshape(-1)
    n = idx.shape[0]
    if n >= capacity:
        return idx[:capacity]
    return jnp.concatenate([idx, jnp.full((capacity - n,), -1, jnp.int32)])


def queued_fixed_point(
    dense_round: Callable,
    queued_round: Callable,
    carry,
    *,
    max_iters: int,
    capacity: int,
    initial_queue=None,
):
    """Iterate to a fixed point, pushing from queued pixels per round.

    ``carry`` is the op-specific value state (morph: the J plane; EDT: the
    ``(vr_r, vr_c)`` pointer planes).  The two round callbacks:

    * ``dense_round(carry) -> (carry, improved)`` — one full-block sweep,
      returning the boolean plane of pixels whose value changed;
    * ``queued_round(carry, queue) -> (carry, targets, improved)`` — push
      each queued pixel's value to its neighbors, touching only those;
      returns the per-contribution flat target indices and improvement
      flags (length ``F * capacity``, duplicates allowed).

    The loop runs one unconditional dense round first (every pixel may be
    initially unstable — the same implicit seed as the dense-only kernel's
    first iteration) and compacts its improved plane into the queue.  Each
    later round drains the queue if the previous round's improvement count
    fit ``capacity``, and otherwise *spills* to another dense sweep; either
    way the improved pixels become the next queue.  Stops when a round
    improves nothing or after ``max_iters`` rounds (the initial dense round
    counts as round one).  Returns ``(carry, iters, spills)`` where
    ``spills`` counts overflow rounds after the first.

    Push rounds are bit-identical to dense rounds: a neighbor that did not
    improve last round already offered its candidate the last time it did
    improve, and the monotone strict-improvement compare rejects it now —
    so the accepted updates (and, for EDT, their per-offset order, hence
    tie resolution) coincide exactly, and the loop converges in exactly as
    many rounds as the dense-only kernel (one trailing round observes no
    improvement, same as the dense loop's final ``changed == False``
    iteration).

    ``initial_queue`` — optional resident queue ``(queue, count)`` (the
    :func:`compact_mask` layout: int32[capacity] flat indices, dead slots
    ``-1``).  When given, the seeding dense round is SKIPPED and the drain
    starts directly from the provided frontier — the re-entry path of the
    persistent round state (DESIGN.md §2.6): a caller that already knows
    which pixels changed (a BP halo update, a previous drain's unfinished
    queue) pays O(capacity) instead of O(block) to resume.  The caller
    asserts that every pixel holding a value not yet offered to its
    neighbors is queued; ``count > capacity`` is safe (the first round
    spills to a dense sweep, so an overflowing resident frontier degrades
    to exactly the unseeded behavior), and ``count == 0`` returns
    immediately (the caller asserted a fixed point).
    """
    if initial_queue is not None:
        queue, count = initial_queue
        count = jnp.asarray(count, jnp.int32)
        it0 = jnp.int32(0)           # no seeding round to count
    else:
        carry, imp0 = dense_round(carry)
        queue, count, _ = compact_mask(imp0, capacity)
        it0 = jnp.int32(1)

    def cond(state):
        _, _, count, it, _ = state
        return (count > 0) & (it < max_iters)

    def body(state):
        carry, queue, count, it, spills = state
        overflow = count > capacity

        def spill(c):
            c, imp = dense_round(c)
            return (c,) + compact_mask(imp, capacity)[:2]

        def drain(c):
            c, targets, imp = queued_round(c, queue)
            return (c,) + compact_flags(targets, imp, capacity)[:2]

        carry, queue, count = jax.lax.cond(overflow, spill, drain, carry)
        return carry, queue, count, it + 1, spills + overflow.astype(jnp.int32)

    carry, _, _, iters, spills = jax.lax.while_loop(
        cond, body, (carry, queue, count, it0, jnp.int32(0)))
    return carry, iters, spills
