"""Dense-round IWPP engines (E0 `sweep`, E1 `frontier`).

E0 recomputes every pixel each round — the analogue of the raster-sweep
baselines (SR_GPU) and of a queue-less formulation.
E1 tracks the wavefront as a boolean plane: only frontier pixels act as
propagation sources, which is the paper's queue semantics expressed as a
mask.  Both run under one `lax.while_loop` to the fixed point.

Both also report *work counters* (rounds, source-pixels processed) so the
benchmarks can reproduce the paper's queue-size/work analysis (Table 1)
without GPU timers.  The source counter is an exact 64-bit total kept as a
(lo, hi) pair of uint32 words — float32 (the obvious x64-off fallback)
silently rounds past 2^24 sources, which a long run on a large grid reaches
easily.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.pattern import PropagationOp, restore_invalid


def accumulate_u64(lo: jnp.ndarray, hi: jnp.ndarray,
                   n: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact 64-bit accumulate in two uint32 words (x64-off safe).

    ``n`` must be < 2^32 (one round can at most touch every pixel); uint32
    addition wraps mod 2^32, and a wrapped sum is detectable as lo' < lo.
    """
    n = n.astype(jnp.uint32)
    new_lo = lo + n
    new_hi = hi + (new_lo < lo).astype(jnp.uint32)
    return new_lo, new_hi


class RunStats(NamedTuple):
    rounds: jnp.ndarray       # int32
    sources_lo: jnp.ndarray   # uint32 — low word of the exact source count
    sources_hi: jnp.ndarray   # uint32 — high word

    @property
    def sources_processed(self) -> int:
        """Exact total frontier pixels acted on (host-side int: two
        counted device -> host reads)."""
        return ((spans.host_int(self.sources_hi) << 32)
                | spans.host_int(self.sources_lo))


@partial(jax.jit, static_argnums=(0, 2, 3))
def run_dense(op: PropagationOp, state, engine: str = "frontier",
              max_rounds: int = 1_000_000):
    """Run `op` to its fixed point with dense rounds.

    engine: "frontier" (E1) or "sweep" (E0: frontier forced to all-valid
    every round, i.e. zero wavefront tracking).
    Returns (state, RunStats).
    """
    frontier0 = op.init_frontier(state)
    stats0 = RunStats(jnp.int32(0), jnp.uint32(0), jnp.uint32(0))

    def cond(carry):
        _, frontier, stats = carry
        return jnp.any(frontier) & (stats.rounds < max_rounds)

    def body(carry):
        state, frontier, stats = carry
        if engine == "sweep":
            # E0: ignore tracking; every valid pixel is a source.
            frontier = state["valid"]
        n_src = jnp.sum(frontier, dtype=jnp.uint32)
        state, new_frontier = op.round(state, frontier)
        lo, hi = accumulate_u64(stats.sources_lo, stats.sources_hi, n_src)
        stats = RunStats(stats.rounds + 1, lo, hi)
        if engine == "sweep":
            # Terminate on no-change rather than frontier emptiness.
            new_frontier = jnp.broadcast_to(jnp.any(new_frontier), new_frontier.shape) & state["valid"]
        return state, new_frontier, stats

    out, _, stats = jax.lax.while_loop(cond, body, (state, frontier0, stats0))
    # Engine output contract: invalid cells hold their input values (the
    # dense rounds can grow an invalid *receiver* one step toward the mask).
    return restore_invalid(op, state, out), stats


def run_to_stability(op: PropagationOp, state, max_rounds: int = 1_000_000):
    """Non-jit convenience wrapper (engine E1)."""
    return run_dense(op, state, "frontier", max_rounds)
