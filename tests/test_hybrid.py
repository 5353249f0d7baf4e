"""The hybrid cooperative CPU+device engine (DESIGN.md §2.3).

Covers the cooperative pool shapes (host-only / device-only / mixed),
failure injection (a dead worker's tiles are re-queued and the surviving
worker class finishes the queue with output bit-identical to the E1
reference), the chunk-sizing policy (EWMA converges toward the measured
relative speed), and the `incomplete` surfacing contract.
"""

import threading
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

import repro.solve as solve_mod
from repro.core.scheduler import ChunkPolicy, DeviceWorker, TileScheduler
from repro.core.tiles import default_batched_solver, initial_active_tiles
from repro.data.images import bg_disks, seeded_marker, tissue_image
from repro.edt.ops import EdtOp, distance_map
from repro.edt.ref import edt_wavefront
from repro.morph.ops import MorphReconstructOp
from repro.morph.ref import reconstruct_fh
from repro.solve import solve


@pytest.fixture(scope="module")
def morph_case():
    _, mask = tissue_image(96, 96, coverage=0.8, seed=5)
    marker = seeded_marker(mask, n_seeds=6, seed=5)
    ref = reconstruct_fh(marker.copy(), mask, connectivity=8).astype(np.int32)
    op = MorphReconstructOp(connectivity=8)
    state = op.make_state(jnp.asarray(marker.astype(np.int32)),
                          jnp.asarray(mask.astype(np.int32)))
    return op, state, ref


@pytest.fixture(scope="module")
def edt_case():
    fg = bg_disks(64, 64, coverage=0.9, n_disks=3, seed=7)
    ref_M, _ = edt_wavefront(fg, connectivity=8)
    op = EdtOp(connectivity=8)
    return op, op.make_state(jnp.asarray(fg)), ref_M


@pytest.fixture
def fail_inject(monkeypatch):
    """Set solve's hybrid fault-injection hook for one test."""
    def _set(spec):
        monkeypatch.setattr(solve_mod, "_HYBRID_FAIL_INJECT", spec)
    yield _set


# ---------------------------------------------------------------------------
# pool shapes: host-only / device-only / mixed all reach the E1 fixed point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool", [
    dict(n_workers=2, n_device_workers=0),           # host-only
    dict(n_workers=0, n_device_workers=1),           # device-only
    dict(n_workers=2, n_device_workers=1),           # mixed (the paper's §4)
    dict(n_workers=1, n_device_workers=2),           # mixed, 2 device streams
])
def test_hybrid_pool_shapes_match_morph_ref(morph_case, pool):
    op, state, ref = morph_case
    out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=4, **pool)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert st.engine == "hybrid" and not st.incomplete
    assert st.tiles_processed > 0 and st.rounds >= 1
    assert st.worker_errors == ()
    if pool["n_workers"] == 0:
        assert st.device_tiles == st.tiles_processed


def test_hybrid_pallas_device_drain_matches_ref(morph_case, edt_case):
    op, state, ref = morph_case
    out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=2,
                    n_workers=1, n_device_workers=1, hybrid_pallas=True)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert not st.incomplete
    eop, estate, ref_M = edt_case
    out, st = solve(eop, estate, engine="hybrid", tile=16, drain_batch=2,
                    n_workers=1, n_device_workers=1, hybrid_pallas=True)
    np.testing.assert_array_equal(np.asarray(distance_map(out)), ref_M)
    assert not st.incomplete


def test_hybrid_edt_distance_exact(edt_case):
    op, state, ref_M = edt_case
    out, st = solve(op, state, engine="hybrid", tile=16, n_workers=2,
                    n_device_workers=1)
    np.testing.assert_array_equal(np.asarray(distance_map(out)), ref_M)
    assert not st.incomplete


def test_hybrid_empty_pool_raises(morph_case):
    op, state, _ = morph_case
    with pytest.raises(ValueError, match="hybrid"):
        solve(op, state, engine="hybrid", n_workers=0, n_device_workers=0)
    with pytest.raises(ValueError, match="worker"):
        TileScheduler({"J": np.zeros((32, 32), np.int32)}, 16, None,
                      np.ones((2, 2), bool), n_workers=0)


# ---------------------------------------------------------------------------
# failure injection: the surviving worker class finishes the queue
# ---------------------------------------------------------------------------

def test_host_worker_death_device_finishes_bit_identical(morph_case, fail_inject):
    """Kill the (only) host worker mid-run: its tiles are re-queued and the
    device worker drains the rest — output bit-identical to the reference
    (the §5.2.4 idempotence argument on the cooperative pool)."""
    op, state, ref = morph_case
    fail_inject((0, 0))      # worker id 0 = the host thread; dies on 1st tile
    out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=4,
                    n_workers=1, n_device_workers=1)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert st.requeues >= 1
    assert not st.incomplete


def test_device_worker_death_hosts_finish_distance_exact(edt_case, fail_inject):
    """Kill the device worker on its first claimed chunk: host threads
    finish the queue, EDT output distance-exact against the wavefront
    reference."""
    op, state, ref_M = edt_case
    fail_inject((2, 0))      # worker ids 0,1 = hosts; 2 = the device worker
    out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=4,
                    n_workers=2, n_device_workers=1)
    np.testing.assert_array_equal(np.asarray(distance_map(out)), ref_M)
    assert st.requeues >= 1
    assert not st.incomplete


def test_real_device_error_recorded_and_hosts_finish(morph_case, monkeypatch):
    """A device drain that raises a real (not injected) error — e.g. a
    kernel that fails to compile — must not vanish into the fault-tolerance
    path: SchedulerStats records its repr, a RuntimeWarning names it, and
    the host threads still finish the queue bit-exact."""
    op, state, ref = morph_case

    def broken_batch_fn(blocks):
        raise ValueError("device drain failed to lower")

    monkeypatch.setattr(solve_mod, "_batched_drain_for",
                        lambda *a, **k: broken_batch_fn)
    with pytest.warns(RuntimeWarning, match="device drain failed to lower"):
        out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=2,
                        n_workers=2, n_device_workers=1)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert not st.incomplete and st.device_tiles == 0
    assert st.worker_errors and all(
        "ValueError('device drain failed to lower')" in e
        for e in st.worker_errors)


def test_injected_failures_are_not_recorded_as_errors(morph_case, fail_inject):
    op, state, ref = morph_case
    fail_inject((2, 0))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out, st = solve(op, state, engine="hybrid", tile=16, drain_batch=2,
                        n_workers=2, n_device_workers=1)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert st.requeues >= 1 and st.worker_errors == ()


def test_hybrid_incomplete_surfaced(morph_case, fail_inject, monkeypatch):
    """Every worker of every wave dying must never be reported as a fixed
    point: SolveStats.incomplete=True plus a RuntimeWarning."""
    op, state, ref = morph_case
    fail_inject(("all", 0))
    monkeypatch.setattr(TileScheduler, "max_survivor_waves", 2)
    with pytest.warns(RuntimeWarning, match="NOT at its fixed point"):
        out, st = solve(op, state, engine="hybrid", tile=16, n_workers=1,
                        n_device_workers=1, max_rounds=1)
    assert st.incomplete
    assert st.tiles_processed == 0
    # the partial state is monotone-valid: below the fixed point, above the
    # (clipped) marker — never corrupted
    J = np.asarray(out["J"])
    assert (J <= ref).all() and (J >= np.asarray(state["J"])).all()


def test_hybrid_total_failure_degrades_to_dense_rounds(fail_inject, monkeypatch):
    """With every scheduler pass losing every worker, the BP verification
    round alone still reaches the exact fixed point (E1-speed degradation:
    one dense round per BP round) — slow, but never wrong."""
    _, mask = tissue_image(32, 32, coverage=0.9, seed=3)
    marker = seeded_marker(mask, n_seeds=1, seed=3)
    ref = reconstruct_fh(marker.copy(), mask, connectivity=8).astype(np.int32)
    op = MorphReconstructOp(connectivity=8)
    state = op.make_state(jnp.asarray(marker.astype(np.int32)),
                          jnp.asarray(mask.astype(np.int32)))
    fail_inject(("all", 0))
    monkeypatch.setattr(TileScheduler, "max_survivor_waves", 1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        out, st = solve(op, state, engine="hybrid", tile=16, n_workers=1,
                        n_device_workers=0)
    np.testing.assert_array_equal(np.asarray(out["J"]), ref)
    assert not st.incomplete
    assert st.tiles_processed == 0 and st.rounds > 1


# ---------------------------------------------------------------------------
# chunk sizing: cost-model seed, EWMA refinement
# ---------------------------------------------------------------------------

def test_chunk_policy_seed_and_clamp():
    assert ChunkPolicy(rel_speed=4.0, max_chunk=8).chunk() == 4
    assert ChunkPolicy(rel_speed=100.0, max_chunk=8).chunk() == 8   # clamp hi
    # The low clamp is 2, not 1: even a slow device stream claims one tile
    # of look-ahead to amortize its per-claim lock/wakeup overhead (the
    # claim-time half-queue cap handles the endgame).
    assert ChunkPolicy(rel_speed=0.1, max_chunk=8).chunk() == 2     # clamp lo


def test_chunk_policy_ewma_converges_toward_faster_worker():
    """The measured ratio overrides the seed: a device measured 5x faster
    than the host converges the chunk to 5; a device that *slows down*
    below host speed shrinks the chunk back to the look-ahead floor."""
    p = ChunkPolicy(rel_speed=2.0, max_chunk=16, alpha=0.25)
    for _ in range(50):
        p.observe_host(10e-3)
        p.observe_device(2e-3)
    assert abs(p.rel_speed - 5.0) < 0.25
    assert p.chunk() == 5
    for _ in range(100):
        p.observe_device(20e-3)    # device now 2x *slower* than the host
    assert p.rel_speed < 1.0
    assert p.chunk() == 2


def test_chunk_policy_seed_used_until_both_classes_measured():
    p = ChunkPolicy(rel_speed=6.0, max_chunk=16)
    p.observe_host(1e-3)           # device never measured yet
    assert p.chunk() == 6


def test_chunk_policy_is_thread_safe_under_concurrent_observation():
    p = ChunkPolicy(rel_speed=3.0, max_chunk=16)

    def host():
        for _ in range(500):
            p.observe_host(8e-3)

    def dev():
        for _ in range(500):
            p.observe_device(4e-3)

    ts = [threading.Thread(target=host), threading.Thread(target=dev)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert abs(p.rel_speed - 2.0) < 0.1


# ---------------------------------------------------------------------------
# scheduler-level: device workers share the queue with host threads
# ---------------------------------------------------------------------------

def test_device_worker_on_raw_scheduler_matches_ref():
    """A DeviceWorker plugged straight into TileScheduler (no solve() glue):
    batched drains + commutative merge reach the host path's fixed point."""
    marker, mask = tissue_image(64, 64, coverage=0.7, seed=9)
    ref = reconstruct_fh(marker, mask, 8).astype(np.int32)
    op = MorphReconstructOp(connectivity=8)
    state = {"J": np.minimum(marker, mask).astype(np.int32),
             "I": mask.astype(np.int32),
             "valid": np.ones(mask.shape, bool)}
    T = 16
    active = np.asarray(initial_active_tiles(
        op, {k: jnp.asarray(v) for k, v in state.items()}, T))
    batch_fn = default_batched_solver(op, T)
    dev = DeviceWorker(batch_fn, drain_batch=4)
    sched = TileScheduler(state, T, None, active, n_workers=0,
                          mutable=("J",), device_workers=[dev],
                          pad_values={"J": np.iinfo(np.int32).min,
                                      "I": np.iinfo(np.int32).min,
                                      "valid": False})
    st = sched.run()
    np.testing.assert_array_equal(state["J"], ref)
    assert st.tiles_processed > 0 and not st.incomplete
    # all work was done by the device worker (wid 0 is the only worker)
    assert sum(st.per_worker.values()) == st.tiles_processed
