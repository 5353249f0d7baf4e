"""Multi-device tests.  Each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single-device view (per the dry-run protocol)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_morph_matches_ref():
    """E3 engine (shard_map + ppermute halo + psum convergence) == FH ref."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import run_sharded
        from repro.data.images import tissue_image
        from repro.morph.ops import MorphReconstructOp
        from repro.morph.ref import reconstruct_fh
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        marker, mask = tissue_image(64, 96, 0.7, seed=0)
        ref = reconstruct_fh(marker, mask, 8)
        op = MorphReconstructOp(connectivity=8)
        state = op.make_state(jnp.asarray(marker.astype(np.int32)),
                              jnp.asarray(mask.astype(np.int32)))
        out, stats = run_sharded(op, state, mesh)
        np.testing.assert_array_equal(np.asarray(out["J"]), ref.astype(np.int32))
        assert int(stats.bp_rounds) >= 1
        assert int(stats.tiles_processed) == 0   # dense TP drain: no tile queue
        print("OK rounds=", int(stats.bp_rounds))
    """)


def test_sharded_edt_matches_ref():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import run_sharded
        from repro.data.images import binary_blobs
        from repro.edt.ops import EdtOp, distance_map
        from repro.edt.ref import edt_wavefront
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        fg = binary_blobs(64, 64, 0.5, seed=1)
        ref_M, _ = edt_wavefront(fg, 8)
        op = EdtOp(connectivity=8)
        out, stats = run_sharded(op, op.make_state(jnp.asarray(fg)), mesh)
        np.testing.assert_array_equal(np.asarray(distance_map(out)), ref_M)
        assert int(stats.bp_rounds) >= 1
        print("OK")
    """)


def test_composed_shard_map_tiled_matches_ref_across_meshes():
    """The paper's full two-level hierarchy: per-shard active-tile queues
    (E2) inside the mesh TP/BP pipeline (E3).  Bit-exact with the FH
    reference (morph) / distance-exact (EDT) on 1x1, 2x2 and 1x8 meshes,
    with the BP rounds re-seeding only halo-improved tiles."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import run_sharded
        from repro.data.images import binary_blobs, tissue_image, seeded_marker
        from repro.edt.ops import EdtOp, distance_map
        from repro.edt.ref import edt_wavefront
        from repro.morph.ops import MorphReconstructOp
        from repro.morph.ref import reconstruct_fh
        marker, mask = tissue_image(48, 64, 0.7, seed=0)
        marker = seeded_marker(mask, n_seeds=4, seed=0)
        ref = reconstruct_fh(marker.copy(), mask, 8).astype(np.int32)
        mop = MorphReconstructOp(connectivity=8)
        mstate = mop.make_state(jnp.asarray(marker.astype(np.int32)),
                                jnp.asarray(mask.astype(np.int32)))
        fg = binary_blobs(48, 64, 0.5, seed=1)
        ref_M, _ = edt_wavefront(fg, 8)
        eop = EdtOp(connectivity=8)
        estate = eop.make_state(jnp.asarray(fg))
        for shape in ((1, 1), (2, 2), (1, 8)):
            mesh = jax.make_mesh(shape, ("data", "model"))
            out, st = run_sharded(mop, mstate, mesh, tile=16,
                                  queue_capacity=8, drain_batch=2)
            np.testing.assert_array_equal(np.asarray(out["J"]), ref)
            assert int(st.tiles_processed) > 0
            assert np.asarray(st.per_device_tiles).shape == shape
            out, st = run_sharded(eop, estate, mesh, tile=16, queue_capacity=8)
            np.testing.assert_array_equal(np.asarray(distance_map(out)), ref_M)
            print("OK", shape, int(st.bp_rounds), int(st.tiles_processed))
    """)


def test_composed_engine_pallas_backed_drain():
    """run_sharded's TP drain accepts the Pallas kernel solvers (with the
    threaded (T+2)^2 bound) — the VMEM drain inside the mesh pipeline."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import run_sharded
        from repro.data.images import tissue_image, seeded_marker
        from repro.kernels.ops import tile_solver_morph, tile_solver_morph_batched
        from repro.morph.ops import MorphReconstructOp
        from repro.morph.ref import reconstruct_fh
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        marker, mask = tissue_image(48, 48, 0.7, seed=2)
        marker = seeded_marker(mask, n_seeds=4, seed=2)
        ref = reconstruct_fh(marker.copy(), mask, 8).astype(np.int32)
        op = MorphReconstructOp(connectivity=8)
        state = op.make_state(jnp.asarray(marker.astype(np.int32)),
                              jnp.asarray(mask.astype(np.int32)))
        out, st = run_sharded(
            op, state, mesh, tile=16, queue_capacity=8, drain_batch=2,
            tile_solver=tile_solver_morph(8, True, 18 ** 2),
            batched_tile_solver=tile_solver_morph_batched(8, True, 18 ** 2))
        np.testing.assert_array_equal(np.asarray(out["J"]), ref)
        print("OK tiles=", int(st.tiles_processed))
    """, devices=4)


def test_composed_engine_solve_nondivisible_and_masked():
    """solve(engine="shard_map-tiled") end-to-end: a grid no mesh divides
    (exercising _pad_to_multiple) under a non-rectangular valid mask, on 8
    devices — full-array comparable with the E1 reference (the invalid-
    pixel contract)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.frontier import run_dense
        from repro.data.images import bg_disks
        from repro.edt.ops import EdtOp, distance_map
        from repro.solve import solve
        H, W = 37, 51
        yy, xx = np.mgrid[:H, :W]
        valid = ((yy - H / 2) ** 2 + (xx - W / 2) ** 2) < (0.45 * max(H, W)) ** 2
        fg = bg_disks(H, W, coverage=0.9, n_disks=2, seed=4)
        op = EdtOp(connectivity=8)
        state = op.make_state(jnp.asarray(fg), jnp.asarray(valid))
        ref_out, _ = run_dense(op, state, "frontier")
        out, stats = solve(op, state, engine="shard_map-tiled", tile=16,
                           queue_capacity=8)
        assert stats.engine == "shard_map-tiled" and stats.n_devices == 8
        assert stats.tiles_processed > 0
        np.testing.assert_array_equal(np.asarray(distance_map(out)),
                                      np.asarray(distance_map(ref_out)))
        # invalid cells hold their input values (contract)
        np.testing.assert_array_equal(np.asarray(out["vr"])[:, ~valid],
                                      np.asarray(state["vr"])[:, ~valid])
        print("OK")
    """)


def test_invalid_band_at_shard_border_cannot_source():
    """Regression: the BP halo round used to seed the WHOLE exchanged ring
    as frontier — a poisoned invalid band sitting exactly on a shard
    boundary was handed to the neighbor device's halo, marked as a source,
    and corrupted its valid region.  The seed is now masked by valid."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import run_sharded
        from repro.core.frontier import run_dense
        from repro.morph.ops import MorphReconstructOp
        mesh = jax.make_mesh((1, 2), ("data", "model"))
        H, W = 16, 32
        valid = np.ones((H, W), bool)
        valid[:, 15:17] = False          # invalid band straddling the border
        mask = np.where(valid, 100, 255).astype(np.int32)
        marker = np.zeros((H, W), np.int32)
        marker[0, 0] = 50
        marker = np.where(valid, marker, 255)   # poisoned to the max
        op = MorphReconstructOp(connectivity=8)
        state = op.make_state(jnp.asarray(marker), jnp.asarray(mask),
                              jnp.asarray(valid))
        ref, _ = run_dense(op, state, "frontier")
        for kw in ({}, dict(tile=8, queue_capacity=8)):
            out, _ = run_sharded(op, state, mesh, **kw)
            np.testing.assert_array_equal(np.asarray(out["J"]),
                                          np.asarray(ref["J"]))
        print("OK")
    """, devices=2)


def test_per_device_tile_counters_psum_to_stats():
    """Hypothesis property: the per-device drain counters (out_spec sharded
    over the mesh) always sum to the psum'd tiles_processed total in the
    stats record, and the composed output matches the E1 reference."""
    pytest.importorskip("hypothesis", reason="install via requirements-dev.txt")
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from hypothesis import given, settings, strategies as st
        from repro.core.distributed import run_sharded
        from repro.core.frontier import run_dense
        from repro.morph.ops import MorphReconstructOp
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        op = MorphReconstructOp(connectivity=8)
        @given(st.integers(0, 2**31 - 1))
        @settings(max_examples=5, deadline=None)
        def prop(seed):
            rng = np.random.default_rng(seed)
            mask = rng.integers(0, 256, (32, 32)).astype(np.int32)
            marker = np.minimum(
                rng.integers(0, 256, (32, 32)).astype(np.int32), mask)
            state = op.make_state(jnp.asarray(marker), jnp.asarray(mask))
            out, stc = run_sharded(op, state, mesh, tile=8, queue_capacity=8)
            per_dev = np.asarray(stc.per_device_tiles)
            assert per_dev.shape == (2, 4)
            assert int(per_dev.sum()) == int(stc.tiles_processed)
            ref, _ = run_dense(op, state, "frontier")
            np.testing.assert_array_equal(np.asarray(out["J"]),
                                          np.asarray(ref["J"]))
        prop()
        print("OK")
    """)


def test_pjit_train_step_matches_single_device():
    """The production sharded train step computes the same update as the
    single-device step (2x2 mesh, fp32, drop-free MoE island)."""
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ShapeSpec
        from repro.configs.registry import smoke_config
        from repro.data.pipeline import batch_for_step
        from repro.distributed import sharding as shd
        from repro.distributed.context import ParallelCtx, parallel_ctx
        from repro.models.transformer import init_params
        from repro.train.optim import OptConfig, init_opt_state
        from repro.train.step import make_train_step
        cfg = dataclasses.replace(smoke_config("deepseek-v2-lite-16b"),
                                  dtype="float32")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=4,
                                         capacity_factor=64.0))
        shape = ShapeSpec("t", 16, 4, "train")
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        batch = {k: jnp.asarray(v) for k, v in
                 batch_for_step(cfg, shape, 0).items()}
        # single device
        p1, o1, m1 = jax.jit(make_train_step(cfg, OptConfig()))(params, opt, batch)
        # sharded
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        pspec = shd.named(mesh, shd.param_specs(cfg, params, mesh))
        oshard = {"m": pspec, "v": pspec,
                  "step": shd.named(mesh, jax.sharding.PartitionSpec())}
        bshard = shd.named(mesh, shd.batch_specs(cfg, batch, mesh))
        with parallel_ctx(ParallelCtx(mesh, ("data",))), mesh:
            fn = jax.jit(make_train_step(cfg, OptConfig()),
                         in_shardings=(pspec, oshard, bshard))
            p2, o2, m2 = fn(params, opt, batch)
        # cross-shard reduction order and the MoE island's pmean'd aux give
        # ~1e-4 relative fp32 noise
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-3)
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
        assert max(jax.tree_util.tree_leaves(d)) < 1e-3, sorted(
            jax.tree_util.tree_leaves(d))[-3:]
        print("OK loss=", float(m2["loss"]))
    """)


def test_compressed_dp_psum_close_to_exact():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from repro.distributed.compression import compressed_psum, init_error_feedback
        mesh = jax.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 33), jnp.float32)
        ef = jnp.zeros((8, 64, 33), jnp.float32)
        def f(gl, efl):
            out, ef2 = compressed_psum(gl, efl, "data")
            return out, ef2
        fn = jax.jit(jax.shard_map(f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec("data"),) * 2,
            out_specs=(jax.sharding.PartitionSpec("data"),) * 2,
            check_vma=False))
        out, ef2 = fn(g, ef)
        exact = jnp.mean(g, axis=0, keepdims=True)
        rel = float(jnp.max(jnp.abs(out[0] - exact[0]))) / float(jnp.max(jnp.abs(exact)))
        assert rel < 0.2, rel          # single round: one int8 bucket of noise
        # the real claim: error feedback makes the scheme unbiased over time —
        # the running mean of repeated reductions converges to the exact mean
        # at rate ~1/T (the residual ef_T is bounded, and the telescoped sum
        # of outputs equals T*exact + O(ef_T)).
        def run_mean_err(T):
            acc = jnp.zeros_like(out)
            efr = ef
            for _ in range(T):
                o, efr = fn(g, efr)
                acc = acc + o
            return float(jnp.max(jnp.abs(acc[0] / T - exact[0]))) \
                / float(jnp.max(jnp.abs(exact)))
        e4, e64 = run_mean_err(4), run_mean_err(64)
        assert e64 < e4 / 4, (e4, e64)      # ~1/T decay
        assert e64 < 0.02, e64
        print("OK rel=", rel, "e4=", e4, "e64=", e64)
    """)


def test_elastic_reshard_across_mesh_sizes():
    """Save under a 4x2 mesh, restore under 2x2 and 8x1 — elastic restart."""
    run_sub("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.ckpt.checkpoint import save
        from repro.ckpt.elastic import restore_elastic
        from repro.configs.registry import smoke_config
        from repro.distributed import sharding as shd
        from repro.models.transformer import init_params
        cfg = smoke_config("gemma2-27b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        p1 = shd.reshard_tree = jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh1, s),
                shd.param_specs(cfg, params, mesh1)))
        with tempfile.TemporaryDirectory() as d:
            save(d, 7, p1)
            for shape_ in ((2, 2), (8, 1)):
                mesh2 = jax.make_mesh(shape_, ("data", "model"))
                specs2 = shd.param_specs(cfg, params, mesh2)
                step, p2, _ = restore_elastic(d, params, mesh2, specs2)
                assert step == 7
                chk = jax.tree_util.tree_map(
                    lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
                    params, p2)
                assert all(jax.tree_util.tree_leaves(chk))
        print("OK")
    """)


def test_mini_dryrun_lower_compile():
    """The dry-run pipeline end-to-end on a small mesh: every step kind."""
    run_sub("""
        import jax
        from repro.launch import dryrun
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        from repro.distributed.context import parallel_ctx
        for arch, shape in [("gemma2-27b", "train_4k"),
                            ("deepseek-v2-lite-16b", "prefill_32k"),
                            ("recurrentgemma-2b", "long_500k")]:
            cfg, ctx, fn, args, in_sh, out_sh, donate = dryrun.build_cell(
                arch, shape, mesh)
            with parallel_ctx(ctx), mesh:
                c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                            donate_argnums=donate).lower(*args).compile()
            ca = c.cost_analysis()
            if isinstance(ca, (list, tuple)):   # older jax: list of dicts
                ca = ca[0]
            assert ca.get("flops", 0) > 0
            print("OK", arch, shape)
    """, devices=4, timeout=560)
