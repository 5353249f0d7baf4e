"""The main-path Pallas kernels compile for a TPU v5e.

No chip is needed: the TPU compiler that ships with JAX compiles for a
*described* ``v5e:2x2`` topology.  Each case lowers one kernel at a real
tile size onto the first described chip and checks that the compiled
program holds a Mosaic kernel (``tpu_custom_call``) — interpret mode never
sees the refusals this catches (scalar VMEM stores, scoped-VMEM overflow).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.edt_tile import edt_tile_solve_batched_nd, edt_tile_solve_nd
from repro.kernels.morph_tile import morph_tile_solve, morph_tile_solve_batched
from repro.kernels.ops import (raster_pass_kernel, tile_solver_label,
                               tile_solver_label_batched)
from repro.solve import COMPILED_PALLAS_TILES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
            try:
                yield topologies.get_topology_desc(platform="tpu",
                                                   topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure means "cannot"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


I32, BOOL = jnp.int32, jnp.bool_


def _morph(conn, bound, batched):
    kern = morph_tile_solve_batched if batched else morph_tile_solve
    return (lambda J, I, v: kern(J, I, v, connectivity=conn, max_iters=bound,
                                 interpret=False),
            lambda blk: ((blk, I32), (blk, I32), (blk, BOOL)))


def _edt(conn, bound, batched):
    kern = edt_tile_solve_batched_nd if batched else edt_tile_solve_nd

    def shapes(blk):
        nd = len(blk) - batched
        lead, sp = blk[:batched], blk[batched:]
        return ((lead + (nd,) + sp, I32), (blk, BOOL), (lead + (nd,) + sp, I32))
    return (lambda vr, v, co: kern(vr, v, co, connectivity=conn,
                                   max_iters=bound, interpret=False),
            shapes)


def _label(conn, bound, batched):
    make = tile_solver_label_batched if batched else tile_solver_label
    solver = make(conn, False, bound)
    return (lambda lab, fg, v: solver({"lab": lab, "fg": fg, "valid": v}),
            lambda blk: ((blk, I32), (blk, BOOL), (blk, BOOL)))


# (kernel family, spatial rank, tile, batch K or None): every tile at which
# a compiled `auto` may run `tiled-pallas`, for each op's default
# neighbourhood ("fill" = fill_holes' 4-connected flood on the morph kernel)
FAMILIES = {2: ("morph", "edt", "label", "fill"), 3: ("morph", "edt")}
CASES = [(fam, nd, t, k) for nd, t in COMPILED_PALLAS_TILES
         for fam in FAMILIES[nd]
         for k in (None, 8 if fam == "edt" and nd == 2 else 4)]


@pytest.mark.parametrize("family,ndim,tile,batch", CASES,
                         ids=[f"{f}-{n}d-T{t}-{'K%d' % k if k else 'dense'}"
                              for f, n, t, k in CASES])
def test_tile_kernel_compiles_for_v5e(one_chip, family, ndim, tile, batch):
    conn = 4 if family == "fill" else 8 if ndim == 2 else "conn26"
    bound = (tile + 2) ** ndim           # the tiled engine's geodesic bound
    fn, shapes = {"morph": _morph, "edt": _edt, "label": _label,
                  "fill": _morph}[family](conn, bound, batch is not None)
    blk = ((batch,) if batch else ()) + (tile + 2,) * ndim
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes(blk)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_raster_pass_compiles_for_v5e(one_chip):
    a = jax.ShapeDtypeStruct((1024, 1024), I32, sharding=one_chip)
    compiled = jax.jit(
        lambda J, I: raster_pass_kernel(J, I, interpret=False)).lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()
