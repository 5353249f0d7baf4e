"""The first-class operation plugin registry: ``OpSpec`` + ``register_op``.

The paper frames IWPP as a *pattern* shared by a whole family of image
operations — morphological reconstruction and EDT are the two it
benchmarks, with fill-holes and h-maxima named as further instances (§2),
and the MIC follow-up (Gomes & Teodoro 2016) ports the pattern across
operations by swapping the propagation condition, not the engine.  This
module is that seam made explicit: **an operation is a declarative
:class:`OpSpec`**, and every engine-facing plug point the dispatch layer
needs — Pallas tile solvers, the host scheduler's commutative merge, the
cost model's per-op weights, state construction and result extraction —
lives on the spec, not inside ``solve.py``.

Adding an operation therefore never touches engine code (the acceptance
bar of docs/OPS.md "add your own op in ~50 lines"):

    from repro.ops import OpSpec, register_op
    register_op("my_op", OpSpec(op_cls=MyOp, factory=MyOp, ...))
    solve("my_op", my_input, engine="tiled")      # every engine, by name

Two indices back the registry:

* **by name** — what ``solve("edt", ...)``, :func:`get_op` and
  :func:`list_ops` use;
* **by op class** — what the engines use to resolve an op *instance* to
  its spec (:func:`spec_for`, MRO walk so derived ops inherit their
  parent's plug points unless they register their own).

The legacy per-plug-point registrars (``repro.solve.register_pallas_solver``
/ ``register_scheduler_merge``) remain as shims over :func:`amend_op_class`:
they patch the class-indexed spec in place, creating an anonymous (unnamed)
spec when the class was never ``register_op``'d.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

from repro.core import spans

__all__ = [
    "OpSpec", "register_op", "get_op", "list_ops", "spec_for",
    "amend_op_class", "default_scheduler_merge", "on_spec_change", "run_op",
]


def default_scheduler_merge(op) -> None:
    """The default ``scheduler_merge`` factory: ``None`` tells the host
    scheduler to use its built-in elementwise-max merge — correct for any
    op whose mutable state is a single monotone-max plane (morph, fill
    holes, label propagation).  Ops whose merge couples leaves or depends
    on pixel coordinates (EDT's Voronoi pointers) register a real factory.
    """
    return None


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Declarative description of one IWPP operation (DESIGN.md §2.4).

    Only ``op_cls`` and ``factory`` are mandatory; everything else has a
    working default, so a minimal op runs on the generic engines (sweep /
    frontier / tiled / shard_map) immediately and opts into the specialized
    ones (Pallas drains, host scheduler, cost-model weighting) by filling
    the corresponding fields.

    Plug points
    -----------
    op_cls : the ``PropagationOp`` subclass instances of which this spec
        describes.  Engines resolve an op instance to its spec by MRO walk
        over this index, so a derived op (e.g. ``FillHolesOp`` deriving
        from ``MorphReconstructOp``) inherits plug points it doesn't
        override.
    factory : ``factory(**op_kw) -> PropagationOp`` — builds the op for
        by-name ``solve()`` calls (op-level knobs such as ``connectivity``
        pass through).
    make_state : ``make_state(op, *inputs, **kw) -> state`` — builds the
        op's state pytree from its natural raw inputs (image(s)).  Default
        delegates to ``op.make_state``.
    finalize : ``finalize(op, out_state) -> result`` — extracts the
        user-facing result array from a converged state (morph: the ``J``
        plane; EDT: the squared distance map).  Default: the state itself.
    pallas_solver / pallas_batch_solver : ``f(op, interpret, max_iters) ->
        tile_solver`` factories for the ``tiled-pallas`` engine and the
        hybrid engine's Pallas device workers; the solver contract is
        ``block -> (block, unconverged)`` (``kernels/ops.py``,
        DESIGN.md §2.1).  Without a batched factory the engine falls back
        to ``jax.vmap`` of the per-tile solver.
    pallas_queue_solver / pallas_queue_batch_solver :
        ``f(op, interpret, max_iters, queue_capacity) -> tile_solver`` —
        the queued-kernel variants behind ``solve(..., kernel_queue=True)``
        (in-kernel multi-level queue, DESIGN.md §2.5).  Same solver
        contract; ``queue_capacity`` is the per-block local-queue size
        (``None`` = the kernel-side default).  Optional: ops without them
        simply reject ``kernel_queue=True`` with a clear error.
    scheduler_merge : ``f(op) -> merge_block_fn | None`` — the host
        scheduler's commutative write-back merge (None = built-in
        elementwise max, see :func:`default_scheduler_merge`).
    example_state : ``f(rng, (H, W)) -> (op, state)`` — a representative
        random *masked* input for the op-contract conformance suite
        (``tests/test_op_contract.py``): registering an op with this field
        buys idempotence / engine-equivalence / invalid-restore checks for
        free.

    Geometry capabilities (DESIGN.md §2.7)
    --------------------------------------
    supported_ndims : spatial ranks the op's state builder and round
        support (default: 2-D only).  Ops whose rounds are rank-generic
        (morph, edt) declare ``(2, 3)``.
    neighborhoods : canonical connectivity names accepted by
        :meth:`make_op` — a subset of ``repro.core.geometry.NEIGHBORHOODS``
        (2-D: ``conn4``/``conn8``; 3-D: ``conn6``/``conn18``/``conn26``).
        A by-name ``solve(..., connectivity=...)`` request outside this set
        raises ``ValueError`` naming the op, the requested name, and this
        list.  Legacy ints 4/8 mean ``conn4``/``conn8``.

    Cost-model hints
    ----------------
    bytes_per_pixel : HBM bytes of *mutable* payload per pixel (morph: one
        int32 ``J`` plane = 4; EDT: the (ndim, *spatial) int32 ``vr``
        pointer = 4*ndim).  Scales ``CostModel.transfer_cost``.
    round_cost_weight : relative compute of one propagation round per
        pixel against morph's 8-neighbor max (EDT's distance arithmetic
        ~ 2x).  Scales ``CostModel.drain_cost``.
    calibration_states : ``f(size) -> [(label, op, state), ...]`` —
        representative workloads (typically one sparse-wavefront and one
        dense/near-converged regime) that :func:`repro.core.calibrate.
        run_calibration` measures to build this op's entries in the
        measured cost profile (DESIGN.md §2.8).  Ops without it are priced
        by the morph reference rates scaled by the two hint fields above.
    """

    op_cls: type
    factory: Callable
    name: str = ""
    make_state: Optional[Callable] = None
    finalize: Optional[Callable] = None
    pallas_solver: Optional[Callable] = None
    pallas_batch_solver: Optional[Callable] = None
    pallas_queue_solver: Optional[Callable] = None
    pallas_queue_batch_solver: Optional[Callable] = None
    scheduler_merge: Callable = default_scheduler_merge
    example_state: Optional[Callable] = None
    supported_ndims: Tuple[int, ...] = (2,)
    neighborhoods: Tuple[str, ...] = ("conn4", "conn8")
    bytes_per_pixel: float = 4.0
    round_cost_weight: float = 1.0
    calibration_states: Optional[Callable] = None
    doc: str = ""

    def make_op(self, connectivity: Optional[Union[int, str]] = None):
        """Build the op via the factory, forwarding the op-level
        ``connectivity`` knob only when given (each op's own default
        applies otherwise).  The single construction path behind both
        by-name ``solve()`` and :func:`run_op` — and the single validation
        point for the connectivity-by-name contract: an unknown name, or a
        known one this op does not declare in ``neighborhoods``, raises
        ``ValueError`` here, before any engine work happens."""
        if connectivity is not None:
            from repro.core.geometry import NEIGHBORHOODS, connectivity_name
            canon = connectivity_name(connectivity)   # raises on unknown
            if canon not in self.neighborhoods:
                label = self.name or self.op_cls.__name__
                raise ValueError(
                    f"op {label!r} does not support connectivity "
                    f"{connectivity!r} ({canon!r}, "
                    f"{NEIGHBORHOODS[canon].ndim}-D); supported "
                    f"neighborhoods: {list(self.neighborhoods)} "
                    f"(supported ndims: {list(self.supported_ndims)})")
        return self.factory(**({} if connectivity is None
                               else {"connectivity": connectivity}))

    def build_state(self, op, *inputs, **kw):
        """Build the op's state from raw inputs via the spec's builder."""
        if self.make_state is not None:
            return self.make_state(op, *inputs, **kw)
        return op.make_state(*inputs, **kw)

    def extract(self, op, out_state):
        """Extract the user-facing result from a converged state."""
        if self.finalize is not None:
            return self.finalize(op, out_state)
        return out_state


_BY_NAME: Dict[str, OpSpec] = {}
_BY_CLASS: Dict[type, OpSpec] = {}
# Hooks fired with the op class whenever its spec is (re)registered or
# amended — lets spec-derived caches elsewhere (e.g. the solve layer's
# jitted-solver memo) invalidate instead of serving a stale plug point.
_SPEC_CHANGE_HOOKS: list = []


def on_spec_change(hook: Callable[[type], None]) -> None:
    """Subscribe ``hook(op_cls)`` to spec registrations/amendments."""
    _SPEC_CHANGE_HOOKS.append(hook)


def _notify_spec_change(op_cls: type) -> None:
    for hook in _SPEC_CHANGE_HOOKS:
        hook(op_cls)


def register_op(name: str, spec: OpSpec) -> OpSpec:
    """Register ``spec`` under ``name`` (and under ``spec.op_cls``).

    Re-registering a name replaces the previous spec (latest wins — the
    same semantics as the legacy per-plug-point registrars).  Returns the
    stored spec (with ``name`` filled in).
    """
    if not name:
        raise ValueError("op name must be a non-empty string")
    spec = dataclasses.replace(spec, name=name)
    _BY_NAME[name] = spec
    _BY_CLASS[spec.op_cls] = spec
    _notify_spec_change(spec.op_cls)
    return spec


def get_op(name: str) -> OpSpec:
    """Look up a registered op by name; raises with the alternatives."""
    try:
        return _BY_NAME[name]
    except KeyError:
        raise ValueError(
            f"unknown op {name!r}; registered ops: {list_ops()} "
            "(register new ops with repro.ops.register_op)") from None


def list_ops() -> Tuple[str, ...]:
    """Names of all registered ops, sorted."""
    return tuple(sorted(_BY_NAME))


def spec_for(op) -> Optional[OpSpec]:
    """Resolve an op *instance* to its spec via MRO walk (None if the op's
    class hierarchy was never registered)."""
    for cls in type(op).__mro__:
        if cls in _BY_CLASS:
            return _BY_CLASS[cls]
    return None


def amend_op_class(op_cls: type, **fields) -> OpSpec:
    """Patch plug-point fields onto the spec indexed under ``op_cls``.

    Backs the legacy ``register_pallas_solver`` / ``register_scheduler_merge``
    shims: if ``op_cls`` itself was never registered, an *anonymous* spec is
    created for it (class index only — it does not appear in
    :func:`list_ops` and cannot be solved by name), **seeded from the
    nearest registered ancestor's spec** so amending one plug point on a
    subclass keeps every other plug point the old per-plug-point MRO
    registries would have inherited (e.g. ``register_pallas_solver`` on an
    ``EdtOp`` subclass must not silently swap its coordinate-aware
    scheduler merge for the elementwise-max default).
    """
    spec = _BY_CLASS.get(op_cls)
    if spec is None:
        parent = next((_BY_CLASS[c] for c in op_cls.__mro__ if c in _BY_CLASS),
                      None)
        spec = (OpSpec(op_cls=op_cls, factory=op_cls) if parent is None else
                dataclasses.replace(parent, op_cls=op_cls, factory=op_cls,
                                    name=""))
    spec = dataclasses.replace(spec, **fields)
    _BY_CLASS[op_cls] = spec
    if spec.name:
        _BY_NAME[spec.name] = spec
    _notify_spec_change(op_cls)
    return spec


@spans.recorded("iwpp.run_op")
def run_op(name: str, *inputs, connectivity: Optional[Union[int, str]] = None,
           **solve_kw):
    """Run a registered op end to end: build, solve, extract.

    The one-call protocol every per-op wrapper (``reconstruct``, ``edt``,
    ``fill_holes``, ``label``) delegates to: build the op via the spec
    factory (forwarding ``connectivity`` when given), build the state from
    the raw ``inputs``, ``solve()`` with the remaining keywords, and
    return ``(spec.extract(op, out), SolveStats)`` — the user-facing
    result, not the state pytree (use ``solve(name, ...)`` directly when
    the converged state itself is wanted).
    """
    from repro.solve import solve
    spec = get_op(name)
    with spans.span("iwpp.build_state"):
        op = spec.make_op(connectivity)
        state = spec.build_state(op, *inputs)
    out, stats = solve(op, state, **solve_kw)
    with spans.span("iwpp.extract"):
        return spec.extract(op, out), stats
