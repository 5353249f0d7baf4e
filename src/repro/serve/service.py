"""Multi-tenant batched IWPP serving front door (DESIGN.md §2.9,
docs/SERVING.md).

``IwppService`` turns a stream of independent ``submit(op_name, inputs)``
requests into saturated batched solves — the ROADMAP's "millions of users"
front door over the whole engine stack:

* **Async queue + futures** — ``submit`` returns a
  ``concurrent.futures.Future`` immediately; one daemon drain thread
  claims batches and resolves them.
* **Coalescing** — compatible pending requests (same op, bucketed spatial
  shape, dtypes, connectivity, engine signature —
  :func:`repro.serve.batching.request_key`) ride ONE
  :func:`repro.solve.solve_batch` call; near-miss shapes join a batch via
  the pad-to-bucket policy (state-level neutral padding, bit-identical
  results after crop).
* **Engine selection per batch** — ``engine="auto"`` ranks candidates with
  :func:`repro.solve.default_cost_model` (the calibrated profile when one
  is installed, DESIGN.md §2.8); the autotune process + disk caches are
  shared across requests, so one tenant's measured winner serves every
  later tenant of the same signature.
* **Result cache + single-flight** — finalized results are cached
  content-addressed (:func:`repro.serve.batching.content_fingerprint`);
  an identical in-flight request attaches to the pending future instead of
  solving twice.
* **Admission control** — bounded queue depth and per-tenant in-flight
  caps; over-limit submits raise :class:`Rejected` carrying a
  ``retry_after_s`` backoff hint instead of growing memory without bound.
* **Observability** — :meth:`IwppService.stats` returns a
  :class:`~repro.serve.metrics.ServeStats` snapshot (requests/sec, batch
  histogram, cache hit rate, queue depth, p50/p95/p99 latency).

The token-decode :class:`~repro.serve.engine.ServeEngine` (the LM
substrate's continuous-batching slot pool) lives beside this module and is
unrelated plumbing.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.ops import get_op
from repro.serve.batching import (Coalescer, PendingRequest, content_fingerprint,
                                  crop_state, padded_state, request_key)
from repro.serve.metrics import MetricsRecorder, ServeStats


class Rejected(RuntimeError):
    """Admission-control refusal (backpressure, never silent queue growth).

    ``retry_after_s`` is the service's backoff hint: roughly the time the
    current backlog needs to drain at the recent per-request service rate.
    """

    def __init__(self, reason: str, retry_after_s: float):
        super().__init__(f"{reason}; retry after ~{retry_after_s:.3f}s")
        self.reason = reason
        self.retry_after_s = retry_after_s


class IwppService:
    """The batched multi-tenant ``solve()`` service (module docstring).

    Parameters
    ----------
    engine, interpret, autotune, cost_model, **solve_kw :
        forwarded to :func:`repro.solve.solve_batch` for every batch —
        ``engine="auto"`` (default) re-ranks per batch with
        :func:`~repro.solve.default_cost_model`; ``solve_kw`` takes the
        per-engine knobs (``tile``, ``drain_batch``, ...).
    max_batch : most requests coalesced into one solve.
    batch_window_s : how long the drain thread holds an under-full batch
        open for compatible followers (0 = drain immediately).
    max_queue_depth : pending-request bound; past it ``submit`` raises
        :class:`Rejected`.
    max_inflight_per_tenant : per-tenant cap on submitted-but-unresolved
        requests (single-flight joins and cache hits are free).
    cache_capacity : content-addressed result cache entries (LRU; 0
        disables caching *and* single-flight dedup).
    bucket_multiple : pad-to-bucket granularity for coalescing near-miss
        shapes (1 = exact-shape grouping only).
    start : spawn the drain thread now; ``start=False`` lets tests and
        benches queue a deterministic backlog first, then call
        :meth:`start`.
    """

    def __init__(self, *, engine: str = "auto",
                 interpret: Optional[bool] = None,
                 autotune: bool = False, cost_model=None,
                 max_batch: int = 8, batch_window_s: float = 0.002,
                 max_queue_depth: int = 64,
                 max_inflight_per_tenant: int = 16,
                 cache_capacity: int = 128, bucket_multiple: int = 64,
                 metrics: Optional[MetricsRecorder] = None,
                 start: bool = True, **solve_kw):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {max_queue_depth}")
        self._engine = engine
        self._interpret = interpret
        self._autotune = autotune
        self._cost_model = cost_model
        self._solve_kw = dict(solve_kw)
        self.max_batch = max_batch
        self.batch_window_s = batch_window_s
        self.max_queue_depth = max_queue_depth
        self.max_inflight_per_tenant = max_inflight_per_tenant
        self.bucket_multiple = bucket_multiple
        self.metrics = metrics if metrics is not None else MetricsRecorder()
        # Engine signature: part of the coalescing key so batches formed
        # under one config can never be replayed under another (matters
        # once per-request overrides exist; today it is service-constant).
        self._engine_sig = (engine, interpret, autotune,
                            tuple(sorted(self._solve_kw.items())))

        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._coalescer = Coalescer()
        self._cache: "Dict[str, Any]" = {}        # fingerprint -> result
        self._cache_lru: List[str] = []
        self.cache_capacity = cache_capacity
        # fingerprint -> primary PendingRequest with live joiner list
        self._inflight_by_fp: Dict[str, PendingRequest] = {}
        self._joiners: Dict[int, List[float]] = {}   # rid -> join t_submits
        self._tenant_inflight: Dict[str, int] = {}
        self._tenant_of: Dict[int, str] = {}
        self._inflight = 0
        self._rid = 0
        self._closing = False
        # Test hook (tests/test_serve.py failure injection): a predicate
        # over the claimed batch; True makes the batch solve raise, which
        # must reject only that batch's futures and keep the queue
        # draining.
        self.fail_injector: Optional[
            Callable[[List[PendingRequest]], bool]] = None

        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "IwppService":
        with self._lock:
            if self._closing:
                raise RuntimeError("service is closed")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._drain_loop, name="iwpp-serve", daemon=True)
                self._thread.start()
        return self

    def close(self, drain: bool = True) -> None:
        """Stop the service.  ``drain=True`` (default) serves every pending
        request first; ``drain=False`` rejects them with :class:`Rejected`.
        """
        if drain:
            with self._lock:
                need_start = (self._thread is None and not self._closing
                              and len(self._coalescer) > 0)
            if need_start:
                self.start()           # never-started service with a backlog
        with self._cond:
            self._closing = True
            if not drain:
                for req in self._coalescer.take_batch(10 ** 9):
                    self._resolve_failure(
                        [req], Rejected("service closed", 0.0))
                # keep draining whatever is already claimed
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()

    def __enter__(self) -> "IwppService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission --------------------------------------------------------
    def submit(self, op_name: str, inputs, *,
               connectivity: Optional[Union[int, str]] = None,
               tenant: str = "default") -> Future:
        """Queue one request; returns a Future resolving to the op's
        *finalized* result (``OpSpec.finalize`` semantics, the same array
        :func:`repro.ops.run_op` returns).

        ``inputs`` is the op's natural raw input(s) — an array, or a tuple
        of arrays for multi-input ops (morph: ``(marker, mask)``); the
        first input's shape is the request's spatial shape.  Raises
        :class:`Rejected` when admission control refuses (full queue /
        tenant cap), ``ValueError`` for an unknown op.
        """
        get_op(op_name)                       # unknown op: raise before queue
        inputs = inputs if isinstance(inputs, tuple) else (inputs,)
        inputs = tuple(np.asarray(x) for x in inputs)
        fp = content_fingerprint(op_name, inputs, connectivity)
        key = request_key(op_name, inputs[0].shape,
                          [str(x.dtype) for x in inputs], connectivity,
                          self._engine_sig, self.bucket_multiple)
        now = time.monotonic()
        with self._cond:
            if self._closing:
                raise RuntimeError("service is closed")
            hit = self._cache_get(fp)
            if hit is not None:
                self.metrics.count("submitted")
                self.metrics.count("cache_hits")
                self.metrics.count("completed")
                self.metrics.record_latency(time.monotonic() - now)
                fut: Future = Future()
                fut.set_result(hit)
                return fut
            primary = self._inflight_by_fp.get(fp)
            if primary is not None:
                # Single-flight: identical request already queued/solving —
                # share its future, count as a cache hit (it costs nothing).
                self.metrics.count("submitted")
                self.metrics.count("cache_hits")
                self._joiners[primary.rid].append(now)
                return primary.future
            # -- admission control ----------------------------------------
            if len(self._coalescer) >= self.max_queue_depth:
                self.metrics.count("rejected")
                raise Rejected(
                    f"queue full ({len(self._coalescer)} pending >= "
                    f"max_queue_depth={self.max_queue_depth})",
                    self._retry_after())
            if (self._tenant_inflight.get(tenant, 0)
                    >= self.max_inflight_per_tenant):
                self.metrics.count("rejected")
                raise Rejected(
                    f"tenant {tenant!r} at max_inflight_per_tenant="
                    f"{self.max_inflight_per_tenant}", self._retry_after())
            self._rid += 1
            req = PendingRequest(rid=self._rid, op_name=op_name,
                                 inputs=inputs, connectivity=connectivity,
                                 tenant=tenant, key=key, fingerprint=fp,
                                 future=Future(), t_submit=now)
            self._coalescer.push(req)
            if self.cache_capacity > 0:
                self._inflight_by_fp[fp] = req
            self._joiners[req.rid] = []
            self._tenant_inflight[tenant] = \
                self._tenant_inflight.get(tenant, 0) + 1
            self._tenant_of[req.rid] = tenant
            self.metrics.count("submitted")
            self.metrics.count("cache_misses")
            self._cond.notify_all()
            return req.future

    def _retry_after(self) -> float:
        backlog = len(self._coalescer) + self._inflight + 1
        return max(1e-3, self.metrics.ewma_request_s()
                   * backlog / max(1, self.max_batch))

    # -- result cache ------------------------------------------------------
    def _cache_get(self, fp: str):
        val = self._cache.get(fp)
        if val is not None:
            self._cache_lru.remove(fp)
            self._cache_lru.append(fp)
        return val

    def _cache_put(self, fp: str, val) -> None:
        if self.cache_capacity <= 0:
            return
        if fp not in self._cache:
            self._cache_lru.append(fp)
        self._cache[fp] = val
        while len(self._cache_lru) > self.cache_capacity:
            evict = self._cache_lru.pop(0)
            del self._cache[evict]

    # -- drain loop --------------------------------------------------------
    def _drain_loop(self) -> None:
        while True:
            with self._cond:
                while not self._closing and len(self._coalescer) == 0:
                    self._cond.wait()
                if self._closing and len(self._coalescer) == 0:
                    return
                head = self._coalescer.peek_oldest()
                if (self.batch_window_s > 0
                        and self._coalescer.compatible_pending(head.key)
                        < self.max_batch):
                    # Hold the batch open one window for compatible
                    # followers (re-checked once; bounded added latency).
                    self._cond.wait(self.batch_window_s)
                batch = self._coalescer.take_batch(self.max_batch)
                self._inflight += len(batch)
            if batch:
                self._execute(batch)

    def _execute(self, batch: List[PendingRequest]) -> None:
        import jax.numpy as jnp
        from repro.solve import solve_batch
        t0 = time.monotonic()
        try:
            if self.fail_injector is not None and self.fail_injector(batch):
                raise RuntimeError("injected batch failure (serve test hook)")
            spec = get_op(batch[0].op_name)
            op = spec.make_op(batch[0].connectivity)
            target = batch[0].key[1]          # the bucketed spatial shape
            states, origs = [], []
            for r in batch:
                st = spec.build_state(op, *(jnp.asarray(x) for x in r.inputs))
                p, orig = padded_state(op, st, target)
                states.append(p)
                origs.append(orig)
            results = solve_batch(op, states, engine=self._engine,
                                  interpret=self._interpret,
                                  autotune=self._autotune,
                                  cost_model=self._cost_model,
                                  **self._solve_kw)
        except BaseException as e:  # noqa: BLE001 — isolate to this batch
            self._resolve_failure(batch, e)
            return
        wall = time.monotonic() - t0
        self.metrics.record_batch(len(batch), wall)
        now = time.monotonic()
        with self._cond:
            for r, orig, (out, _st) in zip(batch, origs, results):
                res = spec.extract(op, crop_state(out, orig))
                self._cache_put(r.fingerprint, res)
                joins = self._release(r)
                self.metrics.count("completed", 1 + len(joins))
                self.metrics.record_latency(now - r.t_submit)
                for tj in joins:
                    self.metrics.record_latency(now - tj)
                r.future.set_result(res)

    def _resolve_failure(self, batch: List[PendingRequest],
                         exc: BaseException) -> None:
        """Reject exactly this batch's futures; the queue keeps draining."""
        with self._cond:
            for r in batch:
                joins = self._release(r)
                self.metrics.count("failed", 1 + len(joins))
                if not r.future.done():
                    r.future.set_exception(exc)

    def _release(self, r: PendingRequest) -> List[float]:
        """Drop one claimed request's accounting; returns joiner stamps."""
        self._inflight = max(0, self._inflight - 1)
        tenant = self._tenant_of.pop(r.rid, None)
        if tenant is not None:
            left = self._tenant_inflight.get(tenant, 1) - 1
            if left > 0:
                self._tenant_inflight[tenant] = left
            else:
                self._tenant_inflight.pop(tenant, None)
        if self._inflight_by_fp.get(r.fingerprint) is r:
            del self._inflight_by_fp[r.fingerprint]
        return self._joiners.pop(r.rid, [])

    # -- observability -----------------------------------------------------
    def stats(self) -> ServeStats:
        with self._lock:
            return self.metrics.snapshot(queue_depth=len(self._coalescer),
                                         inflight=self._inflight)
