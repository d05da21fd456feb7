"""Transfer + stage pipelining: the generalized ping/pong engine.

The paper overlaps host->device transfer of batch k+1 with compute of
batch k through a pair of HBM channel buffers (Fig. 14a), and its
multi-accelerator system keeps *every* pipeline stage busy on a
different batch simultaneously.  JAX gives the same overlap for free
*if* the driver (1) enqueues ``jax.device_put`` of upcoming batches
before blocking on results, (2) defers the host sync by one batch so
the dispatch queue never drains, and (3) dispatches the stages of a
multi-operator chain *skewed* -- stage i of batch k in the same breath
as stage i+1 of batch k-1 -- so no stage's dispatch ring ever idles
waiting for the whole previous batch to finish.  This module packages
those tricks behind one generic ring so every workload (CFD
simulation, serving, benchmarks, tests) uses the identical machinery
instead of hand-rolling the loop.

``depth`` is the plan's prefetch K: 0 = fully serial (stage, compute,
sync -- the paper's baseline), 1 = classic double buffering, K>1 = deeper
staging that also rides out host-side jitter.

:func:`run_stage_pipelined` runs a chain with one dispatch ring per
stage (per-stage depths; one stage is the single-operator K-deep
engine), handing HBM-resident inter-stage values from producer to
consumer without host round-trips.
Its multi-device mode (``place_fns``, built from a
:class:`~repro.memory.placement.PlacementPlan` via
:func:`placement_meshes`) runs one dispatch ring per *device group*:
each stage shards its element batch over its own group's mesh and the
HBM-resident handoff is resharded between groups as it crosses.

:class:`StagePipelineDriver` is the reentrant core it builds on: the
same skewed ring as a feed/tick state machine, so a long-running caller
(``repro.serve``) can push batches as they arrive, idle the ring dry,
and resume -- with optional per-batch error capture instead of the
batch-job raise-through.
"""
from __future__ import annotations

import time
from collections import deque
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

import jax

# Span categories (the ``repro.trace.attribution`` vocabulary).  The
# tracer is duck-typed -- any object with begin/end/span/name_track/bump,
# falsy when disabled -- so this module never imports ``repro.trace``
# and the executors stay import-light.
_CAT_SLOT = "slot"
_CAT_DISPATCH = "dispatch"
_CAT_HANDOFF = "handoff"
_CAT_STAGE_HOST = "stage-host"
_CAT_RETIRE = "retire"
_CAT_SYNC = "sync"
_HOST_TRACK = 0


def _traced_stage_fn(stage_fn: Callable[[Any], Any], tracer) -> Callable:
    """Wrap the staging fn so each host->device stage gets a host-track
    span (batch index = call order, which is staging order)."""
    counter = [0]

    def staged(item: Any) -> Any:
        j = counter[0]
        counter[0] += 1
        with tracer.span(f"stage b{j}", _CAT_STAGE_HOST, _HOST_TRACK,
                         batch=j):
            return stage_fn(item)

    return staged


def placement_meshes(
    placement, devices: Optional[Sequence[Any]] = None
) -> Optional[List[Tuple[Any, ...]]]:
    """Per-stage local device groups for a PlacementPlan.

    Maps each stage's topology device ids onto the local JAX devices
    (``devices`` defaults to ``jax.devices()``).  Returns None when the
    placement does not fit the local pool (too few devices) or is the
    degenerate single-group case -- callers then fall back to today's
    single-mesh execution, which is bitwise-identical by construction.
    """
    if placement is None:
        return None
    devices = list(devices) if devices is not None else list(jax.devices())
    used = placement.devices_used
    if not used or used[-1] >= len(devices):
        return None  # placement planned for a bigger machine than this
    groups = [
        tuple(devices[d] for d in sp.devices) for sp in placement.stages
    ]
    if len({g for g in groups}) == 1 and len(groups[0]) == 1:
        return None  # every stage on one device: today's path exactly
    return groups


def reblock_batched_fn(
    fn: Callable[[Dict[str, Any]], Dict[str, Any]],
    element_keys: Sequence[str],
    sub_elements: int,
) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    """Re-blocking handoff: run a batched dict->dict stage fn at its own
    (smaller) E_s inside a chain batch of E elements.

    The wrapper slices every element-keyed array along the leading batch
    axis into ``sub_elements`` chunks, runs ``fn`` per chunk (shared
    operands pass through whole), and concatenates the outputs back to
    the chain batch -- all on device, so the handoff stays HBM-resident.
    Elements are independent along the batch axis (the same property the
    element-sharded meshes rely on), so the result is bitwise-equal to
    one full-batch call; only the dispatch granularity changes.  A batch
    no larger than ``sub_elements`` calls ``fn`` untouched."""
    import jax.numpy as jnp

    keys = frozenset(element_keys)
    sub = max(1, int(sub_elements))

    def reblocked(env: Dict[str, Any]) -> Dict[str, Any]:
        n = next(
            (env[k].shape[0] for k in env if k in keys), None
        )
        if n is None or n <= sub:
            return fn(env)
        outs = []
        for lo in range(0, n, sub):
            outs.append(fn({
                k: (v[lo:lo + sub] if k in keys else v)
                for k, v in env.items()
            }))
        return {
            k: jnp.concatenate([o[k] for o in outs], axis=0)
            for k in outs[0]
        }

    return reblocked


def stage_skews(depths: Sequence[int]) -> List[int]:
    """How many batches each stage lags behind stage 0.

    ``depths[0]`` is the host staging depth (it skews nothing -- staging
    runs *ahead*); ``depths[i>0]`` is the dispatch-ring depth between
    stage i-1 and stage i, i.e. how many batches of the inter-stage
    stream may be in flight before stage i consumes the oldest.  Skews
    accumulate: with per-ring depth 1 on a 3-stage chain, stage 2 works
    on batch k-2 while stage 0 works on batch k.
    """
    skews = [0] * len(depths)
    for i in range(1, len(depths)):
        skews[i] = skews[i - 1] + depths[i]
    return skews


def run_stage_pipelined(
    stage_fns: Sequence[Callable[[Any, Any], Any]],
    batches: Iterable[Any],
    *,
    stage_fn: Callable[[Any], Any] = lambda x: x,
    depths: Union[int, Sequence[int]] = 1,
    reduce_fn: Optional[Callable[[Any], Any]] = None,
    defer_sync: Optional[bool] = None,
    place_fns: Optional[Sequence[Optional[Callable[[Any, Any],
                                                   Any]]]] = None,
    tracer=None,
    monitor=None,
    stage_names: Optional[Sequence[str]] = None,
    metrics=None,
    metrics_labels: Optional[Dict[str, str]] = None,
) -> List[Any]:
    """Run every batch through a chain of stages, cross-batch pipelined.

    Each ``stage_fns[i]`` is called as ``fn(staged, carry)`` where
    ``staged`` is the batch's staged host input and ``carry`` is the
    value returned by stage i-1 for the same batch (``None`` for stage
    0); its return value is handed to stage i+1 *on device* -- the
    HBM-resident inter-stage stream.  The last stage's carry is realized
    (via ``reduce_fn``, then ``jax.device_get``) and the per-batch
    results are returned in batch order.

    ``depths`` is one dispatch-ring depth per stage (an int applies
    chain-wide): ``depths[0]`` stages that many host batches ahead of
    the one computing; ``depths[i>0]`` lets stage i run that many
    batches behind stage i-1, so with any positive inter-stage depth the
    dispatch order interleaves stage i of batch k with stage i+1 of
    batch k-1 (software pipelining).  All inter-stage depths 0 degrades
    to the back-to-back schedule: every stage of batch k before batch
    k+1.

    Every batch still passes through every stage exactly once with
    identical inputs, so results are bitwise-equal to the serial
    schedule -- only the dispatch interleaving changes.

    ``place_fns`` is the multi-device hook: ``place_fns[i](staged,
    carry)`` runs right before stage i consumes a batch and returns the
    ``(staged, carry)`` pair moved onto stage i's device group (e.g.
    ``jax.device_put`` of the HBM-resident handoff onto the consumer's
    element-sharded mesh).  ``None`` entries (or ``place_fns=None``)
    leave the record untouched -- the single-device fallback.

    ``tracer`` (``repro.trace.Tracer``; None/NULL = off) gives each
    stage its own track: every (stage, batch) dispatch becomes a *slot*
    span carrying ``stage``/``batch``/``tick`` args, with the reshard
    handoff and the stage-fn dispatch as nested children; host staging,
    each retire's ``reduce_fn`` and the syncs after it land on the host
    track.  ``monitor`` (a
    ``runtime.StepMonitor``) is fed the wall time between consecutive
    batch retirements; flagged steps annotate the retire's sync span
    with ``straggler=True``.  Both only observe -- per-batch results are
    identical with or without them.

    ``metrics`` (a ``repro.metrics`` registry; None/NULL = off) records
    per-stage dispatch/handoff time histograms, stall counters, and a
    tick histogram, labeled with ``metrics_labels`` (the serve engine
    passes the plan signature) -- always-on telemetry next to the
    tracer's bounded spans.  Observation only, like the tracer.
    """
    driver = StagePipelineDriver(
        stage_fns, stage_fn=stage_fn, depths=depths, reduce_fn=reduce_fn,
        defer_sync=defer_sync, place_fns=place_fns, tracer=tracer,
        monitor=monitor, stage_names=stage_names,
        metrics=metrics, metrics_labels=metrics_labels,
    )
    it = iter(batches)
    while True:
        while driver.wants_input:
            try:
                driver.feed(next(it))
            except StopIteration:
                driver.close()
                break
        if driver.idle:
            break
        driver.tick()
    return [v for _, v in driver.take()]


class _Poison:
    """A captured per-batch failure riding the carry slot: downstream
    stages skip the batch and retire delivers the error in its place."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException) -> None:
        self.error = error


class StagePipelineDriver:
    """The skewed dispatch ring of :func:`run_stage_pipelined` as a
    reentrant feed/tick state machine.

    :func:`run_stage_pipelined` drives it feed-while-hungry/tick-until-
    dry over a finite batch source and is tick-for-tick identical to the
    closed-form loop it replaced.  A long-running caller (the
    ``repro.serve`` engine) instead interleaves :meth:`feed` and
    :meth:`tick` as admission waves arrive: each fed batch remembers the
    tick it *entered* the ring, and stage ``i`` dispatches batch ``k``
    once (a) stage ``i-1`` has finished it and (b) ``skews[i]`` ticks
    have passed since entry -- so a ring that went idle resumes with the
    same per-stage skew for the batches that follow, no global restart.

    ``capture_errors=True`` turns the batch-job raise-through into
    per-batch delivery: a stage/place/reduce/sync failure poisons that
    batch's record, downstream stages skip it, and :meth:`take` yields
    ``(k, exception)`` for it -- the ring itself never wedges.  The
    default (``False``) propagates, exactly like the batch driver.
    """

    def __init__(
        self,
        stage_fns: Sequence[Callable[[Any, Any], Any]],
        *,
        stage_fn: Callable[[Any], Any] = lambda x: x,
        depths: Union[int, Sequence[int]] = 1,
        reduce_fn: Optional[Callable[[Any], Any]] = None,
        defer_sync: Optional[bool] = None,
        place_fns: Optional[Sequence[Optional[Callable[[Any, Any],
                                                       Any]]]] = None,
        tracer=None,
        monitor=None,
        stage_names: Optional[Sequence[str]] = None,
        capture_errors: bool = False,
        metrics=None,
        metrics_labels: Optional[Dict[str, str]] = None,
    ) -> None:
        stage_fns = list(stage_fns)
        n_stages = len(stage_fns)
        if n_stages == 0:
            raise ValueError("need at least one stage")
        if place_fns is not None and len(place_fns) != n_stages:
            raise ValueError(
                f"need {n_stages} place fns, got {len(place_fns)}"
            )
        if isinstance(depths, int):
            depths = [depths] * n_stages
        else:
            depths = list(depths)
        if len(depths) != n_stages:
            raise ValueError(
                f"need {n_stages} stage depths, got {len(depths)}"
            )
        if any(d < 0 for d in depths):
            raise ValueError(f"stage depths must be >= 0, got {depths}")
        if defer_sync is None:
            defer_sync = any(d > 0 for d in depths)
        names = (list(stage_names) if stage_names
                 else [f"stage{i}" for i in range(n_stages)])
        if len(names) != n_stages:
            raise ValueError(
                f"need {n_stages} stage names, got {len(names)}"
            )
        if tracer:
            tracer.name_track(_HOST_TRACK, "host")
            for i, nm in enumerate(names):
                tracer.name_track(1 + i, nm)
            stage_fn = _traced_stage_fn(stage_fn, tracer)
        self.stage_fns = stage_fns
        self.stage_fn = stage_fn
        self.depths = depths
        self.skews = stage_skews(depths)
        self.reduce_fn = reduce_fn
        self.defer_sync = defer_sync
        self.place_fns = place_fns
        self.tracer = tracer
        self.monitor = monitor
        self.names = names
        self.capture_errors = capture_errors
        # -- always-on metrics (duck-typed like the tracer: this module
        # never imports repro.metrics; any registry-shaped object works,
        # and a falsy one -- None or NULL_REGISTRY -- costs one check
        # here and nothing per tick) ----------------------------------------
        self._m_tick = self._m_dispatch = self._m_handoff = None
        self._m_stall = None
        if metrics:
            lab = dict(metrics_labels or {})
            self._m_tick = metrics.histogram(
                "pipeline_tick_seconds",
                "One driver tick: enter/dispatch-all-stages/retire.", **lab)
            self._m_dispatch = [
                metrics.histogram(
                    "pipeline_stage_dispatch_seconds",
                    "One (stage, batch) dispatch slot, handoff included.",
                    stage=nm, **lab)
                for nm in names
            ]
            self._m_handoff = [
                metrics.histogram(
                    "pipeline_stage_handoff_seconds",
                    "Cross-group reshard of the HBM-resident handoff.",
                    stage=nm, **lab)
                for nm in names
            ]
            self._m_stall = [
                {
                    reason: metrics.counter(
                        "pipeline_stall_total",
                        "Skipped stage dispatches by cause: ring skew "
                        "not yet satisfied, or producer stage behind.",
                        stage=nm, reason=reason, **lab)
                    for reason in ("skew", "producer")
                }
                for nm in names
            ]
        # -- ring state ------------------------------------------------------
        self._staged: deque = deque()       # staged, not yet entered
        #: batch k -> [staged, carry]; held from entry until retire (the
        #: window the planner prices as ring replicas)
        self._records: Dict[int, List[Any]] = {}
        self._entry_tick: Dict[int, int] = {}
        self._done = [0] * n_stages         # next batch stage i dispatches
        self._retire_next = 0
        self._entered = 0                   # batches entered into the ring
        self._accepted = 0                  # batches fed (entered + staged)
        self._t = 0
        self._pending: deque = deque()      # deferred (value, k) syncs
        self._out: deque = deque()          # retired (k, result) in order
        self._closed = False
        self._last_retire = (
            [time.perf_counter()] if monitor is not None else None
        )

    # -- feeding -------------------------------------------------------------
    @property
    def wants_input(self) -> bool:
        """True while the host staging window (``depths[0]`` ahead plus
        the one entering this tick) has room and the source isn't closed."""
        return not self._closed and len(self._staged) <= self.depths[0]

    @property
    def in_flight(self) -> int:
        """Batches accepted but not yet delivered through :meth:`take`."""
        return (len(self._staged) + len(self._records)
                + len(self._pending) + len(self._out))

    @property
    def accepted(self) -> int:
        """Total batches fed so far (the next :meth:`feed`'s index)."""
        return self._accepted

    @property
    def idle(self) -> bool:
        """True when nothing is staged, in the ring, or pending sync."""
        return not (self._staged or self._records or self._pending)

    def feed(self, item: Any) -> int:
        """Stage one batch into the ring; returns its batch index."""
        if self._closed:
            raise RuntimeError("driver is closed")
        k = self._accepted
        try:
            self._staged.append(self.stage_fn(item))
        except Exception as e:
            if not self.capture_errors:
                raise
            self._staged.append(_Poison(e))
        self._accepted += 1
        return k

    def close(self) -> None:
        """No more batches will be fed; remaining ticks drain the ring."""
        self._closed = True

    # -- the tick ------------------------------------------------------------
    def tick(self) -> bool:
        """Advance the ring one tick: enter at most one staged batch,
        give every stage its one skew-scheduled dispatch, retire at most
        one finished batch.  Returns False once nothing progressed (ring
        dry -- feed more or stop)."""
        tracer = self.tracer
        tick_t0 = time.perf_counter() if self._m_tick is not None else 0.0
        progressed = False
        if self._staged:
            k = self._entered
            staged = self._staged.popleft()
            if isinstance(staged, _Poison):
                self._records[k] = [None, staged]
            else:
                self._records[k] = [staged, None]
            self._entry_tick[k] = self._t
            self._entered += 1
            progressed = True
        t = self._t
        for i, fn in enumerate(self.stage_fns):
            k = self._done[i]
            if k not in self._records or k >= self._entered:
                continue
            if t - self._entry_tick[k] < self.skews[i]:
                if self._m_stall is not None:
                    self._m_stall[i]["skew"].inc()
                continue  # ring depth: stage i lags entry by skews[i]
            if i > 0 and self._done[i - 1] <= k:
                if self._m_stall is not None:
                    self._m_stall[i]["producer"].inc()
                continue  # producer stage hasn't finished this batch
            self._done[i] = k + 1
            progressed = True
            rec = self._records[k]
            if isinstance(rec[1], _Poison):
                continue  # upstream failure: skip, deliver at retire
            slot = (tracer.begin(f"b{k}", _CAT_SLOT, 1 + i,
                                 stage=i, batch=k, tick=t)
                    if tracer else None)
            slot_t0 = (time.perf_counter()
                       if self._m_dispatch is not None else 0.0)
            try:
                if self.place_fns is not None and self.place_fns[i] is not None:
                    hand_t0 = (time.perf_counter()
                               if self._m_handoff is not None else 0.0)
                    if tracer:
                        with tracer.span(f"reshard b{k}", _CAT_HANDOFF,
                                         1 + i, stage=i, batch=k):
                            rec[0], rec[1] = self.place_fns[i](rec[0], rec[1])
                    else:
                        rec[0], rec[1] = self.place_fns[i](rec[0], rec[1])
                    if self._m_handoff is not None:
                        self._m_handoff[i].observe(
                            time.perf_counter() - hand_t0)
                if tracer:
                    with tracer.span(self.names[i], _CAT_DISPATCH, 1 + i,
                                     stage=i, batch=k):
                        rec[1] = fn(rec[0], rec[1])
                else:
                    rec[1] = fn(rec[0], rec[1])
            except Exception as e:
                if not self.capture_errors:
                    raise
                rec[1] = _Poison(e)
            if self._m_dispatch is not None:
                self._m_dispatch[i].observe(time.perf_counter() - slot_t0)
            if slot is not None:
                tracer.end(slot)
        k = self._retire_next
        if k in self._records and self._done[-1] > k:
            rec = self._records.pop(k)
            del self._entry_tick[k]
            self._retire_next += 1
            self._retire(rec[1], k)
            progressed = True
        if not self._records and not self._staged:
            while self._pending:
                self._flush_one()
        self._t += 1
        if self._m_tick is not None:
            self._m_tick.observe(time.perf_counter() - tick_t0)
        return progressed

    # -- retire / sync -------------------------------------------------------
    def _retire(self, carry: Any, k: int) -> None:
        if isinstance(carry, _Poison):
            self._out.append((k, carry.error))
            return
        tracer = self.tracer
        try:
            if self.reduce_fn is None:
                value = carry
            elif tracer:
                with tracer.span(f"retire b{k}", _CAT_RETIRE, _HOST_TRACK,
                                 batch=k):
                    value = self.reduce_fn(carry)
            else:
                value = self.reduce_fn(carry)
        except Exception as e:
            if not self.capture_errors:
                raise
            self._out.append((k, e))
            return
        if not self.defer_sync:
            self._deliver_sync(value, k)
            return
        self._pending.append((value, k))
        if len(self._pending) > 1:
            self._flush_one()

    def _flush_one(self) -> None:
        self._deliver_sync(*self._pending.popleft())

    def _deliver_sync(self, value: Any, k: int) -> None:
        try:
            self._out.append((k, self._sync_get(value, k)))
        except Exception as e:
            if not self.capture_errors:
                raise
            self._out.append((k, e))

    def _sync_get(self, value: Any, k: int) -> Any:
        tracer = self.tracer
        sp = (tracer.begin(f"sync b{k}", _CAT_SYNC, _HOST_TRACK, batch=k)
              if tracer else None)
        try:
            got = jax.device_get(value)
        except Exception:
            if self.capture_errors and sp is not None:
                tracer.end(sp)
            raise
        if self.monitor is not None:
            now = time.perf_counter()
            flagged = self.monitor.record(now - self._last_retire[0])
            self._last_retire[0] = now
            if flagged and sp is not None:
                sp.args["straggler"] = True
        if sp is not None:
            tracer.end(sp)
        return got

    # -- results -------------------------------------------------------------
    def take(self) -> List[Tuple[int, Any]]:
        """Drain the delivered results: ``(batch index, realized value)``
        pairs in batch order (the value is the captured exception for a
        poisoned batch under ``capture_errors``)."""
        out = list(self._out)
        self._out.clear()
        return out
