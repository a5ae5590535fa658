"""Async federation serving: micro-batching + sharded caches.

``FederationService.handle`` pays one jitted agent dispatch per request —
fine for a demo, hopeless under traffic.  ``AsyncFederationService``
turns the service into an open system:

  * **submit/handle** — clients (any number of threads) enqueue requests;
    each gets a ``concurrent.futures.Future`` of a ``FederationResult``.
  * **micro-batching** — a dispatcher thread coalesces queued requests
    and flushes when ``max_batch`` are waiting or the oldest has waited
    ``max_wait_ms``.  Each flush costs ONE batched agent forward (the
    whole point: the per-call jit dispatch overhead is amortized over the
    flush) and, where the transport launches one (the thread plane's
    kernel path), ONE IoU launch for the flush's fresh images, issued by
    the dispatcher ahead of the forward, so the shard threads build
    tables from host arrays; with WBF, ONE device fuse of those images'
    requests after the forward, whose rows the shard threads take into
    their memo in place of grouping and fusing on the host.
  * **sharded caches behind a transport** — the subset-evaluation memo
    is split across W shared-nothing shards, each owned by one
    dispatcher-side thread.  The evaluation plane is pluggable
    (``transport=``, resolved through ``repro.serving.transports``):
    ``"thread"`` (default, ``ShardedSubsetEvaluationCore`` — in-process,
    zero IPC, assembly serializes on the GIL), ``"process"``
    (``ProcessShardedSubsetEvaluationCore`` — one worker process per
    shard, off the GIL) or ``"socket"``
    (``SocketShardedSubsetEvaluationCore`` — H shard HOSTS over TCP with
    consistent-hash routing and health-checked requeue).  All planes
    answer bit-identical results, and the service never asks which one
    it runs on: every plane's shard part goes through the one
    ``_account_shard`` (tables, then route, ensembles, results and log
    in ``FederationService._account_batch``); only ensemble rows cross
    an RPC transport's boundary.
  * **overlap** — the dispatcher hands each shard's slice of the flush to
    that shard's thread and immediately returns to batching: ensemble
    assembly (provider "inference" is parallel-latency in the paper's
    model, Sec. II-B) overlaps the NEXT flush's agent forward.

At ``max_batch=1, workers=1`` every request is its own flush through the
same single-state ``select_action`` call ``handle`` makes, so results are
identical to the synchronous service (``tests/test_async_service.py``).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from functools import partial
from typing import List, Optional, Sequence, Union

import numpy as np

from repro.federation.env import ArmolEnv
from repro.obs.metrics import MetricsRegistry, merge_snapshots
from repro.obs.tracing import NULL_SPAN, no_phase
from repro.serving.federation_service import (FederationResult,
                                              FederationService)
from repro.serving.transports import ShardTransport, get_transport

# the dict-shaped stats contract: key order and names are part of the
# public accessor (tests and benches read these directly)
_STAT_KEYS = ("requests", "flushes", "batched_requests", "max_flush",
              "flush_full", "flush_timeout", "flush_drain", "iou_launches",
              "fused_on_device")


class AsyncFederationService:
    """Micro-batching front-end over ``FederationService``.

    Parameters
    ----------
    max_batch:    flush when this many requests are queued.
    max_wait_ms:  ... or when the oldest queued request is this old.
    workers:      cache shards == ensemble workers (threads, processes
                  or locally spawned hosts, per the transport).
    transport:    the evaluation plane — a registered name (``"thread"``
                  default: in-process shards, zero IPC; ``"process"``:
                  one worker process per shard, off the GIL;
                  ``"socket"``: H shard hosts over TCP with health-
                  checked requeue) or a prebuilt
                  :class:`~repro.serving.transports.ShardTransport`
                  instance.  All planes answer bit-identical results.
    transport_options: transport-specific knobs passed to the registry
                  build (the socket plane's ``hosts=["addr:port", ...]``
                  / health intervals).
    mp_context:   multiprocessing start method for the process/socket
                  planes (``"spawn"`` default — the parent runs jax,
                  whose runtime threads do not survive ``fork``).
    adaptive:     deadline-aware flush sizing — queue depth scales the
                  wait budget down (see ``_flush_deadline``).  Off by
                  default: fixed ``max_batch``/``max_wait_ms`` behavior
                  is bit-identical to the non-adaptive service.
    pool:         optional scenario provider pool; the service keeps a
                  scenario clock (one step per request) and accounts each
                  flush under the pool's segment at that clock — cores,
                  fees and latencies swap mid-stream at flush boundaries.

    Use as a context manager (or call ``close()``): a dispatcher thread
    and W worker threads run behind the scenes.
    """

    def __init__(self, env: ArmolEnv, agent, *, deterministic: bool = True,
                 transmission_ms: float = 20.0, max_batch: int = 16,
                 max_wait_ms: float = 2.0, workers: int = 2,
                 adaptive: bool = False, pool=None,
                 transport: Union[str, ShardTransport] = "thread",
                 transport_options: Optional[dict] = None,
                 mp_context: str = "spawn", obs=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.env = env
        self.agent = agent
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.adaptive = bool(adaptive)
        # scenario pool (``repro.scenarios.pool.DynamicProviderPool`` or
        # anything with view_at/sharded_core_at/snapshot_at): each flush
        # is accounted under the pool state at the service's scenario
        # clock, which advances one step per request — mid-stream regime
        # swaps apply at flush boundaries, never inside one (the
        # transport's ``segment`` names what a flush evaluates against).
        self.pool = pool
        self._scn_clock = 0
        if isinstance(transport, str):
            transport = get_transport(transport).build(
                env=env, pool=pool, workers=int(workers),
                mp_context=mp_context, options=transport_options)
        self.transport = transport
        # the transport decides the real shard count (joined socket
        # hosts may outnumber ``workers``); one parent-side accounting
        # thread per shard id
        self.workers = int(transport.n_shards)
        self.shard_backend = transport.name
        self._svc = FederationService(env, agent,
                                      deterministic=deterministic,
                                      transmission_ms=transmission_ms)
        from repro.core.loops import agent_policy
        self._policy = agent_policy(agent, deterministic=deterministic)

        self._cv = threading.Condition()
        self._queue: deque = deque()  # (img_idx, enqueue_t, future, trace)
        self._closed = False
        # observability: the service's flush counters live on a metrics
        # registry (the obs handle's when given — so serve-level metrics
        # land in its metrics.json — else a private always-on one, which
        # keeps the ``stats`` accessor live with obs off).  flush_full /
        # flush_timeout/flush_drain: WHY each flush fired — queue hit
        # max_batch, the oldest request's deadline expired, or close()
        # drained the queue.  Tests assert on these instead of
        # wall-clock sleeps (timer behavior without timing flakiness).
        self.obs = obs
        self._obs_on = obs is not None and obs.enabled
        self._metrics = obs.metrics if self._obs_on else MetricsRegistry()
        self._tracer = obs.tracer if self._obs_on else None
        if self._tracer is not None and not self._tracer.enabled:
            self._tracer = None
        self._svc.obs = obs
        self._stat = {k: (self._metrics.gauge("serving." + k)
                          if k == "max_flush"
                          else self._metrics.counter("serving." + k))
                      for k in _STAT_KEYS}
        # phases of the served path (obs.phase: profiler annotation +
        # histogram + span of a sampled request); with obs off every
        # phase is the shared no-op span and no clock is read
        self._phase = obs.phase if self._obs_on else no_phase
        self._h_policy = self._h_policy_cpu = self._h_shard_wait = \
            self._h_iou_table = self._h_assemble_cpu = \
            self._h_iou_launch = self._h_fuse_launch = None
        if self._obs_on:
            self._h_flush_size = self._metrics.histogram(
                "serving.flush_size",
                bounds=tuple(float(b) for b in range(1, 65)))
            # enqueue -> popped by the dispatcher, per request
            self._h_queue_wait = self._metrics.histogram(
                "serving.queue_wait_ms")
            # the flush's decision: feature gather, padding, forward,
            # actions on the host
            self._h_policy = self._metrics.histogram("serving.policy_ms")
            # ... and the dispatcher thread's CPU time in it
            self._h_policy_cpu = self._metrics.histogram(
                "serving.policy_cpu_ms")
            # the dispatcher's IoU launch for the flush and, after the
            # decision, its readback (one observation each; a flush that
            # launches nothing observes the launch step alone)
            self._h_iou_launch = self._metrics.histogram(
                "serving.iou_launch_ms")
            # the dispatcher's device fuse of the flush (a flush that
            # launches no IoU fuses nothing and observes nothing)
            self._h_fuse_launch = self._metrics.histogram(
                "serving.fuse_launch_ms")
            # handed to a shard thread -> that thread starts on it
            self._h_shard_wait = self._metrics.histogram(
                "serving.shard_wait_ms")
            # one shard part's IoU tables, and the thread CPU time of its
            # whole assembly
            self._h_iou_table = self._metrics.histogram(
                "serving.iou_table_ms")
            self._h_assemble_cpu = self._metrics.histogram(
                "serving.assemble_cpu_ms")
            # IoU entries the launches computed (padded images x boxes^2)
            # and those the tables built use
            self._c_iou_launched = self._metrics.counter(
                "serving.iou_entries_launched")
            self._c_iou_used = self._metrics.counter(
                "serving.iou_entries_used")
            # providers each request's action selects
            self._h_providers = self._metrics.histogram(
                "serving.providers_selected",
                bounds=tuple(float(b) for b in range(
                    env.n_providers + 1)))
        # per-shard RPC latency histograms + condemned-shard counters
        # always land in the service's registry; worker-shipped spans
        # only when tracing is on (no-op for the thread transport)
        self.transport.bind_obs(self._metrics, self._tracer)
        # every device-fuse shape a flush can reach, compiled before the
        # first request (0 where flushes fuse on the host)
        self.fuse_shapes = self.transport.warm_fuse(self.max_batch)
        self._shard_pools = [
            ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix=f"fed-shard-{i}")
            for i in range(self.workers)]
        self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                            name="fed-dispatch", daemon=True)
        self._dispatcher.start()

    @property
    def core(self):
        """The transport's sharded core (on the thread plane with a pool,
        the live segment's)."""
        return self.transport.core

    # -- client surface --------------------------------------------------
    def submit(self, img_idx: int) -> "Future[FederationResult]":
        """Enqueue one request; returns immediately.

        Args:  ``img_idx`` — trace image id (int()-able).
        Returns: a ``concurrent.futures.Future`` resolving to the
          request's :class:`FederationResult` once its flush is assembled
          (``.result()`` blocks; ``handle`` is the blocking shorthand).
        Failure modes: raises ``RuntimeError`` when the service is
          closed; a failed flush (dead shard worker, evaluation error)
          sets that exception on every future of the affected flush —
          the service itself keeps serving subsequent requests.
        """
        fut: Future = Future()
        tid = self._tracer.sample_request() if self._tracer is not None \
            else None
        with self._cv:
            if self._closed:
                raise RuntimeError("AsyncFederationService is closed")
            self._queue.append((int(img_idx), time.monotonic(), fut, tid))
            self._cv.notify()
        if tid is not None:
            # the request span: enqueue -> future resolution (covers the
            # queue wait, the flush, the shard RPC and assembly)
            t_sub = time.monotonic()
            ts = time.time()
            img = int(img_idx)

            def _done(f, tid=tid, t_sub=t_sub, ts=ts, img=img):
                self._tracer.record({
                    "name": "request", "trace": tid, "span": tid,
                    "parent": None, "ts": ts,
                    "dur_ms": (time.monotonic() - t_sub) * 1e3,
                    "attrs": {"img": img,
                              "error": f.exception() is not None}})
            fut.add_done_callback(_done)
        return fut

    def handle(self, img_idx: int) -> FederationResult:
        return self.submit(img_idx).result()

    def handle_many(self, img_indices: Sequence[int]
                    ) -> List[FederationResult]:
        futs = [self.submit(i) for i in img_indices]
        return [f.result() for f in futs]

    # -- dispatcher ------------------------------------------------------
    def _flush_deadline(self, enqueue_t: float, depth: int) -> float:
        """When the oldest queued request must flush.

        Fixed mode (default): enqueue time + ``max_wait_ms`` — unchanged
        seed behavior.  Adaptive mode scales the wait DOWN with queue
        depth (deadline-aware flush sizing): an empty queue waits the
        full budget hoping to coalesce, a queue at ``max_batch`` flushes
        immediately — under load the service stops holding requests
        hostage to the timer, near idle it still batches aggressively.
        """
        if not self.adaptive:
            return enqueue_t + self.max_wait_s
        frac = min(depth / self.max_batch, 1.0)
        return enqueue_t + self.max_wait_s * (1.0 - frac)

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue:     # closed and drained
                    return
                while len(self._queue) < self.max_batch and not self._closed:
                    deadline = self._flush_deadline(self._queue[0][1],
                                                    len(self._queue))
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
                # why this flush fired — decided while the queue state is
                # still visible, counted with the other stats in _flush
                if len(self._queue) >= self.max_batch:
                    reason = "flush_full"
                elif self._closed:
                    reason = "flush_drain"
                else:
                    reason = "flush_timeout"
                batch = [self._queue.popleft()
                         for _ in range(min(self.max_batch,
                                            len(self._queue)))]
                t_pop = time.monotonic() if self._obs_on else 0.0
                clock = self._scn_clock
                if self.pool is not None:
                    self._scn_clock += len(batch)
            try:
                self._flush(batch, clock, reason, t_pop)
            except BaseException as e:   # keep serving after a bad flush
                for _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(e)

    def _flush(self, batch, clock: int, reason: str = "flush_full",
               t_pop: float = 0.0) -> None:
        span = NULL_SPAN
        if self._obs_on:
            self._h_flush_size.observe(len(batch))
            self._h_queue_wait.observe_batch(
                [(t_pop - b[1]) * 1e3 for b in batch])
            # the flush span hangs off the first sampled request of the
            # batch; the per-shard assembly hangs off it as child spans
            tids = [b[3] for b in batch if b[3] is not None]
            tid = tids[0] if tids else None
            span = self._phase("flush", None, tid, tid, reason=reason,
                               size=len(batch), clock=int(clock),
                               n_traced=len(tids))
        with span:
            self._flush_batch(batch, clock, reason, span)

    def _decide(self, imgs: np.ndarray, clock: int) -> np.ndarray:
        """The flush's actions, one row per request, on the host."""
        sel = getattr(self.agent, "select_for_images", None)
        if sel is not None:
            # selector policy: decide straight from the image indices —
            # no feature forward, no padding; the same call the sync
            # service makes, so both paths are bit-identical by
            # construction.  The flush clock pins the pool segment.
            if self.pool is not None:
                return np.asarray(sel(imgs, step=clock), np.float32)
            return np.asarray(sel(imgs), np.float32)
        if len(imgs) == 1:
            # same single-state act path as FederationService.handle, so
            # max_batch=1 is result-identical to the synchronous service
            a, _ = self.agent.select_action(
                self.env.features[imgs[0]],
                deterministic=self._svc.deterministic)
            return np.asarray(a, np.float32).reshape(1, -1)
        # pad the flush to max_batch so the batched forward is shape-
        # stable: one jit compile for the service's lifetime instead of
        # one per distinct queue depth (row-independent MLP heads make
        # the padding rows inert)
        feats = self.env.features[imgs]
        if len(imgs) < self.max_batch:
            pad = np.broadcast_to(
                feats[-1], (self.max_batch - len(imgs), feats.shape[1]))
            feats = np.concatenate([feats, pad], axis=0)
        return np.asarray(self._policy.select_batch(feats),
                          np.float32)[:len(imgs)]

    def _flush_batch(self, batch, clock: int, reason: str, span) -> None:
        imgs = np.asarray([b[0] for b in batch], np.int64)
        costs = lats = None
        if self.pool is not None:
            # one consistent (segment, fee/latency) snapshot per flush:
            # in-flight assembly keeps its captured segment even if the
            # clock crosses a boundary while it overlaps the next flush
            view = self.pool.view_at(clock)
            costs, lats = view.costs, view.latencies
        seg = self.transport.segment(self.pool, clock)
        # the flush's IoU launch, queued on the device ahead of the
        # forward; its readback waits until the decision is back
        with self._phase("iou_launch", self._h_iou_launch):
            pending = self.transport.launch_iou(seg, imgs)
        with self._phase("policy", self._h_policy,
                         cpu_hist=self._h_policy_cpu):
            actions = self._decide(imgs, clock)
        ious = fused = None
        if pending is not None:
            # the flush's cold ensembles, queued behind the IoU launch
            # whose result they read on the device; nobody waits here
            with self._phase("fuse_launch", self._h_fuse_launch):
                fused = self.transport.launch_fuse(seg, pending, imgs,
                                                   actions, self.max_batch)
            with self._phase("iou_launch", self._h_iou_launch):
                ious = pending.matrices()
        if self._obs_on:
            self._h_providers.observe_batch(
                (np.asarray(actions) > 0.5).sum(axis=1).tolist())
        with self._cv:      # counters race with reset_stats() otherwise
            if pending is not None:
                self._count_iou(pending.launched, 0)
            self._stat["flushes"].inc()
            self._stat[reason].inc()
            self._stat["requests"].inc(len(batch))
            if len(batch) > 1:
                self._stat["batched_requests"].inc(len(batch))
            self._stat["max_flush"].set_max(len(batch))
        # span + log context for the fan-out; both None-cheap when obs
        # is off
        trace_ctx = None if span.trace_id is None else \
            (span.trace_id, span.span_id)
        log_ctx = None
        if self.obs is not None and self.obs.serving_log is not None:
            seg_idx = None if self.pool is None else \
                int(self.pool.schedule.segment_index(clock))
            log_ctx = {"seg": seg_idx, "clock": int(clock),
                       "reason": reason, "backend": self.shard_backend}
        # fan out by home shard; the dispatcher does NOT wait — ensemble
        # assembly overlaps the next flush's agent forward
        for sid, positions in self._partition(imgs).items():
            self._shard_pools[sid].submit(
                self._account_shard, seg, sid,
                [batch[p] for p in positions], actions[positions],
                costs, lats, trace_ctx, log_ctx,
                time.perf_counter() if self._obs_on else 0.0, ious,
                None if fused is None else fused.part(positions))

    def _partition(self, imgs: np.ndarray):
        groups: dict = {}
        route = self.transport.route
        for pos, img in enumerate(imgs):
            groups.setdefault(route(img), []).append(pos)
        return groups

    def _count_iou(self, launched: int, used: int) -> None:
        """An IoU launch the service learns of (the flush's, or a shard
        part's for images the flush's lacked): ``launched`` entries
        computed, ``used`` entries the tables built took.  ``_cv``'s lock
        is re-entrant, so the dispatcher counts under its own hold."""
        if launched:
            with self._cv:
                self._stat["iou_launches"].inc()
        if self._obs_on:
            self._c_iou_launched.inc(launched)
            self._c_iou_used.inc(used)

    def _account_shard(self, seg, sid: int, items, actions: np.ndarray,
                       costs, lats, trace_ctx=None, log_ctx=None,
                       t_submit: float = 0.0, ious=None,
                       fused=None) -> None:
        """Runs on shard ``sid``'s dedicated thread — the only thread that
        ever writes that shard's dicts (for the flush's segment ``seg``)
        or talks to its worker/host.  The dispatcher only reads the table
        memo's membership, to choose what the flush's launch covers;
        ``ious`` is that launch's host matrices by image id, and
        ``fused`` this part's rows of the flush's device fuse, which the
        shard's memo takes where it lacks them (waiting for the fuse's
        host copy).  An image whose table was in flight when the
        dispatcher looked is delivered twice and built once; one that the
        launch lacks is launched for here and fused on the host.  A dead
        worker fails this shard's futures cleanly (the socket plane first
        requeues to surviving hosts) — other shards and the dispatcher
        keep serving."""
        # the flush span a sampled request's assembly hangs off
        tid, parent = trace_ctx or (None, None)
        try:
            if self._obs_on:
                self._h_shard_wait.observe(
                    (time.perf_counter() - t_submit) * 1e3)
            with self._phase("shard_assemble", None, tid, parent,
                             cpu_hist=self._h_assemble_cpu, shard=sid,
                             n=len(items)) as asm:
                imgs = [it[0] for it in items]
                with self._phase("iou_table", self._h_iou_table, tid,
                                 asm.span_id):
                    launched, used = self.transport.tables(seg, sid, imgs,
                                                           ious)
                self._count_iou(launched, used)
                # a worker's eval span hangs off this assemble span
                wire = None if asm.trace_id is None else \
                    (asm.trace_id, asm.span_id)
                with self._phase("fuse", None, tid, asm.span_id):
                    results = self._svc._account_batch(
                        imgs, actions,
                        core=self.transport.local_core(seg, sid),
                        ensembles=partial(self.transport.ensembles, seg,
                                          sid, imgs, trace=wire,
                                          fused=fused),
                        costs=costs, latency_ms=lats, log_ctx=log_ctx)
                if fused is not None and fused.taken:
                    with self._cv:
                        self._stat["fused_on_device"].inc(fused.taken)
            for (_, _, fut, _), res in zip(items, results):
                fut.set_result(res)
        except BaseException as e:
            for _, _, fut, _ in items:
                if not fut.done():
                    fut.set_exception(e)

    # -- cache invalidation ----------------------------------------------
    def invalidate_images(self, img_indices: Sequence[int]) -> int:
        """Drop the images' cached artifacts EVERYWHERE this service
        could read them back: the live shard plane (all regimes on all
        worker processes or hosts) and, when a pool is attached, every
        segment core the pool has materialized on the parent side.  This
        is the one invalidation entry point callers should use —
        invalidating only the pool (or only the core) leaves the other
        side serving stale ensembles."""
        return self.transport.invalidate(img_indices, self.pool)

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        self._dispatcher.join()
        for pool in self._shard_pools:
            pool.shutdown(wait=True)
        self.transport.close()      # reap workers/hosts (threads: no-op)

    def __enter__(self) -> "AsyncFederationService":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None

    def mean_flush_size(self) -> float:
        return self.stats["requests"] / max(self.stats["flushes"], 1)

    # -- scenario clock --------------------------------------------------
    @property
    def clock(self) -> int:
        with self._cv:
            return self._scn_clock

    def set_clock(self, step: int) -> None:
        """Jump the scenario clock (e.g. to force a regime for tests or
        to sync with an external scheduler).  Takes effect at the next
        flush boundary; flushes already dispatched keep their snapshot."""
        with self._cv:
            self._scn_clock = int(step)

    # -- observability accessors ------------------------------------------
    @property
    def stats(self) -> dict:
        """The dict-shaped flush-counter accessor (key order is part of
        the contract): live values read off the metrics registry."""
        return {k: int(m.value) for k, m in self._stat.items()}

    def reset_stats(self) -> None:
        """Zero the flush counters (e.g. after warm-up traffic), so
        reported batching stats cover only the measured window."""
        with self._cv:     # same guard the counters increment under
            self._metrics.reset(prefix="serving.")

    def extra_metric_snapshots(self) -> list:
        """Shard-side snapshots NOT already in the service's registry:
        each worker/host registry shipped back over the transport (RPC
        planes) or the sharded core's hit/miss counters (threads).  Feed
        these to ``Obs.write_metrics`` — the obs registry itself is the
        service's registry, so only these extras need merging in."""
        return [self.transport.snapshot()]

    def metrics_snapshot(self, include_workers: bool = True) -> dict:
        """One merged counters/gauges/histograms snapshot for this
        service: its registry plus each shard's side of the story
        (worker/host registries over RPC, the sharded core's hit/miss
        counters on threads).  Plain dicts, mergeable with
        :func:`repro.obs.merge_snapshots`."""
        snaps = [self._metrics.snapshot()]
        if include_workers:
            snaps.extend(self.extra_metric_snapshots())
        return merge_snapshots(*snaps)
