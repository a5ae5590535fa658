"""The shard transport seam: one protocol, a registry, three planes.

``AsyncFederationService`` programs against :class:`ShardTransport`
alone and never asks which plane it runs on.  This module holds:

  * :class:`ShardTransport` — the protocol: per flush, its segment and
    IoU launch; per shard part, its tables and ensembles; routing,
    invalidation, metrics and close.  The base class answers as an RPC
    plane does: (image, mask) rows go to shard workers or hosts, which
    build their own tables.
  * a **registry** — transports self-register under their wire name;
    ``AsyncFederationService(transport="socket")`` resolves through
    :func:`get_transport`, so downstream planes (and tests) can register
    their own without touching the service.
  * :class:`ThreadTransport` / :class:`ProcessTransport` /
    :class:`SocketTransport` — the in-process shards, the W-worker
    process plane, and the H-host socket plane, all answering
    bit-identical rows (``tests/test_serving_socket.py`` holds the
    three-way parity).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Type

from repro.ensemble.boxes import Detections

_REGISTRY: Dict[str, Type["ShardTransport"]] = {}


def register_transport(name: str):
    """Class decorator: publish a transport under its wire name."""
    def _reg(cls: Type["ShardTransport"]) -> Type["ShardTransport"]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return _reg


def get_transport(name: str) -> Type["ShardTransport"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown shard transport {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_transports() -> List[str]:
    return sorted(_REGISTRY)


class ShardTransport:
    """What the async service needs from an evaluation plane.

    A transport OWNS its core (built in :meth:`build`, reaped in
    :meth:`close`) and answers:

      * ``route(img)`` — the image's home shard id in
        ``[0, n_shards)``; the service runs one parent-side accounting
        thread per shard id.
      * ``segment(pool, clock)`` — what a flush at scenario ``clock``
        evaluates against, handed back to every call below.
      * ``launch_iou(seg, imgs)`` — the flush's IoU launch, or ``None``.
      * ``launch_fuse(seg, pending, imgs, actions, n_rows)`` — the
        flush's device fuse over that launch, or ``None``; each shard
        part gets its rows of it (``part``).
      * ``warm_fuse(n_rows)`` — compile every shape of that fuse; the
        number of shapes.
      * ``tables(seg, sid, imgs, ious)`` — build shard ``sid``'s tables
        for its part of the flush from the launch's host matrices;
        returns the IoU entries launched and used.
      * ``ensembles(seg, sid, imgs, masks, trace, fused)`` — ensembles
        for the rows, request order preserved; ``trace`` is the wire
        trace context, ``fused`` the part's rows of the device fuse.
        RPC transports may REQUEUE rows to surviving shards when ``sid``
        is condemned mid-call.
      * ``local_core(seg, sid)`` — shard ``sid``'s in-process core, where
        the serving log reads AP50, or ``None``.
      * ``invalidate(imgs, pool)`` — drop cached artifacts on every shard,
        all regimes, and in ``pool``'s parent-side segment cores; returns
        entries dropped, each counted once.
      * ``snapshot()`` — shard-side metrics as one plain-dict snapshot
        (:func:`repro.obs.metrics.merge_snapshots`-compatible): what the
        parent's registry does NOT already hold.
      * ``condemned`` — shard ids permanently failed (never reused).

    The defaults are an RPC plane's: the segment is a picklable
    ``PoolSnapshot`` recipe (``None`` without a pool), nothing is
    launched, fused or tabled in the parent, and ensembles come from the
    core's ``eval_on``.  ``core`` stays public: the underlying sharded
    evaluation core, for surfaces the protocol deliberately does not
    wrap (tests, benches, ``precompute`` warm-up).
    """

    name = "?"

    def __init__(self, core):
        self.core = core

    # -- construction -----------------------------------------------------
    @classmethod
    def build(cls, *, env=None, pool=None, workers: int = 2,
              mp_context: str = "spawn",
              options: Optional[dict] = None) -> "ShardTransport":
        """Build the transport's core for a service: from ``pool``'s base
        traces when a scenario pool is attached, else from ``env.core``'s
        traces + config.  ``options`` carries transport-specific knobs
        (the socket plane's ``hosts``/health parameters)."""
        raise NotImplementedError

    # -- the service-facing protocol --------------------------------------
    @property
    def n_shards(self) -> int:
        return self.core.n_shards

    def route(self, img_idx: int) -> int:
        return self.core.shard_id(int(img_idx))

    def segment(self, pool, clock: int):
        # the worker/host pool persists across segments; the segment
        # rides along with each shard request as a recipe
        return None if pool is None else pool.snapshot_at(clock)

    def launch_iou(self, seg, imgs: Sequence[int]):
        return None

    def launch_fuse(self, seg, pending, imgs: Sequence[int], actions,
                    n_rows: int):
        return None

    def warm_fuse(self, n_rows: int) -> int:
        return 0

    def tables(self, seg, sid: int, imgs: Sequence[int],
               ious) -> Tuple[int, int]:
        return 0, 0

    def ensembles(self, seg, sid: int, imgs: Sequence[int],
                  masks: Sequence[int], trace,
                  fused=None) -> List[Detections]:
        return self.core.eval_on(sid, imgs, masks, seg, trace=trace)

    def local_core(self, seg, sid: int):
        return None

    def invalidate(self, img_indices: Sequence[int], pool) -> int:
        dropped = 0 if pool is None else pool.invalidate_images(img_indices)
        return dropped + self.core.invalidate_images(img_indices)

    def snapshot(self) -> dict:
        return self.core.metrics_snapshot()

    @property
    def condemned(self) -> List[int]:
        return []

    def bind_obs(self, metrics=None, tracer=None) -> None:
        """Attach the parent's registry/tracer to the plane (RPC latency
        histograms, condemned counters, shard-shipped spans)."""

    def close(self) -> None:
        self.core.close()


@register_transport("thread")
class ThreadTransport(ShardTransport):
    """In-process shards (``ShardedSubsetEvaluationCore``): zero IPC,
    ensembles serialize on the GIL.  A flush's segment is the pool's
    sharded core at its clock; on the kernel path the flush makes one IoU
    launch and, with WBF, one device fuse of its cold ensembles over it;
    the shard threads build tables from the launch's host matrices, take
    the fuse's rows into their own shard's memo and look the rest up
    there."""

    @classmethod
    def build(cls, *, env=None, pool=None, workers: int = 2,
              mp_context: str = "spawn",
              options: Optional[dict] = None) -> "ThreadTransport":
        from repro.federation.evaluation import \
            ShardedSubsetEvaluationCore
        if pool is not None:
            return cls(pool.sharded_core_at(0, workers))
        return cls(ShardedSubsetEvaluationCore.like(env.core, workers))

    def segment(self, pool, clock: int):
        """The pool's sharded core for this flush's segment (warm,
        memoized pool-side); updates ``self.core`` so routing follows the
        live segment.  The core itself without a pool."""
        if pool is not None:
            self.core = pool.sharded_core_at(clock, self.n_shards)
        return self.core

    def launch_iou(self, seg, imgs: Sequence[int]):
        return seg.launch_iou(imgs)

    def launch_fuse(self, seg, pending, imgs: Sequence[int], actions,
                    n_rows: int):
        return seg.launch_fuse(pending, imgs, actions, n_rows)

    def warm_fuse(self, n_rows: int) -> int:
        return self.core.warm_fuse(n_rows)

    def tables(self, seg, sid: int, imgs: Sequence[int],
               ious) -> Tuple[int, int]:
        return seg.shards[sid].precompute(imgs, ious)

    def ensembles(self, seg, sid: int, imgs: Sequence[int],
                  masks: Sequence[int], trace,
                  fused=None) -> List[Detections]:
        return seg.shards[sid].ensembles(imgs, masks, fused)

    def local_core(self, seg, sid: int):
        return seg.shards[sid]

    def invalidate(self, img_indices: Sequence[int], pool) -> int:
        if pool is not None:
            # the live sharded core is one of the pool's, swept there
            return pool.invalidate_images(img_indices)
        return self.core.invalidate_images(img_indices)

    def snapshot(self) -> dict:
        from repro.obs.metrics import counters_snapshot
        return counters_snapshot(self.core.stats, "core.")

    def close(self) -> None:    # thread shards hold no OS resources
        pass


@register_transport("process")
class ProcessTransport(ShardTransport):
    """W shard worker processes on this box behind batched pipe RPC
    (``ProcessShardedSubsetEvaluationCore``): ``img % W`` routing,
    condemn-never-reuse on worker death."""

    @classmethod
    def build(cls, *, env=None, pool=None, workers: int = 2,
              mp_context: str = "spawn",
              options: Optional[dict] = None) -> "ProcessTransport":
        from repro.serving.mp_shards import \
            ProcessShardedSubsetEvaluationCore
        if pool is not None:
            core = ProcessShardedSubsetEvaluationCore.for_pool(
                pool, workers, mp_context=mp_context)
        else:
            core = ProcessShardedSubsetEvaluationCore.like(
                env.core, workers, mp_context=mp_context)
        return cls(core)

    @property
    def condemned(self) -> List[int]:
        return [sid for sid, dead in enumerate(self.core._failed) if dead]

    def bind_obs(self, metrics=None, tracer=None) -> None:
        self.core.bind_obs(metrics, tracer)


@register_transport("socket")
class SocketTransport(ShardTransport):
    """H shard HOSTS over TCP (``SocketShardedSubsetEvaluationCore``):
    consistent-hash routing over healthy hosts, health-checked
    condemn + requeue.  ``options`` accepts ``hosts=[(addr, port), ...]``
    to join externally started ``repro.launch.shard_host`` servers
    (spawns ``workers`` local hosts otherwise) plus the socket core's
    health/timeout knobs (``health_interval_s``, ``op_timeout_s``,
    ``virtual_nodes``, ...)."""

    @classmethod
    def build(cls, *, env=None, pool=None, workers: int = 2,
              mp_context: str = "spawn",
              options: Optional[dict] = None) -> "SocketTransport":
        from repro.serving.socket_shards import \
            SocketShardedSubsetEvaluationCore
        opts = dict(options or {})
        hosts = opts.pop("hosts", None)
        if hosts is not None:
            opts["hosts"] = [(str(h), int(p)) for h, p in
                             (hp.rsplit(":", 1) if isinstance(hp, str)
                              else hp for hp in hosts)]
        else:
            opts["n_shards"] = workers
        opts.setdefault("mp_context", mp_context)
        if pool is not None:
            core = SocketShardedSubsetEvaluationCore.for_pool(
                pool, opts.pop("n_shards", workers), **opts)
        else:
            core = SocketShardedSubsetEvaluationCore.like(
                env.core, opts.pop("n_shards", workers), **opts)
        return cls(core)

    @property
    def condemned(self) -> List[int]:
        return self.core.condemned()

    def bind_obs(self, metrics=None, tracer=None) -> None:
        self.core.bind_obs(metrics, tracer)
