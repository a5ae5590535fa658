"""Serving drivers behind one launch entry point.

Provider-side LM serving: batched prefill+decode on a reduced arch.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 8 --prompt-len 32 --new-tokens 16

Federation-side request serving: ``--federation`` routes a stream of
image requests through the Armol selector + provider fan-out + ensemble.
The default path is the synchronous ``FederationService``; ``--async``
switches to the micro-batching ``AsyncFederationService`` (``--workers``
cache shards / ensemble threads, flush at ``--max-batch`` requests or
``--max-wait-ms``, whichever comes first).

  PYTHONPATH=src python -m repro.launch.serve --federation --async \
      --requests 600 --workers 4 --max-batch 16 --max-wait-ms 2

``--transport {thread,process,socket}`` picks the evaluation plane;
``--transport socket`` with ``--hosts host:port,...`` joins externally
started ``repro.launch.shard_host`` servers — the multi-HOST path, see
``docs/serving.md``.

``--roster {tab2,tab3}`` picks the provider roster: the paper's Tab. II
three (AWS, Azure, Google; the default) or its Tab. III ten (those,
Alibaba and MLaaS 4-9), on every path, the scenarios included.

``--policy {rl,cascade,mct,hybrid}`` swaps the subset-selection policy
(the RL agent vs the ``repro.selection`` strategies; see
``docs/policies.md``); all four serve through the identical accounting
path, sync or async.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class FederationRun:
    """What one ``--federation`` run served: the env and policy it built,
    the request list, one result per request, and the measured window on
    the ``time.perf_counter`` clock (warm-up excluded)."""
    env: object
    agent: object
    requests: List[int]
    results: list
    t0: float
    t1: float
    stats: dict = field(default_factory=dict)
    shard_metrics: dict = field(default_factory=dict)


ROSTERS = {"tab2": "default_providers", "tab3": "scalability_providers"}


def roster_providers(name: str) -> list:
    """The provider profiles of roster ``name`` (``ROSTERS``)."""
    from repro.federation import providers
    return getattr(providers, ROSTERS[name])()


def run_federation(args) -> FederationRun:
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.traces import generate_traces
    from repro.obs.metrics import merge_snapshots
    from repro.serving.async_service import AsyncFederationService
    from repro.serving.federation_service import FederationService

    obs = None
    if args.obs_dir:
        from repro.obs import Obs
        obs = Obs(args.obs_dir, trace_sample=args.trace_sample,
                  seed=args.seed)
    pool = None
    if args.scenario:
        from repro.scenarios import (DynamicProviderPool,
                                     NonStationaryArmolEnv, build_scenario)
        providers = roster_providers(args.roster)
        schedule = build_scenario(args.scenario, providers,
                                  horizon=max(args.requests, 2),
                                  seed=args.seed)
        print(schedule.describe())
        pool = DynamicProviderPool(providers, schedule,
                                   n_images=args.images, seed=args.seed)
        env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                    observe_pool=False, seed=args.seed + 1)
    else:
        traces = generate_traces(roster_providers(args.roster),
                                 args.images, seed=args.seed)
        env = ArmolEnv(traces, mode="gt", beta=0.0, seed=args.seed + 1)
    if args.policy == "rl":
        agent = SAC(SACConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers, seed=args.seed))
    elif args.policy == "cascade":
        from repro.selection import CascadeSelector
        agent = CascadeSelector(env, beta=args.beta)
    elif args.policy == "mct":
        from repro.selection import MCTSelector
        agent = MCTSelector(env, budget=args.budget, seed=args.seed)
    else:   # hybrid: cascade gate fronting a (freshly initialized) SAC
        from repro.selection import HybridSelector
        rl = SAC(SACConfig(state_dim=env.state_dim,
                           n_providers=env.n_providers, seed=args.seed))
        agent = HybridSelector(env, rl, beta=args.beta)
    rng = np.random.default_rng(args.seed)
    reqs = [int(i) for i in rng.integers(0, args.images, args.requests)]
    transport = args.transport
    topts = None
    if args.hosts:
        if transport != "socket":
            raise SystemExit("--hosts requires --transport socket")
        topts = {"hosts": [hp.strip() for hp in args.hosts.split(",")
                           if hp.strip()]}
    mode = (f"async/{transport}" if args.use_async else "sync")
    print(f"[serve] federation ({mode}, policy={args.policy}, "
          f"roster={args.roster}): "
          f"{env.n_providers} providers, "
          f"{args.images} images, {args.requests} requests"
          + (f", scenario={args.scenario}" if args.scenario else ""))

    if args.use_async:
        with AsyncFederationService(
                env, agent, max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms, adaptive=args.adaptive,
                workers=args.workers, pool=pool, transport=transport,
                transport_options=topts, obs=obs) as svc:
            svc.handle_many(reqs[:args.max_batch])      # warm jit + shards
            svc.reset_stats()
            if pool is not None:
                svc.set_clock(0)    # warm-up must not consume the schedule
            if obs is not None:
                # open AFTER warm-up so the log covers only measured
                # traffic; gt mode scores each record's ensemble AP50
                obs.open_serving_log(
                    [p.name for p in env.traces.providers],
                    env.traces.gts if env.mode == "gt" else None)
            t0 = time.perf_counter()
            futures = [svc.submit(i) for i in reqs]
            results = [f.result() for f in futures]
            t1 = time.perf_counter()
            stats = dict(svc.stats)
            shard_metrics = merge_snapshots(*svc.extra_metric_snapshots())
            extra = (f" mean_flush={svc.mean_flush_size():.1f}"
                     f" flushes={svc.stats['flushes']}"
                     f" shards={svc.workers}")
            if pool is not None:
                extra += (f" segments="
                          f"{pool.schedule.segment_index(svc.clock) + 1}")
            if obs is not None:
                obs.write_metrics([shard_metrics])
    else:
        svc = FederationService(env, agent, obs=obs)
        svc.handle(reqs[0])                             # warm jit
        if obs is not None:
            obs.open_serving_log(
                [p.name for p in env.traces.providers],
                env.traces.gts if env.mode == "gt" else None)
        t0 = time.perf_counter()
        results = [svc.handle(i) for i in reqs]
        t1 = time.perf_counter()
        stats, shard_metrics = {}, {}
        extra = ""
        if obs is not None:
            obs.write_metrics()

    cost = sum(r.cost_milli_usd for r in results)
    lat = np.asarray([r.latency_ms for r in results])
    dt = t1 - t0
    print(f"[serve] {len(results)} requests in {dt:.2f}s "
          f"({len(results) / max(dt, 1e-9):.0f} req/s){extra}")
    print(f"[serve] accounted cost={cost:.1f} mUSD, modeled latency "
          f"p50={np.percentile(lat, 50):.0f}ms "
          f"p99={np.percentile(lat, 99):.0f}ms")
    if obs is not None:
        obs.close()
        print(f"[serve] observability artifacts in {args.obs_dir} "
              f"(render: python -m repro.launch.obs_report "
              f"{args.obs_dir})")
    return FederationRun(env, agent, reqs, results, t0, t1, stats,
                         shard_metrics)


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="",
                    help="LM architecture (required unless --federation)")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced)")
    ap.add_argument("--requests", type=int, default=None,
                    help="request count (default: 8 LM, 400 federation)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--federation", action="store_true",
                    help="serve federation requests instead of the LM")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="micro-batching AsyncFederationService")
    ap.add_argument("--workers", type=int, default=4,
                    help="async: cache shards / ensemble worker threads")
    ap.add_argument("--transport", default="thread",
                    choices=("thread", "process", "socket"),
                    help="async: the evaluation plane — in-process "
                         "threads (zero IPC, GIL-bound assembly), one "
                         "worker process per shard (parallel assembly), "
                         "or shard HOSTS over TCP (multi-host; spawns "
                         "--workers local hosts unless --hosts names "
                         "external ones).  Results are bit-identical "
                         "across all three")
    ap.add_argument("--hosts", default="",
                    help="async --transport socket: comma-separated "
                         "addr:port of externally started shard hosts "
                         "(python -m repro.launch.shard_host); empty = "
                         "spawn --workers hosts locally")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="async: flush when this many requests queue")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="async: flush when the oldest request is this old")
    ap.add_argument("--adaptive", action="store_true",
                    help="async: deadline-aware flush sizing from queue "
                         "depth (deeper queue -> flush sooner)")
    ap.add_argument("--images", type=int, default=120,
                    help="federation: trace-set size")
    ap.add_argument("--roster", default="tab2", choices=tuple(ROSTERS),
                    help="federation: the provider roster — the paper's "
                         "Tab. II three (AWS, Azure, Google) or its "
                         "Tab. III ten (those, Alibaba, MLaaS 4-9)")
    ap.add_argument("--policy", default="rl",
                    choices=("rl", "cascade", "mct", "hybrid"),
                    help="federation: subset-selection policy — the RL "
                         "agent, the calibrated cheap-first cascade, the "
                         "online budgeted MCT selector, or the cascade "
                         "gate fronting the RL agent (docs/policies.md)")
    ap.add_argument("--beta", type=float, default=-0.05,
                    help="cascade/hybrid: cost weight of the calibration "
                         "objective (ap50 + beta * fee)")
    ap.add_argument("--budget", type=float, default=2.0,
                    help="mct: per-request fee budget (mUSD)")
    ap.add_argument("--scenario", default="",
                    help="federation: serve through a non-stationary "
                         "provider scenario (one schedule step per "
                         "request; implies --async)")
    ap.add_argument("--obs-dir", default="",
                    help="federation: write observability artifacts "
                         "(metrics.json, serving_log.jsonl, trace.jsonl) "
                         "to this directory; results are bit-identical "
                         "with or without it")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="fraction of requests traced through the async "
                         "plane (0 = tracing off/free; needs --obs-dir)")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    args = _parser().parse_args(argv)
    if args.requests is None:
        args.requests = 400 if args.federation else 8
    if args.scenario and not args.use_async:
        # mid-stream pool swaps live in the async service's flush path
        args.use_async = True
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.federation:
        run_federation(args)
        return 0
    if not args.arch:
        _parser().error("--arch is required unless --federation is given")

    from repro.configs.base import get_arch
    from repro.serving.engine import Request, ServeEngine

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    engine = ServeEngine(cfg, max_len=args.max_len, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rng.integers(0, cfg.vocab_size,
                                 size=rng.integers(4, args.prompt_len + 1),
                                 dtype=np.int32),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature, rid=i)
            for i in range(args.requests)]
    t0 = time.time()
    outs = engine.serve(reqs, seed=args.seed)
    dt = time.time() - t0
    tok = sum(len(o.tokens) for o in outs)
    print(f"[serve] {cfg.name}: {len(reqs)} requests, {tok} tokens "
          f"in {dt:.2f}s ({tok / dt:.1f} tok/s)")
    for o in outs[:3]:
        print(f"  rid={o.rid} tokens={o.tokens[:8].tolist()}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
