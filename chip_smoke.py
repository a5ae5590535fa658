#!/usr/bin/env python3
"""Chip smoke run: the federation's serving and training paths, once, on
one TPU, each checked against its plain reference.

    python3 chip_smoke.py

Phases run in order in this one process; the first that fails ends the
run with a non-zero exit and no result line:

  device   jax must see a TPU.  JAX_PLATFORMS is left alone and nothing
           falls back to the CPU.
  serve    ``repro.launch.serve``'s federation path on the thread plane
           (``--federation --async --transport thread --workers 4
           --max-batch 16 --images 400 --requests 512 --policy rl``, SAC
           at hidden (256, 256)): one batched policy forward per flush and
           the Pallas IoU kernel, against the same requests through the
           synchronous ``FederationService.handle_many`` with the numpy
           IoU twin.
  process  the same requests on the process plane (``--transport process
           --workers 2``): host-only workers, which must hold no jax
           backend and must answer exactly what the reference answers.
  train    SAC at hidden (256, 256), L=8 lanes, through
           ``run_off_policy`` with a ``DeviceReplayBuffer``, against the
           same run with the numpy ``ReplayBuffer`` (``index_mode="host"``):
           histories and buffers bit-identical, losses finite.

What "agree" means in the serve phase: selected masks and billed costs
are bit-identical; every IoU entry of the kernel is within ``IOU_ATOL`` of
the twin's; the corpus AP50 of the served requests is within
``AP50_ATOL``; and where no IoU entry differs at all, every fused output
must have the reference's count and labels, and boxes and scores within
``FUSE_ATOL`` (the thread plane fuses a flush's cold ensembles on the
device, whose float32 sums run in another order than the host's).  The
counts of differing IoU entries and of requests whose fused output
differs at all are printed either way.

Lines starting ``[smoke]`` are smoke-run readings, not benchmark
numbers.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

SERVE_ARGV = ["--federation", "--async", "--transport", "thread",
              "--workers", "4", "--max-batch", "16", "--images", "400",
              "--requests", "512", "--policy", "rl"]
PROCESS_OVERRIDES = ["--transport", "process", "--workers", "2"]
# paper-shaped off-policy schedule (Sec. IV-B) cut to two short epochs:
# 13 update blocks of 50 scanned SAC steps each
TRAIN_KW = dict(images=400, epochs=2, steps_per_epoch=400, batch_size=256,
                start_steps=100, update_after=200, update_every=50,
                update_iters=50)
IOU_ATOL = 1e-6
AP50_ATOL = 1e-6
FUSE_ATOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Counts backend compiles (jax's ``backend_compile_duration`` event,
    which also fires on a persistent-cache hit) with the time each one
    finished on the ``time.perf_counter`` clock."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events = []            # (finished at, seconds)

    def _listen(self, event, duration, **_kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def between(self, t0: float, t1: float):
        """(count, seconds) of the compiles that finished in [t0, t1]."""
        hits = [d for t, d in self.events if t0 <= t <= t1]
        return len(hits), sum(hits)


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def device_check() -> dict:
    """The device the run will use; refuses anything but a TPU."""
    import importlib.metadata

    import jax
    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    check(d.platform == "tpu",
          f"jax found no TPU (platform {d.platform!r}); this run does "
          "not fall back to the CPU")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _same_dets(a, b) -> bool:
    import numpy as np
    return (np.array_equal(a.boxes, b.boxes)
            and np.array_equal(a.scores, b.scores)
            and np.array_equal(a.labels, b.labels))


def _fused_gap(a, b) -> float:
    """Largest box or score difference of two fused outputs; infinite
    where their counts or labels differ."""
    import numpy as np
    if len(a) != len(b) or not np.array_equal(a.labels, b.labels):
        return math.inf
    return float(max(np.max(np.abs(a.boxes - b.boxes), initial=0.0),
                     np.max(np.abs(a.scores - b.scores), initial=0.0)))


def _corpus_ap50(results, reqs, traces) -> float:
    from repro.ensemble.metrics import ap50
    dts = {t: r.detections for t, r in enumerate(results)}
    gts = {t: traces.gts[i] for t, i in enumerate(reqs)}
    return ap50(dts, gts)


def _assert_same_routes(results, ref, plane: str) -> None:
    import numpy as np
    acts = np.stack([r.action for r in results])
    acts_ref = np.stack([r.action for r in ref])
    n_mask = int(np.sum(np.any(acts != acts_ref, axis=1)))
    costs = np.asarray([r.cost_milli_usd for r in results])
    costs_ref = np.asarray([r.cost_milli_usd for r in ref])
    n_cost = int(np.sum(costs != costs_ref))
    say(f"{plane}: requests whose mask differs from the reference: "
        f"{n_mask}; whose cost differs: {n_cost}")
    check(n_mask == 0, f"{plane}: {n_mask} selected masks differ")
    check(n_cost == 0, f"{plane}: {n_cost} billed costs differ")


def _serve(argv, compiles: CompileCounter):
    from repro.launch import serve
    run = serve.run_federation(serve.parse_args(argv))
    dt = run.t1 - run.t0
    n_win, _ = compiles.between(run.t0, run.t1)
    say(f"requests/s={len(run.results) / dt:.6g} ({len(run.results)} "
        f"requests in {dt:.6g}s, timed until every result is a host "
        f"array); compiles inside the window after warm-up: {n_win}; "
        f"flushes={run.stats.get('flushes')}")
    return run


def phase_serve(argv, compiles: CompileCounter, *, expect_kernel: bool):
    """Thread plane against sync ``handle_many`` with the numpy twin.
    Returns ``(run, reference results)``."""
    import copy

    import numpy as np
    from repro.federation.evaluation import SubsetEvaluationCore
    from repro.serving.federation_service import FederationService

    t_start = time.perf_counter()
    run = _serve(argv, compiles)
    env, traces = run.env, run.env.traces
    check(run.agent.cfg.hidden == (256, 256),
          f"SAC hidden {run.agent.cfg.hidden}, not the paper's (256, 256)")
    check(env.core.use_kernel == expect_kernel,
          f"use_kernel='auto' resolved to {env.core.use_kernel}, "
          f"expected {expect_kernel}")

    # the reference: same features and policy, numpy-twin core, sync path
    ref_env = copy.copy(env)
    ref_env.core = SubsetEvaluationCore(
        traces, **{**env.core.config(), "use_kernel": False})
    ref = FederationService(ref_env, run.agent).handle_many(run.requests)
    _assert_same_routes(run.results, ref, "serve")

    # IoU tables: the kernel's batched path (the one each flush takes)
    # against the numpy twin, image by image
    imgs = sorted(set(run.requests))
    kern = SubsetEvaluationCore(traces, **{**env.core.config(),
                                           "use_kernel": True})
    kern.precompute(imgs)
    n_iou = n_entries = 0
    max_diff = 0.0
    iou_diff_imgs = set()
    for i in imgs:
        got, want = kern.table(i).iou, ref_env.core.table(i).iou
        n_entries += want.size
        diff = got != want
        if diff.any():
            iou_diff_imgs.add(i)
            n_iou += int(diff.sum())
            max_diff = max(max_diff, float(np.max(np.abs(got - want))))
    n_fused = sum(not _same_dets(a.detections, b.detections)
                  for a, b in zip(run.results, ref))
    fuse_gap = max((_fused_gap(a.detections, b.detections)
                    for a, b in zip(run.results, ref)), default=0.0)
    ap_k = _corpus_ap50(run.results, run.requests, traces)
    ap_r = _corpus_ap50(ref, run.requests, traces)
    say(f"serve: IoU entries differing kernel vs numpy twin: {n_iou} of "
        f"{n_entries} over {len(imgs)} images (max |diff| {max_diff!r}); "
        f"requests whose fused output differs: {n_fused} of "
        f"{len(ref)} (max gap {fuse_gap!r}); corpus AP50 "
        f"kernel={ap_k!r} twin={ap_r!r}")
    check(max_diff <= IOU_ATOL,
          f"kernel IoU differs from the twin by {max_diff} > {IOU_ATOL}")
    check(abs(ap_k - ap_r) <= AP50_ATOL,
          f"corpus AP50 differs by {abs(ap_k - ap_r)} > {AP50_ATOL}")
    if n_iou == 0:
        check(fuse_gap <= FUSE_ATOL, f"IoU tables bit-identical but a "
              f"fused output differs by {fuse_gap} > {FUSE_ATOL}")
    n_c, s_c = compiles.between(t_start, time.perf_counter())
    say(f"serve: phase compiles={n_c} compile_s={s_c:.6g} "
        f"peak_bytes_in_use={peak_bytes()}")
    return run, ref


def phase_process(argv, serve_run, ref, compiles: CompileCounter):
    """The same requests on the process plane: host-only workers that
    answer exactly what the numpy reference answers."""
    run = _serve(argv, compiles)
    check(run.requests == serve_run.requests,
          "process: a different request list than the serve phase")
    _assert_same_routes(run.results, ref, "process")
    n_fused = sum(not _same_dets(a.detections, b.detections)
                  for a, b in zip(run.results, ref))
    gauges = run.shard_metrics.get("gauges", {})
    held = gauges.get("worker.jax_backends_initialized")
    say(f"process: requests whose fused output differs from the "
        f"reference: {n_fused}; workers holding a jax backend: {held}")
    check(n_fused == 0, f"process: {n_fused} fused outputs differ")
    check(held == 0, f"process: workers holding a jax backend: {held}")
    return run


def phase_train(compiles: CompileCounter, *, images, epochs,
                steps_per_epoch, lanes=8, **kw):
    """SAC with the device replay buffer against the numpy buffer."""
    import jax
    import numpy as np
    from repro.core.device_replay import DeviceReplayBuffer
    from repro.core.loops import run_off_policy
    from repro.core.replay_buffer import ReplayBuffer
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.evaluation import SubsetEvaluationCore
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces

    t_start = time.perf_counter()
    traces = generate_traces(default_providers(), images, seed=0)
    # one memoized core for both runs: its answers do not depend on
    # whether a table was cached, and the second run skips the rebuild
    core = SubsetEvaluationCore(traces)
    capacity = 10_000

    def train(make_buffer):
        env = ArmolEnv(traces, mode="gt", beta=-0.03, seed=3, core=core)
        agent = SAC(SACConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers, seed=0))
        buf = make_buffer(env)
        t0 = time.perf_counter()
        hist = run_off_policy(agent, env, lanes=lanes, epochs=epochs,
                              steps_per_epoch=steps_per_epoch, buffer=buf,
                              log=None, seed=5, **kw)
        jax.block_until_ready(agent.state)
        return agent, buf, hist, time.perf_counter() - t0

    a_h, b_h, h_h, dt_h = train(lambda env: ReplayBuffer(
        capacity, env.state_dim, env.n_providers, seed=5))
    a_d, b_d, h_d, dt_d = train(lambda env: DeviceReplayBuffer(
        capacity, env.state_dim, env.n_providers, seed=5,
        index_mode="host", feature_table=env.device_features()))
    check(a_d.cfg.hidden == (256, 256), "SAC not at hidden (256, 256)")
    steps = epochs * -(-steps_per_epoch // lanes) * lanes
    say(f"train: env steps/s numpy buffer (first run, cold)="
        f"{steps / dt_h:.6g}, device buffer (second run, memo warm)="
        f"{steps / dt_d:.6g}; {steps} env steps each, timed to "
        f"block_until_ready")

    strip = [[{k: v for k, v in h.items() if k != "wall_s"} for h in hh]
             for hh in (h_h, h_d)]
    check(strip[0] == strip[1],
          f"train: histories differ: {strip[0]} vs {strip[1]}")
    fields = ("state", "action", "reward", "next_state", "done")
    bad = [f for f in fields
           if not np.array_equal(getattr(b_h, f), getattr(b_d, f))]
    check(not bad and (b_h.ptr, b_h.size) == (b_d.ptr, b_d.size),
          f"train: replay buffers differ in {bad}")
    # one more block on each agent: the host-mode device buffer draws the
    # numpy buffer's index stream, so both see the same batches
    iters, batch = kw["update_iters"], kw["batch_size"]
    m_h = a_h.update_block(b_h.sample_block(iters, batch))
    m_d = a_d.update_block(b_d.sample_block(iters, batch))
    say(f"train: history {strip[1][-1]}; last-block losses {m_d}")
    check(all(math.isfinite(v) for v in m_d.values()),
          f"train: non-finite losses {m_d}")
    check(m_h == m_d, f"train: losses differ: {m_h} vs {m_d}")
    n_c, s_c = compiles.between(t_start, time.perf_counter())
    say(f"train: phase compiles={n_c} compile_s={s_c:.6g} "
        f"peak_bytes_in_use={peak_bytes()}")


def main() -> int:
    from repro.launch.compile_cache import configure_compile_cache
    say(f"compile cache: {configure_compile_cache()}")
    device = device_check()
    with CompileCounter() as compiles:
        run, ref = phase_serve(SERVE_ARGV, compiles, expect_kernel=True)
        phase_process(SERVE_ARGV + PROCESS_OVERRIDES, run, ref, compiles)
        phase_train(compiles, **TRAIN_KW)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
