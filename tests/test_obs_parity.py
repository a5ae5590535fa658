"""Bit-parity matrix for the observability layer.

The design contract of ``repro.obs``: attaching metrics, tracing, and
the serving log changes NO served result and NO trained weight — obs
reads clocks and copies values, it never touches an rng, a cache key,
or an accounting quantity.  This suite runs the heavier halves of that
matrix: the process-shard backend under a scenario schedule, and the
full ``run_online`` driver.

Slow-marked wholesale: process shards spawn worker interpreters and the
driver parity case trains twice over a scenario horizon.
"""
import json
import os

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.sac import SAC, SACConfig
from repro.federation.providers import default_providers
from repro.launch.obs_report import load_run, render, serving_summary
from repro.obs import Obs, read_serving_log
from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                             build_scenario)
from repro.scenarios.online import run_online
from repro.serving.async_service import AsyncFederationService

pytestmark = pytest.mark.slow

PROVS = default_providers()


def _scenario_env(name="provider_outage", horizon=90, n_images=30):
    schedule = build_scenario(name, PROVS, horizon=horizon)
    pool = DynamicProviderPool(PROVS, schedule, n_images=n_images, seed=0)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    return pool, env


class Greedy:
    """Select every provider — exercises the widest ensembles."""

    def __init__(self, n):
        self.n = n

    def select_for_images(self, imgs, step=None):
        return np.ones((len(imgs), self.n), np.float32)


def _assert_same_results(ref, got):
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.action, b.action)
        assert a.cost_milli_usd == b.cost_milli_usd
        assert a.latency_ms == b.latency_ms
        np.testing.assert_array_equal(a.detections.boxes,
                                      b.detections.boxes)
        np.testing.assert_array_equal(a.detections.scores,
                                      b.detections.scores)


def test_process_backend_obs_parity_and_artifacts(tmp_path):
    pool, env = _scenario_env()
    agent = Greedy(env.n_providers)
    reqs = [int(i) for i in
            np.random.default_rng(0).integers(0, 30, 60)]

    with AsyncFederationService(env, agent, max_batch=1, workers=2,
                                pool=pool, transport="process") as s:
        bare = [s.handle(i) for i in reqs]

    d = str(tmp_path / "run")
    obs = Obs(d, trace_sample=1.0)
    obs.open_serving_log([p.name for p in PROVS], env.traces.gts)
    with AsyncFederationService(env, agent, max_batch=1, workers=2,
                                pool=pool, transport="process",
                                obs=obs) as s:
        inst = [s.handle(i) for i in reqs]
        extras = s.extra_metric_snapshots()
    snap = obs.write_metrics(extras)
    obs.close()

    _assert_same_results(bare, inst)

    # merged metrics: parent-side serving stats + per-shard RPC latency
    # histograms + worker-process op timings in ONE view
    assert snap["counters"]["serving.requests"] == 60.0
    assert any(k.startswith("serving.shard_rpc_ms.s")
               for k in snap["histograms"])
    assert any(k.startswith("worker.op_ms.") for k in snap["histograms"])
    assert any(k.startswith("core.") for k in snap["counters"])

    # serving log covers every request, with the regime recorded; the
    # per-regime summary is the OPE acceptance surface
    recs = read_serving_log(os.path.join(d, "serving_log.jsonl"))
    assert len(recs) == 60
    assert {r["backend"] for r in recs} == {"process"}
    summ = serving_summary(recs)
    assert set(summ) == {f"seg{k}" for k in
                         sorted({r["seg"] for r in recs})}
    assert len(summ) >= 2                       # the outage switched regimes
    assert sum(s["requests"] for s in summ.values()) == 60
    for s in summ.values():
        assert s["mean_ap50"] is not None and 0.0 <= s["mean_ap50"] <= 1.0
        assert s["cost_per_request"] > 0.0
        assert sum(s["flush_reasons"].values()) == s["requests"]

    # trace context crossed the worker pipe: worker_eval spans exist and
    # parent correctly onto shard_assemble spans of the same trace
    spans = load_run(d)["spans"]
    names = {sp["name"] for sp in spans}
    assert {"request", "flush", "shard_assemble", "worker_eval"} <= names
    by_span = {sp["span"]: sp for sp in spans}
    workers = [sp for sp in spans if sp["name"] == "worker_eval"]
    assert workers
    for w in workers:
        parent = by_span[w["parent"]]
        assert parent["name"] == "shard_assemble"
        assert parent["trace"] == w["trace"]
        assert "pid" in w["attrs"]

    # the rendered report stands on its own
    text = render(load_run(d))
    assert "worker_eval" in text and "seg0" in text


def test_run_online_obs_parity_and_event_stream(tmp_path):
    def _run(obs):
        pool, env = _scenario_env(horizon=240, n_images=24)
        agent = SAC(SACConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers, gamma=0.0,
                              hidden=(16, 16)))
        return run_online(agent, env, lanes=2, seed=0, log=None,
                          start_steps=40, explore_steps=20,
                          batch_size=32, update_iters=4, obs=obs)

    ref = _run(None)
    d = str(tmp_path / "run")
    obs = Obs(d)
    got = _run(obs)
    obs.write_metrics()
    obs.close()

    def _strip(x):
        if isinstance(x, dict):
            return {k: _strip(v) for k, v in x.items()
                    if "wall" not in k and "time" not in k}
        if isinstance(x, list):
            return [_strip(v) for v in x]
        return x

    assert _strip(ref["summary"]) == _strip(got["summary"])
    assert _strip(ref["segments"]) == _strip(got["segments"])

    # the event stream narrates the scenario: one close per segment,
    # switches in between, and a final summary
    events = [json.loads(ln) for ln in
              open(os.path.join(d, "events.jsonl")) if ln.strip()]
    kinds = [e["event"] for e in events]
    n_segs = len(got["segments"])
    assert kinds.count("segment_close") == n_segs
    assert kinds.count("regime_switch") == n_segs - 1
    assert kinds[-1] == "scenario_summary"
    for ev in events:
        if ev["event"] == "regime_switch":
            assert ev["buffer"] in ("flush", "fee_relabel", "fresh",
                                    "stash_restore")
            assert ev["to_seg"] == ev["from_seg"] + 1

    # training metrics landed in the registry
    snap = json.load(open(os.path.join(d, "metrics.json")))
    assert snap["counters"]["train.update_iters"] > 0
    assert snap["histograms"]["train.tick_ms"]["count"] > 0


def test_thread_backend_scenario_obs_parity(tmp_path):
    """Thread shards under the same scenario: results identical, and the
    serving log's segment column follows the pool clock."""
    pool, env = _scenario_env(horizon=60, n_images=20)
    agent = Greedy(env.n_providers)
    reqs = list(range(20)) * 2

    def _serve(obs):
        with AsyncFederationService(env, agent, max_batch=4, workers=2,
                                    pool=pool, obs=obs) as s:
            out = []
            for k, i in enumerate(reqs):
                s.set_clock(k)          # sweep the scenario clock
                out.append(s.handle(i))
            return out

    bare = _serve(None)
    d = str(tmp_path / "run")
    obs = Obs(d)
    obs.open_serving_log([p.name for p in PROVS], env.traces.gts)
    inst = _serve(obs)
    obs.close()
    _assert_same_results(bare, inst)
    recs = read_serving_log(os.path.join(d, "serving_log.jsonl"))
    assert len(recs) == len(reqs)
    assert {r["backend"] for r in recs} == {"thread"}
    # clock column is the flush clock; segment follows the schedule
    for r in recs:
        assert r["seg"] == pool.schedule.segment_index(r["clock"])


def test_stats_contract_unchanged_by_obs_registry():
    """The dict-shaped ``stats`` accessor and ``reset_stats`` behave
    identically whether backed by a private registry or an Obs one."""
    pool, env = _scenario_env(horizon=30, n_images=12)
    agent = Greedy(env.n_providers)

    def _stats(obs):
        with AsyncFederationService(env, agent, max_batch=4, workers=2,
                                    pool=pool, obs=obs) as s:
            for f in [s.submit(i % 12) for i in range(24)]:
                f.result()
            st = dict(s.stats)
            s.reset_stats()
            zeroed = dict(s.stats)
        return st, zeroed

    st_bare, z_bare = _stats(None)
    st_obs, z_obs = _stats(Obs(None))
    assert set(st_bare) == set(st_obs) == {
        "requests", "flushes", "batched_requests", "max_flush",
        "flush_full", "flush_timeout", "flush_drain", "iou_launches",
        "fused_on_device"}
    assert st_bare["requests"] == st_obs["requests"] == 24
    assert st_bare["batched_requests"] == st_obs["batched_requests"]
    assert all(v == 0 for v in z_bare.values())
    assert all(v == 0 for v in z_obs.values())
