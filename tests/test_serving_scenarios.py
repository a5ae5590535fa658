"""Scenario-driven serving SLOs on the process-backend plane: per-regime
accounting (outage bills 0 / costs the timeout), snapshot reuse across
revisited regimes, and the benchmark's per-segment aggregation.

Slow-marked: every test spawns worker processes (seconds each on the
spawn context); the nightly --full lane runs them.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.federation.providers import default_providers
from repro.serving.async_service import AsyncFederationService

pytestmark = pytest.mark.slow


class FixedAgent:
    def __init__(self, action):
        self.action = np.asarray(action, np.float32)

    def select_action(self, s, *, deterministic=False):
        s = np.asarray(s)
        if s.ndim == 2:
            return np.tile(self.action, (len(s), 1)), None
        return self.action.copy(), None


def _outage_setup(horizon=120, n_images=30):
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    providers = default_providers()
    schedule = build_scenario("provider_outage", providers, horizon=horizon)
    pool = DynamicProviderPool(providers, schedule, n_images=n_images,
                               seed=0)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    return pool, env


def test_outage_regime_bills_zero_and_charges_timeout():
    pool, env = _outage_setup()
    horizon = 120
    # find an outage segment and a provider that is down in it
    down_seg = down_j = None
    for seg in range(pool.schedule.segment_index(horizon - 1) + 1):
        view = pool.view_at(pool.schedule.segment_range(seg)[0])
        if not view.active.all():
            down_seg, down_j = seg, int(np.flatnonzero(~view.active)[0])
            break
    assert down_seg is not None, "provider_outage schedule has no outage"
    action = np.zeros(env.n_providers, np.float32)
    action[down_j] = 1.0                    # select ONLY the down provider
    start = pool.schedule.segment_range(down_seg)[0]
    with AsyncFederationService(env, FixedAgent(action), max_batch=1,
                                workers=2, pool=pool,
                                transport="process") as svc:
        svc.set_clock(int(start))
        res = svc.handle(3)
    assert res.cost_milli_usd == 0.0        # a down provider bills nothing
    # ... but a request that waited on it pays the outage timeout
    assert res.latency_ms == pytest.approx(
        svc._svc.transmission_ms + pool.outage_timeout_ms)
    assert len(res.detections) == 0         # and gets no detections back


def test_revisited_regime_rehits_installed_snapshot():
    pool, env = _outage_setup()
    with AsyncFederationService(env, FixedAgent([1, 1, 0]), max_batch=1,
                                workers=2, pool=pool,
                                transport="process") as svc:
        for i in range(120):                # walk outage AND recovery
            svc.handle(i % 30)
        installed = [set(s) for s in svc.core._installed]
        for i in range(30):                 # revisit: clock past horizon
            svc.handle(i)                   # clamps to the last segment
        assert [set(s) for s in svc.core._installed] == installed
        # recovery restores the pre-outage fingerprint: down segments and
        # up segments share at most 2 distinct detection keys
        assert all(len(s) <= 2 for s in installed)


def test_benchmark_segment_aggregation_matches_accounting():
    """The serving_scenarios benchmark attributes requests to segments by
    arrival index; with max_batch=1 the attribution is exact, and the
    per-segment cost means must reproduce the segment fee vectors."""
    pool, env = _outage_setup()
    sched = pool.schedule
    action = np.asarray([1, 1, 1], np.float32)
    with AsyncFederationService(env, FixedAgent(action), max_batch=1,
                                workers=2, pool=pool,
                                transport="process") as svc:
        results = [svc.handle(i % 30) for i in range(120)]
    segs = np.asarray([sched.segment_index(i) for i in range(120)])
    cost = np.asarray([r.cost_milli_usd for r in results])
    for s in sorted(set(segs.tolist())):
        view = pool.view_at(int(sched.segment_range(s)[0]))
        want = float(view.costs.sum())      # all three providers selected
        got = cost[segs == s]
        np.testing.assert_allclose(got, want, rtol=1e-6)
