"""Process-backend serving shards: parity with the thread backend,
invalidation fan-out across process boundaries, mid-stream pool swaps,
and clean failure on worker crash.

The whole file is slow-marked: every test spawns (or reuses) worker
processes, which cost seconds each on the spawn context.  The nightly
--full lane runs them; tier-1 stays fast.
"""
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.sac import SAC, SACConfig
from repro.federation.env import ArmolEnv
from repro.federation.evaluation import SubsetEvaluationCore
from repro.federation.providers import default_providers
from repro.federation.traces import generate_traces
from repro.serving.async_service import AsyncFederationService
from repro.serving.federation_service import FederationService
from repro.serving.mp_shards import (ProcessShardedSubsetEvaluationCore,
                                     ShardWorkerError)

pytestmark = pytest.mark.slow

TR = generate_traces(default_providers(), 40, seed=5)
ENV = ArmolEnv(TR, mode="gt", beta=0.0, seed=0)
N = TR.n_providers


@pytest.fixture(scope="module")
def proc_core():
    """One spawned worker pool shared by the direct-core tests (workers
    cost seconds to spawn; the tests only need fresh CACHES, which
    ``invalidate_images`` provides)."""
    core = ProcessShardedSubsetEvaluationCore.like(ENV.core, 3)
    yield core
    core.close()


class FixedAgent:
    """Always selects the same subset (batched-aware, like the real ones)."""

    def __init__(self, action):
        self.action = np.asarray(action, np.float32)

    def select_action(self, s, *, deterministic=False):
        s = np.asarray(s)
        if s.ndim == 2:
            return np.tile(self.action, (len(s), 1)), None
        return self.action.copy(), None


def _assert_results_equal(got, ref):
    np.testing.assert_array_equal(got.action, ref.action)
    assert got.cost_milli_usd == ref.cost_milli_usd
    assert got.latency_ms == ref.latency_ms
    np.testing.assert_array_equal(got.detections.boxes, ref.detections.boxes)
    np.testing.assert_array_equal(got.detections.scores,
                                  ref.detections.scores)
    np.testing.assert_array_equal(got.detections.labels,
                                  ref.detections.labels)


# -- direct core parity ----------------------------------------------------

def test_workers_hold_no_jax_backend(proc_core):
    """Workers are host-only: they serve with the numpy IoU twin and
    never initialize a jax backend, so on a chip they cannot reach for
    the device the serving parent holds."""
    assert proc_core._cfg["use_kernel"] is False
    for img in range(6):
        proc_core.ensemble(img, (1 << N) - 1)
    gauges = proc_core.metrics_snapshot()["gauges"]
    assert gauges["worker.jax_backends_initialized"] == 0


def test_core_matches_unsharded_bit_for_bit(proc_core):
    ref = SubsetEvaluationCore(TR)
    rng = np.random.default_rng(0)
    for _ in range(25):
        img = int(rng.integers(0, len(TR)))
        mask = int(rng.integers(0, 1 << N))
        a = proc_core.ensemble(img, mask)
        b = ref.ensemble(img, mask)
        np.testing.assert_array_equal(a.boxes, b.boxes)
        np.testing.assert_array_equal(a.scores, b.scores)
        np.testing.assert_array_equal(a.labels, b.labels)
        if mask:
            assert proc_core.ap50(img, mask) == ref.ap50(img, mask)
        assert proc_core.cost(mask) == ref.cost(mask)


def test_eval_on_preserves_request_order(proc_core):
    imgs = [0, 3, 6, 9, 12]            # all home on shard 0 (W=3)
    masks = [7, 1, 5, 7, 2]
    got = proc_core.eval_on(0, imgs, masks)
    ref = SubsetEvaluationCore(TR)
    for d, img, m in zip(got, imgs, masks):
        np.testing.assert_array_equal(d.boxes, ref.ensemble(img, m).boxes)


def test_shard_partition_invariants(proc_core):
    proc_core.invalidate_images(range(len(TR)))
    imgs = [0, 1, 2, 3, 4, 5, 8, 9]
    proc_core.precompute(imgs)
    shard_imgs = proc_core.shard_images()
    flat = [i for s in shard_imgs for i in s]
    assert sorted(flat) == imgs                      # no dupes, no strays
    for sid, s_imgs in enumerate(shard_imgs):
        assert all(i % 3 == sid for i in s_imgs)
    assert proc_core.partition([0, 1, 2, 3, 4, 5, 8, 9]) == {
        0: [0, 3, 9], 1: [1, 4], 2: [2, 5, 8]}


def test_invalidate_fans_out_and_recompute_is_identical(proc_core):
    mask = (1 << N) - 1
    imgs = [0, 1, 2, 7, 8]
    proc_core.invalidate_images(range(len(TR)))      # known-clean slate
    ref = SubsetEvaluationCore(TR)
    before = {}
    for i in imgs:
        before[i] = proc_core.ap50(i, mask)
        assert before[i] == ref.ap50(i, mask)
    drop = imgs + [39]                  # 39 never cached on either side
    assert proc_core.invalidate_images(drop) == ref.invalidate_images(drop)
    for i in imgs:                      # loss-free: recompute == before
        assert proc_core.ap50(i, mask) == before[i]


def test_worker_crash_is_clean_error_not_hang(proc_core):
    """This test kills its own dedicated pool (the shared one must stay
    healthy for other tests)."""
    core = ProcessShardedSubsetEvaluationCore.like(ENV.core, 2)
    try:
        core.ensemble(0, 3)
        with core._locks[0]:
            core._conns[0].send((0, "crash"))     # test hook: os._exit(13)
        t0 = time.time()
        with pytest.raises(ShardWorkerError, match="shard 0"):
            core.ensemble(0, 5)                   # img 0 homes on shard 0
        assert time.time() - t0 < 30.0            # error, not a hang
        assert len(core.ensemble(1, 7)) >= 0      # shard 1 still serves
    finally:
        core.close()
    with pytest.raises(ShardWorkerError):
        core.ensemble(1, 1)                       # closed pool refuses


# -- async service: backend parity ----------------------------------------

def test_async_service_process_backend_matches_sync_reference():
    agent = SAC(SACConfig(state_dim=ENV.state_dim, n_providers=N,
                          hidden=(16, 16)))
    svc = FederationService(ENV, agent)
    imgs = [int(i) for i in
            np.random.default_rng(3).integers(0, len(TR), 40)]
    refs = [svc.handle(i) for i in imgs]
    with AsyncFederationService(ENV, agent, max_batch=8, workers=2,
                                transport="process") as asvc:
        got = asvc.handle_many(imgs)
        stats = dict(asvc.stats)
    for g, r in zip(got, refs):
        _assert_results_equal(g, r)
    assert stats["requests"] == len(imgs)
    assert stats["flush_full"] >= 1


def test_async_service_backends_bit_identical_under_concurrency():
    agent = FixedAgent([0, 1, 1])
    rng = np.random.default_rng(11)
    streams = [[int(i) for i in rng.integers(0, len(TR), 40)]
               for _ in range(3)]
    results = {}
    for backend in ("thread", "process"):
        collected = [None] * len(streams)
        with AsyncFederationService(ENV, agent, max_batch=8, workers=2,
                                    max_wait_ms=1.0,
                                    transport=backend) as asvc:
            def client(k):
                futs = [asvc.submit(i) for i in streams[k]]
                collected[k] = [f.result() for f in futs]

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(len(streams))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        results[backend] = collected
    for k in range(len(streams)):
        for a, b in zip(results["thread"][k], results["process"][k]):
            _assert_results_equal(a, b)


def test_process_backend_empty_selection_zero_cost():
    with AsyncFederationService(ENV, FixedAgent([0] * N), max_batch=4,
                                workers=2, transport="process") as asvc:
        res = asvc.handle(5)
    assert len(res.detections) == 0
    assert res.cost_milli_usd == 0.0 and res.latency_ms == 0.0


def test_async_service_worker_death_fails_requests_cleanly():
    with AsyncFederationService(ENV, FixedAgent([1, 0, 0]), max_batch=4,
                                workers=2, transport="process") as asvc:
        assert asvc.handle(0) is not None
        with asvc.core._locks[0]:
            asvc.core._conns[0].send((0, "crash"))
        with pytest.raises(ShardWorkerError):
            asvc.submit(0).result(timeout=60)     # img 0 -> dead shard 0
        # the other shard keeps serving
        assert asvc.submit(1).result(timeout=60).cost_milli_usd == \
            ENV.costs[0]


@pytest.mark.parametrize("transport", ["thread", "process"])
def test_account_shard_fault_reaches_every_plane(transport, monkeypatch):
    """Every plane assembles its shard parts in ``_account_shard``: the
    benchmark's half-the-flush fault, patched there, fails the second
    half of each shard's part on the thread and the process plane alike,
    and the first half still answers as the synchronous service."""
    orig = AsyncFederationService._account_shard

    def half(self, core, sid, items, actions, *a, **k):
        keep = (len(items) + 1) // 2
        for _, _, fut, _ in items[keep:]:
            fut.set_exception(RuntimeError("left out"))
        return orig(self, core, sid, items[:keep], actions[:keep], *a, **k)

    monkeypatch.setattr(AsyncFederationService, "_account_shard", half)
    agent = FixedAgent([1, 0, 1])
    imgs = list(range(8))
    refs = FederationService(ENV, agent).handle_many(imgs)
    # one full flush of eight; two shards of four, each keeping two
    with AsyncFederationService(ENV, agent, max_batch=8,
                                max_wait_ms=60_000.0, workers=2,
                                transport=transport) as asvc:
        futs = [asvc.submit(i) for i in imgs]
        errs = [f.exception(timeout=60) for f in futs]
        assert asvc.stats["flushes"] == 1
    assert [i for i, e in zip(imgs, errs) if e is not None] == [4, 5, 6, 7]
    assert all(str(e) == "left out" for e in errs[4:])
    for f, r in zip(futs[:4], refs[:4]):
        _assert_results_equal(f.result(), r)


def test_bad_backend_rejected():
    with pytest.raises(ValueError, match="unknown shard transport"):
        AsyncFederationService(ENV, FixedAgent([1, 0, 0]),
                               transport="greenlet")


def test_stale_reply_id_condemns_shard_never_misattributes():
    """Reply correlation is explicit on the wire: a reply whose request
    id does not match the in-flight request means the pipe is
    desynchronized (exactly the state a timed-out worker's late answer
    leaves behind) — the shard must be condemned, never have the stale
    rows attributed to the current request."""
    core = ProcessShardedSubsetEvaluationCore.like(ENV.core, 2)
    try:
        real_conn = core._conns[0]

        class StaleConn:
            """Answers every request with the PREVIOUS request's id —
            simulating replies arriving shuffled/shifted by one."""
            rid = 0

            def send(self, msg):
                self.rid = msg[0]

            def poll(self, timeout=0.0):
                return True

            def recv(self):
                return (self.rid - 1, "ok", [])

            def close(self):
                real_conn.close()

        core._conns[0] = StaleConn()
        with pytest.raises(ShardWorkerError, match="reply correlation"):
            core.ensemble(0, 3)               # img 0 homes on shard 0
        assert core._failed[0]
        # the survivor keeps serving correct rows
        ref = SubsetEvaluationCore(TR)
        got = core.ensemble(1, 3)
        np.testing.assert_array_equal(got.boxes, ref.ensemble(1, 3).boxes)
    finally:
        core.close()


# -- async service: mid-stream pool swap across the process boundary ------

def test_pool_swap_parity_thread_vs_process():
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    providers = default_providers()
    schedule = build_scenario("provider_outage", providers, horizon=90)
    pool = DynamicProviderPool(providers, schedule, n_images=30, seed=0)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(16, 16)))
    reqs = [int(i) for i in np.random.default_rng(0).integers(0, 30, 90)]
    outs = {}
    for backend in ("thread", "process"):
        # max_batch=1: the scenario clock advances one request per flush,
        # so both backends account request i under the SAME segment and
        # results must match bit for bit across every switch
        with AsyncFederationService(env, agent, max_batch=1, workers=2,
                                    pool=pool, transport=backend) as s:
            outs[backend] = [s.handle(i) for i in reqs]
            segs = pool.schedule.segment_index(s.clock - 1) + 1
    assert segs >= 2                        # the stream crossed a switch
    for a, b in zip(outs["thread"], outs["process"]):
        _assert_results_equal(a, b)


def test_service_invalidate_reaches_worker_and_pool_caches():
    """`AsyncFederationService.invalidate_images` is the one entry point
    that sweeps BOTH sides of a pool-backed process service: the worker
    processes' per-regime cores and the pool's parent-side segment
    cores — and results recompute identically afterwards."""
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    providers = default_providers()
    schedule = build_scenario("provider_outage", providers, horizon=60)
    pool = DynamicProviderPool(providers, schedule, n_images=20, seed=0)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    pool.core_at(0).ap50(3, 7)          # warm a parent-side segment core
    with AsyncFederationService(env, FixedAgent([1, 1, 1]), max_batch=1,
                                workers=2, pool=pool,
                                transport="process") as svc:
        before = svc.handle(3)
        assert svc.core.cache_sizes()["tables"] >= 1
        dropped = svc.invalidate_images([3])
        assert dropped >= 2             # worker core(s) + pool-side core
        assert 3 not in pool.core_at(0).cached_images()
        svc.set_clock(0)
        _assert_results_equal(svc.handle(3), before)


def test_snapshot_carries_regeneration_seed():
    """Regenerated segments must follow the SNAPSHOT's seed (the pool
    that authored it), not any worker-local default: a core built
    straight from base traces — without ``for_pool`` — still answers
    drifted segments bit-identically for a pool seeded != 0."""
    from repro.scenarios import DynamicProviderPool, build_scenario
    providers = default_providers()
    schedule = build_scenario("accuracy_drift", providers, horizon=100)
    pool = DynamicProviderPool(providers, schedule, n_images=12, seed=7)
    core = ProcessShardedSubsetEvaluationCore(
        pool.base_traces, n_shards=2, voting=pool.voting,
        ablation=pool.ablation)
    try:
        drifted = next(s for s in range(100) if pool.view_at(s).dets_key
                       != pool.view_at(0).dets_key)
        snap = pool.snapshot_at(drifted)
        ref = pool.core_at(drifted)
        for img in range(12):
            a = core.ensemble(img, 7, snapshot=snap)
            b = ref.ensemble(img, 7)
            np.testing.assert_array_equal(a.boxes, b.boxes)
            np.testing.assert_array_equal(a.scores, b.scores)
    finally:
        core.close()


def test_pool_snapshot_installs_once_per_worker_per_fingerprint():
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    providers = default_providers()
    schedule = build_scenario("price_war", providers, horizon=80)
    pool = DynamicProviderPool(providers, schedule, n_images=20, seed=0)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    with AsyncFederationService(env, FixedAgent([1, 1, 0]), max_batch=4,
                                workers=2, pool=pool,
                                transport="process") as svc:
        for i in range(80):
            svc.handle(i % 20)
        # price-war switches are economics-only: every segment shares ONE
        # detection fingerprint, so each worker installed at most one
        # segment core beyond the base — warm caches survive the regime
        # switches exactly like the thread backend's fingerprint keying
        installed = [len(s) for s in svc.core._installed]
        assert all(n <= 1 for n in installed)
