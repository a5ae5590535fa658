"""The thread plane's one IoU launch per flush: the dispatcher launches
for the flush's untabled images across every shard, the shard threads
build their tables from its host arrays, and the tables and served
results equal those of the per-shard ``precompute`` path and of a
one-image ``table()``.  With WBF the dispatcher also launches one device
fuse over it, whose rows the shard threads take into their memo.  The
kernel runs in interpret mode on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.sac import SAC, SACConfig
from repro.ensemble import device_fuse, pipeline
from repro.federation.env import ArmolEnv
from repro.federation.evaluation import ShardedSubsetEvaluationCore
from repro.federation.providers import (default_providers,
                                        scalability_providers)
from repro.federation.traces import generate_traces
from repro.serving.async_service import AsyncFederationService
from repro.serving.federation_service import FederationService

W = 4
# eight distinct images over all four shards: one full flush
IMGS = [0, 1, 2, 3, 5, 6, 9, 14]


class FixedAgent:
    """Always selects the same subset (batched-aware)."""

    def __init__(self, action):
        self.action = np.asarray(action, np.float32)

    def select_action(self, s, *, deterministic=False):
        s = np.asarray(s)
        if s.ndim == 2:
            return np.tile(self.action, (len(s), 1)), None
        return self.action.copy(), None


class TableAgent:
    """A selector policy: each image's action from a table."""

    def __init__(self, actions):
        self.actions = np.asarray(actions, np.float32)

    def select_for_images(self, imgs):
        return self.actions[np.asarray(imgs, np.int64)]


@pytest.fixture
def fuses(monkeypatch):
    """Every device-fuse launch, by its request rows' image slots."""
    calls = []
    real = device_fuse.launch_fuse

    def spy(merged, iou, slots, bits, n_rows, **kw):
        calls.append(list(slots))
        return real(merged, iou, slots, bits, n_rows, **kw)

    monkeypatch.setattr(device_fuse, "launch_fuse", spy)
    return calls


@pytest.fixture
def launches(monkeypatch):
    """Every kernel launch of an IoU batch, by its image count."""
    calls = []
    real = pipeline.launch_self_iou

    def spy(boxes_list):
        calls.append(len(boxes_list))
        return real(boxes_list)

    monkeypatch.setattr(pipeline, "launch_self_iou", spy)
    return calls


def _service(env, agent, **kw):
    # a flush fires at eight requests, never on the timer
    return AsyncFederationService(env, agent, max_batch=8,
                                  max_wait_ms=60_000.0, workers=W, **kw)


def _assert_results_equal(got, ref):
    np.testing.assert_array_equal(got.action, ref.action)
    assert got.cost_milli_usd == ref.cost_milli_usd
    assert got.latency_ms == ref.latency_ms
    for k in ("boxes", "scores", "labels"):
        np.testing.assert_array_equal(getattr(got.detections, k),
                                      getattr(ref.detections, k))


def _assert_fused_equal(got, ref):
    """The device fuse against the host path: the same groups, boxes and
    scores to float32 summation order."""
    np.testing.assert_array_equal(got.action, ref.action)
    assert got.cost_milli_usd == ref.cost_milli_usd
    assert got.latency_ms == ref.latency_ms
    g, r = got.detections, ref.detections
    assert len(g) == len(r)
    np.testing.assert_array_equal(g.labels, r.labels)
    np.testing.assert_array_equal(g.providers, r.providers)
    np.testing.assert_allclose(g.boxes, r.boxes, rtol=0, atol=1e-6)
    np.testing.assert_allclose(g.scores, r.scores, rtol=0, atol=1e-6)


def _assert_tables_equal(core, ref, imgs):
    for i in imgs:
        got, want = core.shard_of(i)._tables[i], ref.shard_of(i)._tables[i]
        assert got.iou.dtype == want.iou.dtype == np.float32
        np.testing.assert_array_equal(got.iou, want.iou)
        np.testing.assert_array_equal(got.boxes, want.boxes)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_flush_launches_once_and_matches_the_per_shard_path(use_kernel,
                                                            launches):
    """Kernel path: a flush over four shards is one launch of all eight
    images, and its tables equal the per-shard ``precompute`` launches'
    bit for bit.  Numpy path: nothing is launched.  Either way the
    answers are the synchronous ``handle_many``'s, and a second flush of
    the same, now tabled, images launches nothing."""
    tr = generate_traces(default_providers(), 16, seed=4)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=use_kernel)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(16, 16),
                          seed=2))
    assert len({i % W for i in IMGS}) == W
    n0 = len(launches)
    with _service(env, agent) as svc:
        got = svc.handle_many(IMGS)
        first, n_first = dict(svc.stats), launches[n0:]
        again = svc.handle_many(IMGS)
        second, n_second = dict(svc.stats), launches[n0:]
        core = svc.core
    assert first["flushes"] == 1
    assert first["iou_launches"] == len(n_first) == int(use_kernel)
    if use_kernel:
        assert n_first == [len(IMGS)]
    # already tabled: the second flush launches nothing
    assert second["flushes"] == 2
    assert second["iou_launches"] == first["iou_launches"]
    assert n_second == n_first
    ref = ShardedSubsetEvaluationCore.like(env.core, W)
    ref.precompute(IMGS)
    _assert_tables_equal(core, ref, IMGS)
    sync = FederationService(env, agent).handle_many(IMGS)
    for a, b, r in zip(got, again, sync):
        _assert_results_equal(a, r)
        _assert_results_equal(b, r)


def test_launch_reads_the_flush_segment_core(launches):
    """With a scenario pool whose segments regenerate detections, each
    flush launches over its own segment's boxes: a flush of the same
    images in a new segment launches again, and every segment's tables
    equal the per-shard path's over that segment's traces."""
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    providers = default_providers()
    schedule = build_scenario("accuracy_drift", providers, horizon=120)
    pool = DynamicProviderPool(providers, schedule, n_images=16, seed=0,
                               use_kernel=True)
    env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                observe_pool=False, seed=1)
    starts = [pool.schedule.segment_range(s)[0]
              for s in range(pool.schedule.segment_index(119) + 1)]
    keys = {}
    for s in starts:
        keys.setdefault(pool.view_at(s).dets_key, s)
    steps = list(keys.values())
    assert len(steps) >= 2, "accuracy_drift never changes detections"
    n0 = len(launches)
    with _service(env, FixedAgent([1, 1, 1]), pool=pool) as svc:
        for step in steps:
            svc.set_clock(int(step))
            svc.handle_many(IMGS)
        stats = dict(svc.stats)
    assert stats["iou_launches"] == len(launches) - n0 == len(steps)
    differs = False
    for step in steps:
        core = pool.sharded_core_at(int(step), W)
        ref = ShardedSubsetEvaluationCore(pool.traces_at(int(step)),
                                          n_shards=W, use_kernel=True)
        ref.precompute(IMGS)
        _assert_tables_equal(core, ref, IMGS)
        base = pool.sharded_core_at(int(steps[0]), W)
        differs |= any(
            core.shard_of(i)._tables[i].iou.shape
            != base.shard_of(i)._tables[i].iou.shape
            or not np.array_equal(core.shard_of(i)._tables[i].iou,
                                  base.shard_of(i)._tables[i].iou)
            for i in IMGS)
    assert differs


@pytest.mark.parametrize("transport", ["process", "socket"])
def test_rpc_planes_make_no_dispatcher_launch(transport, launches, fuses):
    """The process and socket planes build their tables in their shard
    workers or hosts, on the numpy twin: the dispatcher launches nothing,
    IoU or fuse, even where the service's environment asks for the
    kernel."""
    tr = generate_traces(default_providers(), 16, seed=4)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    agent = FixedAgent([1, 0, 1])
    n0 = len(launches)
    with AsyncFederationService(env, agent, max_batch=8, workers=2,
                                transport=transport) as svc:
        got = svc.handle_many(IMGS)
        stats = dict(svc.stats)
        assert svc.fuse_shapes == 0
    assert stats["iou_launches"] == 0
    assert stats["fused_on_device"] == 0
    assert len(launches) == n0
    assert fuses == []
    host = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=False)
    for g, r in zip(got, FederationService(host, agent).handle_many(IMGS)):
        _assert_results_equal(g, r)


def test_one_image_table_is_one_padded_launch_equal_to_the_flush_table(
        launches, monkeypatch):
    """Kernel path: ``table()`` of one untabled image is one launch of
    that image, padded by ``iou_launch_shape`` like a flush's, and its
    IoU equals bit for bit the table a flush builds."""
    shapes = []
    spy = pipeline.launch_self_iou

    def shaped(boxes_list):
        result = spy(boxes_list)
        shapes.append(tuple(result.shape))
        return result

    monkeypatch.setattr(pipeline, "launch_self_iou", shaped)
    tr = generate_traces(default_providers(), 16, seed=4)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    with _service(env, FixedAgent([1, 1, 0])) as svc:
        svc.handle_many(IMGS)
        flushed = svc.core
    n0, s0 = len(launches), len(shapes)
    core = ShardedSubsetEvaluationCore.like(env.core, W).shards[0]
    for k, i in enumerate(IMGS):
        got = core.table(i)
        assert launches[n0:] == [1] * (k + 1)
        b, m = pipeline.iou_launch_shape(1, len(got.boxes))
        assert shapes[s0 + k] == (b, m, m)
        want = flushed.shard_of(i)._tables[i]
        assert got.iou.dtype == want.iou.dtype == np.float32
        np.testing.assert_array_equal(got.iou, want.iou)
    assert core.table(IMGS[0]) is core.table(IMGS[0])
    assert len(launches) == n0 + len(IMGS)


def test_in_flight_images_are_built_once_under_thread_churn():
    """Eight clients repeat a few images through small flushes with the
    interpreter switching threads every microsecond, so the dispatcher
    often launches for an image whose table a shard thread is still
    building.  Each table is built once, with the launch's bits, the
    entries counted used are those of the tables, and every answer is
    the synchronous service's."""
    import sys
    import threading

    from repro.obs import Obs

    tr = generate_traces(default_providers(), 12, seed=6)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    agent = FixedAgent([1, 1, 0])
    rng = np.random.default_rng(5)
    streams = [[int(i) for i in rng.integers(0, 12, 12)] for _ in range(8)]
    got = [None] * len(streams)
    obs = Obs(None)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with AsyncFederationService(env, agent, max_batch=4,
                                    max_wait_ms=0.5, workers=W,
                                    obs=obs) as svc:
            def client(k):
                futs = [svc.submit(i) for i in streams[k]]
                got[k] = [f.result(timeout=120) for f in futs]

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(len(streams))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads)
            stats = dict(svc.stats)
            counters = svc.metrics_snapshot()["counters"]
            core = svc.core
    finally:
        sys.setswitchinterval(old)
        obs.close()
    distinct = sorted({i for s in streams for i in s})
    assert core.stats["tables"] == len(distinct)
    assert 1 <= stats["iou_launches"] <= stats["flushes"]
    tables = [core.shard_of(i)._tables[i] for i in distinct]
    assert counters["serving.iou_entries_used"] == sum(
        len(t.boxes) ** 2 for t in tables)
    ref = ShardedSubsetEvaluationCore.like(env.core, W)
    for i in distinct:
        # a one-image launch per image: bits independent of grouping
        ref.precompute([i])
    _assert_tables_equal(core, ref, distinct)
    sync = FederationService(env, agent)
    for k, s in enumerate(streams):
        for g, r in zip(got[k], sync.handle_many(s)):
            _assert_results_equal(g, r)


def test_flush_fuses_its_cold_requests_on_the_device(launches, fuses):
    """Ten providers, kernel path, WBF: the flush's one IoU launch is
    followed by one device fuse over its rows.  Every request whose image
    the launch carried and whose mask is not empty takes its answer from
    it (one image was tabled before the flush, so it is fused on the
    host); the answers are the synchronous service's; the memo holds
    them, so a second lookup, or a second flush, is a hit."""
    tr = generate_traces(scalability_providers(), 16, seed=7,
                         mean_objects=7.36)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    rng = np.random.default_rng(2)
    actions = (rng.random((16, env.n_providers)) < 0.4).astype(np.float32)
    actions[IMGS[3]] = 0.0                      # an empty selection
    agent = TableAgent(actions)
    masks = {i: int(sum(1 << k for k in np.flatnonzero(actions[i])))
             for i in IMGS}
    tabled = IMGS[1]
    with _service(env, agent) as svc:
        assert svc.fuse_shapes > 0
        core = svc.core
        core.shard_of(tabled).precompute([tabled])
        n0 = len(launches)
        got = svc.handle_many(IMGS)
        first = dict(svc.stats)
        shard = core.shard_of(IMGS[0])
        hits = shard.stats["ens_hits"]
        memo = shard.ensemble(IMGS[0], masks[IMGS[0]])
        assert shard.stats["ens_hits"] == hits + 1
        assert memo is got[0].detections
        again = svc.handle_many(IMGS)
        second = dict(svc.stats)
    assert launches[n0:] == [len(IMGS) - 1]
    # one fuse: the tabled image has no slot, every other row has one
    assert len(fuses) == 1
    assert [s < 0 for s in fuses[0]] == [i == tabled for i in IMGS]
    cold = [i for i in IMGS if i != tabled and masks[i]]
    assert first["fused_on_device"] == len(cold) == len(IMGS) - 2
    # all tabled now: the second flush launches and fuses nothing
    assert second["fused_on_device"] == first["fused_on_device"]
    assert len(fuses) == 1 and second["iou_launches"] == 1
    sync = FederationService(env, agent).handle_many(IMGS)
    for i, a, b, r in zip(IMGS, got, again, sync):
        _assert_fused_equal(a, r)
        _assert_results_equal(b, a)
        if masks[i]:
            assert b.detections is a.detections


def test_nms_ablation_makes_no_fuse_launch(launches, fuses):
    """NMS (and Soft-NMS) stay on the host: the flush still launches its
    IoU, but nothing is fused on the device or warmed for it, and the
    answers are the synchronous service's bit for bit."""
    tr = generate_traces(default_providers(), 16, seed=4)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, ablation="nms",
                   use_kernel=True)
    agent = FixedAgent([1, 1, 1])
    n0 = len(launches)
    with _service(env, agent) as svc:
        assert svc.fuse_shapes == 0
        got = svc.handle_many(IMGS)
        stats = dict(svc.stats)
    assert launches[n0:] == [len(IMGS)]
    assert stats["fused_on_device"] == 0 and fuses == []
    for g, r in zip(got, FederationService(env, agent).handle_many(IMGS)):
        _assert_results_equal(g, r)


def test_fuse_warm_up_covers_every_flush_shape():
    """The service compiles the device fuse for every launch shape its
    flushes can reach, so serving compiles nothing more."""
    tr = generate_traces(default_providers(), 16, seed=4)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    counts = [sum(len(d) for d in dets) for dets in tr.dets]
    want = {pipeline.iou_launch_shape(b, m) for b in range(1, 9)
            for m in range(1, max(counts) + 1)}
    with _service(env, FixedAgent([1, 0, 1])) as svc:
        assert svc.fuse_shapes == len(want)
        programs = device_fuse.fuse_flush._cache_size()
        svc.handle_many(IMGS)
        svc.handle_many([i + 1 for i in IMGS])
        assert svc.stats["fused_on_device"] > 0
        assert device_fuse.fuse_flush._cache_size() == programs
