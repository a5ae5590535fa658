"""Phase timing of the served path and garbage-collection pauses.

With ``Obs`` on, the thread plane times each flush's decision
(``serving.policy_ms``), each request's queue wait up to the
dispatcher's pop, each shard part's wait for its thread, its IoU tables
and the thread CPU time of its assembly; every collection lands in
``runtime.gc_ms.gen<k>``.  Every phase and collection is also a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it on the
device's clock.  With ``Obs`` off none of it runs: no annotation, no gc
callback, and results are bit-identical either way.
"""
import gc
import glob
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.sac import SAC, SACConfig
from repro.federation.env import ArmolEnv
from repro.federation.providers import default_providers
from repro.federation.traces import generate_traces
from repro.obs import DEFAULT_MS_BUCKETS, MetricsRegistry, Obs
from repro.obs.metrics import Histogram
from repro.serving.async_service import AsyncFederationService

TR = generate_traces(default_providers(), 24, seed=7)
ENV = ArmolEnv(TR, mode="gt", beta=0.0, seed=0)
REQS = [int(i) for i in np.random.default_rng(11).integers(0, 24, 40)]
PHASES = {"flush", "policy", "shard_assemble", "iou_table", "fuse"}


def _sac():
    return SAC(SACConfig(state_dim=ENV.state_dim,
                         n_providers=ENV.n_providers, hidden=(16, 16),
                         seed=3))


class _Recording(Histogram):
    """A histogram that also keeps every value, in observation order."""

    __slots__ = ("values",)

    def __init__(self, lock, bounds):
        super().__init__(lock, bounds)
        self.values = []

    def observe(self, v):
        super().observe(v)
        self.values.append(float(v))

    def observe_batch(self, values):
        super().observe_batch(values)
        self.values.extend(float(v) for v in values)


class _RecordingRegistry(MetricsRegistry):
    def histogram(self, name, bounds=DEFAULT_MS_BUCKETS):
        with self._lock:
            if name not in self._histograms:
                self._histograms[name] = _Recording(threading.RLock(),
                                                    bounds)
        return super().histogram(name, bounds)


def _serve(agent, obs, reqs=REQS):
    """Serve ``reqs`` one submit at a time; (results, per-request
    latency in ms on the monotonic clock, service stats)."""
    lat = [None] * len(reqs)
    with AsyncFederationService(ENV, agent, max_batch=8, workers=2,
                                obs=obs) as svc:
        futs = []
        for k, img in enumerate(reqs):
            t0 = time.monotonic()
            fut = svc.submit(img)

            def _done(_f, k=k, t0=t0):
                lat[k] = (time.monotonic() - t0) * 1e3
            fut.add_done_callback(_done)
            futs.append(fut)
        results = [f.result(timeout=60) for f in futs]
    return results, lat, svc.stats


def test_phase_histograms_count_flushes_requests_and_shard_parts():
    obs = Obs(None, trace_sample=1.0)
    obs.metrics = _RecordingRegistry()
    try:
        _, lat, stats = _serve(_sac(), obs)
        hists = obs.metrics.snapshot()["histograms"]
        spans = obs.tracer.spans()
    finally:
        obs.close()
    parts = [s for s in spans if s["name"] == "shard_assemble"]
    assert stats["requests"] == len(REQS)
    assert hists["serving.policy_ms"]["count"] == stats["flushes"]
    assert hists["serving.queue_wait_ms"]["count"] == len(REQS)
    assert sum(s["attrs"]["n"] for s in parts) == len(REQS)
    for name in ("serving.shard_wait_ms", "serving.iou_table_ms",
                 "serving.assemble_cpu_ms"):
        assert hists[name]["count"] == len(parts), name
    # one submitting thread and a FIFO queue: the waits arrive in
    # submission order, and none outlasts its own request
    waits = obs.metrics.histogram("serving.queue_wait_ms").values
    assert len(waits) == len(REQS)
    assert all(0.0 <= w <= t for w, t in zip(waits, lat))
    # iou_table and fuse hang off their shard_assemble span
    by_span = {s["span"]: s for s in spans}
    kids = [s for s in spans if s["name"] in ("iou_table", "fuse")]
    assert len(kids) == 2 * len(parts)
    for s in kids:
        parent = by_span[s["parent"]]
        assert parent["name"] == "shard_assemble"
        assert parent["trace"] == s["trace"]
        assert s["dur_ms"] <= parent["dur_ms"]


def test_results_bit_identical_with_phases_on():
    agent = _sac()
    ref, _, _ = _serve(agent, None)
    obs = Obs(None, trace_sample=1.0)
    try:
        got, _, _ = _serve(agent, obs)
    finally:
        obs.close()
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.action, b.action)
        assert a.cost_milli_usd == b.cost_milli_usd
        assert a.latency_ms == b.latency_ms
        np.testing.assert_array_equal(a.detections.boxes,
                                      b.detections.boxes)
        np.testing.assert_array_equal(a.detections.scores,
                                      b.detections.scores)
        np.testing.assert_array_equal(a.detections.labels,
                                      b.detections.labels)


def test_gc_collect_adds_one_gen2_pause_and_close_restores_callbacks():
    gc.collect()
    before = list(gc.callbacks)
    obs = Obs(None)
    assert len(gc.callbacks) == len(before) + 1
    h = obs.metrics.histogram("runtime.gc_ms.gen2")
    n0 = h.count
    gc.collect()
    assert h.count == n0 + 1
    assert h.vmax >= 0.0
    obs.close()
    assert gc.callbacks == before
    gc.collect()
    assert h.count == n0 + 1


@pytest.mark.parametrize("cyclic", [False, True])
def test_dropped_unclosed_obs_stops_observing(tmp_path, cyclic):
    gc.collect()
    before = list(gc.callbacks)
    # with a trace writer the Obs sits in a reference cycle, so only a
    # collection frees it
    obs = Obs(str(tmp_path), trace_sample=1.0) if cyclic else Obs(None)
    h = obs.metrics.histogram("runtime.gc_ms.gen2")
    del obs
    gc.collect()
    n = h.count
    assert gc.callbacks == before
    gc.collect()
    assert h.count == n


class _CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return None


@pytest.mark.parametrize("off", ["none", "disabled"])
def test_obs_off_adds_no_callback_and_enters_no_annotation(monkeypatch,
                                                           off):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "entered", 0)
    gc.collect()
    before = list(gc.callbacks)
    obs = None if off == "none" else Obs(None, enabled=False)
    _serve(_sac(), obs, REQS[:12])
    gc.collect()
    assert gc.callbacks == before
    assert _CountingAnnotation.entered == 0
    # the same path with observability on enters the annotations
    on = Obs(None)
    try:
        _serve(_sac(), on, REQS[:12])
    finally:
        on.close()
    assert _CountingAnnotation.entered > 0


def test_phases_and_collections_on_the_profilers_clock(tmp_path):
    obs = Obs(None, trace_sample=1.0)
    agent = _sac()
    try:
        with AsyncFederationService(ENV, agent, max_batch=4, workers=2,
                                    obs=obs) as svc:
            svc.handle_many(list(range(8)))        # compiles outside
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                svc.handle_many(list(range(8, 20)))
                gc.collect()
            finally:
                jax.profiler.stop_trace()
    finally:
        obs.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert PHASES | {"gc.gen2"} <= names
