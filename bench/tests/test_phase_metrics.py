"""The readers of the served path's phase and garbage-collection metrics,
on a synthetic registry snapshot and a hand-built trace."""
from __future__ import annotations

import pytest

from bench import common
from bench import trace as tr

PHASE_METRICS = ["policy.host_ms_per_flush", "eval.shard_wait_ms",
                 "eval.table_ms_per_request", "eval.assemble_cpu_share",
                 "host.gc_max_ms", "host.gc_share_of_long_gaps"]


def reader(name: str):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


def hist(total: float, count: int) -> dict:
    return {"buckets": [1.0], "counts": [0, count], "sum": total,
            "count": count, "min": None, "max": None}


def snapshot() -> dict:
    return {"counters": {}, "gauges": {}, "histograms": {
        "serving.policy_ms": hist(12.0, 8),
        "serving.shard_wait_ms": hist(30.0, 20),
        "serving.iou_table_ms": hist(16.0, 20),
        "serving.assemble_cpu_ms": hist(45.0, 20),
        "runtime.gc_ms.gen0": hist(0.0, 0),
        "runtime.gc_ms.gen2": hist(93.0, 1)}}


def trace() -> tr.Trace:
    """Window [0, 1] s: device busy [0, 0.1], [0.2, 0.22], [0.5, 0.9];
    gaps (0.1, 0.2), (0.22, 0.5), (0.9, 1.0), all 50 ms or more."""
    t = tr.Trace()
    t.n_devices = 1
    t.window = (0.0, 1.0)
    t.ops = [("fusion.1", 0.0, 0.1), ("fusion.2", 0.2, 0.22),
             ("copy.1", 0.5, 0.9)]
    t.host = [("python3", "flush", 0.05, 0.3),
              ("python3", "gc.gen2", 0.25, 0.35),       # 0.1 s in a gap
              ("fed-shard-0", "gc.gen0", 0.30, 0.32),   # inside the above
              ("fed-shard-1", "gc.gen1", 0.6, 0.62),    # device busy
              ("fed-shard-1", "gc.gen0", 0.95, 0.97),   # 0.02 s in a gap
              ("python3", "gc.gen2", 1.2, 1.9)]         # outside the window
    return t


def out(**kw) -> dict:
    o = {"obs": snapshot(), "stats": {"requests": 40, "flushes": 8},
         "spans": {"shard_assemble": (60.0, 20)}, "trace": trace()}
    o.update(kw)
    return o


def test_every_reader_has_an_entry_in_the_benchmark():
    names = {m["name"] for m in common.benchmark()["per_layer"]}
    assert set(PHASE_METRICS) <= names


def test_registry_readers():
    o = out()
    assert reader("policy.host_ms_per_flush").read(o, {}) == 1.5
    assert reader("eval.shard_wait_ms").read(o, {}) == 1.5
    assert reader("eval.table_ms_per_request").read(o, {}) == 0.4
    assert reader("eval.assemble_cpu_share").read(o, {}) == 75.0


def test_gc_max_ignores_collections_outside_the_window():
    # the 0.7 s collection past the window's end is not counted
    assert reader("host.gc_max_ms").read(out(), {}) == pytest.approx(100.0)


def test_gc_share_of_long_gaps():
    # long gaps total 0.1 + 0.28 + 0.1 = 0.48 s; collections cover
    # none of (0.1, 0.2), 0.1 s of (0.22, 0.5) (the nested gen0 counted
    # once) and 0.02 s of (0.9, 1.0)
    share = reader("host.gc_share_of_long_gaps").read(out(), {})
    assert share == pytest.approx(100.0 * 0.12 / 0.48)


def test_gc_share_is_zero_without_a_long_gap():
    t = trace()
    t.ops = [("fusion.1", 0.0, 0.49), ("fusion.2", 0.5, 0.96),
             ("copy.1", 0.99, 1.0)]
    assert reader("host.gc_share_of_long_gaps").read(out(trace=t), {}) \
        == 0.0


def test_gc_max_is_zero_without_a_collection_in_the_window():
    t = trace()
    t.host = [h for h in t.host if not h[1].startswith("gc.")]
    assert reader("host.gc_max_ms").read(out(trace=t), {}) == 0.0


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_silent_without_obs(name):
    assert reader(name).read(out(obs=None), {}) is None


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_silent_on_a_program_without_the_phases(name):
    """A registry from before the phases (queue wait only) and a trace
    with no program annotation: each reader finds nothing to read."""
    snap = {"counters": {}, "gauges": {}, "histograms": {
        "serving.queue_wait_ms": hist(88.0, 40)}}
    t = trace()
    t.host = [("python3", "PjitFunction(_act)", 0.1, 0.2)]
    assert reader(name).read(out(obs=snap, spans={}, trace=t), {}) is None


@pytest.mark.parametrize("name", ["host.gc_max_ms",
                                  "host.gc_share_of_long_gaps"])
def test_trace_readers_silent_without_a_trace(name):
    assert reader(name).read(out(trace=None), {}) is None
