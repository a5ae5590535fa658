"""The readers of the flush's device fuse, ``eval.fused_on_device_share``,
``dispatch.fuse_launch_ms_per_flush`` and ``eval.fuse_device_us_per_flush``,
on a synthetic registry snapshot, stats and trace."""
from __future__ import annotations

import pytest

from bench import common
from bench.tests.test_phase_metrics import hist, out, reader, snapshot, trace

FUSE_NAMES = ["eval.fused_on_device_share",
              "dispatch.fuse_launch_ms_per_flush",
              "eval.fuse_device_us_per_flush"]


def test_readers_have_entries_in_the_benchmark():
    entries = {m["name"]: m for m in common.benchmark()["per_layer"]}
    for name in FUSE_NAMES:
        assert entries[name]["workloads"] == ["tab2-cold-open",
                                              "tab3-cold-closed"]
        assert entries[name]["moves"] == "serve_p95_ms"


def fuse_out(**kw) -> dict:
    """Eight flushes of 40 requests, 38 fused on the device; six fuse
    launches of 0.4 ms; two executions of the fuse program in the
    window (300 and 100 us) and one past its end."""
    snap = snapshot()
    snap["histograms"]["serving.fuse_launch_ms"] = hist(2.4, 6)
    t = trace()
    t.modules = [("jit_fuse_flush(7)", 0.1, 0.1003),
                 ("jit__act(3)", 0.2, 0.21),
                 ("jit_fuse_flush(7)", 0.5, 0.5001),
                 ("jit_fuse_flush(7)", 1.5, 1.6)]
    o = out(obs=snap, trace=t, stats={"requests": 40, "flushes": 8,
                                      "iou_launches": 6,
                                      "fused_on_device": 38})
    o.update(kw)
    return o


def test_fuse_readers():
    o = fuse_out()
    assert reader("eval.fused_on_device_share").read(o, {}) == 95.0
    assert reader("dispatch.fuse_launch_ms_per_flush").read(o, {}) \
        == pytest.approx(0.3)
    assert reader("eval.fuse_device_us_per_flush").read(o, {}) \
        == pytest.approx(200.0)
    # the fused count is an always-on stat: it reads with obs off too
    assert reader("eval.fused_on_device_share").read(
        fuse_out(obs=None), {}) == 95.0


@pytest.mark.parametrize("name", FUSE_NAMES)
@pytest.mark.parametrize("case", ["no_requests", "parent", "never_ran"])
def test_fuse_readers_silent(name, case):
    """No request served; a program from before the device fuse (no
    ``fused_on_device`` stat, no ``fuse_launch`` phase, no ``fuse_flush``
    program in the trace); a fuse that never ran (the numpy path), whose
    phase histogram is empty and whose program is not in the trace."""
    if case == "no_requests":
        o = fuse_out(stats={"requests": 0, "flushes": 0,
                            "iou_launches": 0, "fused_on_device": 0})
        if name == "eval.fuse_device_us_per_flush":
            # a trace reading: the program's time needs no request count
            assert reader(name).read(o, {}) == pytest.approx(200.0)
            return
    elif case == "parent":
        o = out(stats={"requests": 40, "flushes": 8, "iou_launches": 6})
    else:
        snap = snapshot()
        snap["histograms"]["serving.fuse_launch_ms"] = hist(0.0, 0)
        o = out(obs=snap, stats={"requests": 40, "flushes": 8,
                                 "iou_launches": 0, "fused_on_device": 0})
        if name == "eval.fused_on_device_share":
            # fusing nothing is a reading, not silence
            assert reader(name).read(o, {}) == 0.0
            return
    assert reader(name).read(o, {}) is None


@pytest.mark.parametrize("name", ["dispatch.fuse_launch_ms_per_flush",
                                  "eval.fuse_device_us_per_flush"])
def test_fuse_readers_silent_without_obs_or_trace(name):
    assert reader(name).read(fuse_out(obs=None, trace=None), {}) is None
