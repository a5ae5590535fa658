"""The readers of the dispatcher's one IoU launch a flush,
``eval.iou_launches_per_flush`` and ``dispatch.iou_ms_per_flush``, on a
synthetic registry snapshot and stats."""
from __future__ import annotations

import pytest

from bench import common
from bench.tests.test_phase_metrics import hist, out, reader, snapshot

NAMES = ["eval.iou_launches_per_flush", "dispatch.iou_ms_per_flush"]


def launch_out(**kw) -> dict:
    """Eight flushes, six of which launched; the dispatcher's
    ``iou_launch`` phase observed twice a launch (launch, readback)."""
    snap = snapshot()
    snap["histograms"]["serving.iou_launch_ms"] = hist(6.0, 12)
    o = out(obs=snap, stats={"requests": 40, "flushes": 8,
                             "iou_launches": 6})
    o.update(kw)
    return o


def test_readers_have_entries_in_the_benchmark():
    entries = {m["name"]: m for m in common.benchmark()["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == ["tab2-cold-open",
                                              "tab3-cold-closed"]
        assert entries[name]["moves"] == "serve_p95_ms"


def test_readers():
    o = launch_out()
    assert reader("eval.iou_launches_per_flush").read(o, {}) == 0.75
    assert reader("dispatch.iou_ms_per_flush").read(o, {}) == 0.75
    # the launch count is an always-on stat: it reads with obs off too
    assert reader("eval.iou_launches_per_flush").read(
        launch_out(obs=None), {}) == 0.75


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", ["no_flushes", "parent", "never_ran"])
def test_readers_silent(name, case):
    """No flush served; a program from before the one launch (no
    ``iou_launches`` stat, no ``iou_launch`` phase); a phase that never
    ran (the numpy path), so its histogram is empty."""
    if case == "no_flushes":
        o = launch_out(stats={"requests": 0, "flushes": 0,
                              "iou_launches": 0})
    elif case == "parent":
        o = out()
    else:
        snap = snapshot()
        snap["histograms"]["serving.iou_launch_ms"] = hist(0.0, 0)
        o = out(obs=snap, stats={"requests": 40, "flushes": 8,
                                 "iou_launches": 0})
        if name == "eval.iou_launches_per_flush":
            # launching nothing is a reading, not silence
            assert reader(name).read(o, {}) == 0.0
            return
    assert reader(name).read(o, {}) is None


def test_iou_ms_reader_silent_without_obs():
    assert reader("dispatch.iou_ms_per_flush").read(
        launch_out(obs=None), {}) is None
