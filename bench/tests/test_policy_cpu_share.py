"""The reader of ``policy.cpu_share``: the dispatcher decision's thread
CPU time over its wall time, from a synthetic registry snapshot."""
from __future__ import annotations

import pytest

from bench import common

NAME = "policy.cpu_share"


def reader():
    return common.load_module(common.BENCH / "metrics" / f"{NAME}.py")


def hist(total: float, count: int) -> dict:
    return {"buckets": [1.0], "counts": [0, count], "sum": total,
            "count": count, "min": None, "max": None}


def out(histograms: dict) -> dict:
    return {"obs": {"counters": {}, "gauges": {}, "histograms": histograms},
            "stats": {"requests": 40, "flushes": 8}}


def both() -> dict:
    return {"serving.policy_ms": hist(12.0, 8),
            "serving.policy_cpu_ms": hist(9.0, 8)}


def test_has_an_entry_in_the_benchmark():
    entry = {m["name"]: m for m in common.benchmark()["per_layer"]}[NAME]
    assert entry["moves"] == "serve_p95_ms"
    assert entry["layer"] == "policy forward"


def test_ratio_of_cpu_to_wall():
    assert reader().read(out(both()), {}) == 75.0


@pytest.mark.parametrize("drop,empty", [("serving.policy_cpu_ms", False),
                                        ("serving.policy_ms", False),
                                        ("serving.policy_cpu_ms", True),
                                        ("serving.policy_ms", True)])
def test_silent_without_both_histograms(drop, empty):
    hists = both()
    if empty:
        hists[drop] = hist(0.0, 0)
    else:
        del hists[drop]
    assert reader().read(out(hists), {}) is None


def test_silent_without_a_registry():
    assert reader().read({"stats": {}}, {}) is None
