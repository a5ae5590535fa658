"""``launch/serve.py --federation --roster``: the Tab. III ten-provider
roster on the launcher's paths, sync and async on the thread plane,
against the plain ``FederationService.handle`` path, and through every
built-in scenario."""
from __future__ import annotations

import numpy as np
import pytest

from repro.launch.serve import parse_args, run_federation
from repro.scenarios.schedule import BUILTIN_SCENARIOS
from repro.serving.federation_service import FederationService

TAB3 = ["aws", "azure", "google", "alibaba"] + \
    [f"mlaas{i}" for i in range(4, 10)]


@pytest.mark.parametrize("mode", [[], ["--async", "--transport", "thread",
                                       "--workers", "2"]],
                         ids=["sync", "async"])
def test_tab3_roster_serves_like_the_plain_path(mode):
    run = run_federation(parse_args(
        ["--federation", "--roster", "tab3", "--images", "40",
         "--requests", "64", "--seed", "5"] + mode))
    assert [p.name for p in run.env.traces.providers] == TAB3
    assert len(run.results) == 64
    plain = FederationService(run.env, run.agent)
    for img, got in zip(run.requests, run.results):
        want = plain.handle(img)
        np.testing.assert_array_equal(got.action > 0.5, want.action > 0.5)
        assert got.cost_milli_usd == want.cost_milli_usd
        assert got.latency_ms == want.latency_ms
        np.testing.assert_array_equal(got.detections.boxes,
                                      want.detections.boxes)
        np.testing.assert_array_equal(got.detections.scores,
                                      want.detections.scores)
        np.testing.assert_array_equal(got.detections.labels,
                                      want.detections.labels)


def test_default_roster_is_tab2():
    run = run_federation(parse_args(
        ["--federation", "--images", "12", "--requests", "8"]))
    assert [p.name for p in run.env.traces.providers] == TAB3[:3]


@pytest.mark.parametrize("scenario", sorted(BUILTIN_SCENARIOS) + ["random:3"])
def test_every_scenario_serves_the_ten(scenario):
    """A scenario takes the whole roster (arrivals add slots after it);
    none serves the three in its place."""
    run = run_federation(parse_args(
        ["--federation", "--roster", "tab3", "--scenario", scenario,
         "--images", "16", "--requests", "24", "--workers", "2",
         "--max-batch", "4"]))
    names = [p.name for p in run.env.traces.providers]
    assert names[:10] == TAB3
    assert all(len(r.action) == len(names) for r in run.results)
