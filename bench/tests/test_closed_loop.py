"""The closed-loop driver (``bench/drivers/serve_closed.py``) rehearsed on
the CPU at a few seconds, and the readers of the subset-evaluation
counters it reports (``eval.iou_pad_share``,
``eval.providers_per_request``), on synthetic registries."""
from __future__ import annotations

import pytest

from bench import common
from bench.tests.conftest import run_tiny, tiny

CELL = "tab3-cold-closed"


def reader(name: str):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


def test_rehearsal_is_correct_fresh_and_closed():
    c = tiny(CELL)
    callers = c["traffic"]["callers"]
    result, out = run_tiny(CELL, seconds=3.0, c=c)
    assert result["correct"], result["checks"]
    assert out["loop"] == "closed"
    warm, w = out["windows"]["warmup"], out["windows"]["window"]
    sent = warm.ids + w.ids
    assert len(sent) == len(set(sent)), "an id was sent twice"
    assert len(w.ids) == result["attempted"] > callers
    assert out["max_outstanding"] == callers
    # every request of the window was answered, and each caller's next
    # request left no earlier than its last answer
    assert out["failed"] == 0
    assert all(d >= s for s, d in zip(w.sent, w.done))
    # the generator's lag: none for the first sends, then each send's
    # distance from the answer that freed its caller
    late = out["late_ms"]
    assert len(late) == len(w.ids)
    assert not late[:callers].any() and (late >= 0).all()
    assert 1 <= out["providers_mean"] <= c["config"]["n_providers"]


def test_selector_is_the_configured_one_for_every_seed():
    """The served actor, which the check also reads, comes from the
    configuration's ``selector_seed``, not from the run's seed."""
    import jax
    import numpy as np
    from bench import weights
    from bench.drivers.serve import build
    from bench.drivers.serve_closed import fix_selector
    from bench.tests.conftest import SEED
    c = tiny(CELL)
    sel = c["config"]["selector_seed"]
    drawn = []
    for seed in (SEED, SEED + 1):
        b = build(c, seed, 8, trace=False)
        b.svc.close()
        drawn.append(b.actor)
        want = weights.make_mlps(sel, [b.actor_sizes])[0]
        fix_selector(b, sel)
        for got in (b.actor, b.agent.state.actor):
            jax.tree_util.tree_map(np.testing.assert_array_equal, got,
                                   want)
    # the seed alone would have drawn a different selector each run
    assert not np.array_equal(drawn[0][0]["w"], drawn[1][0]["w"])


def test_batched_decision_fault_is_not_correct(monkeypatch):
    """Full flushes decide through ``select_action_batch``, which
    ``bench/faults.py``'s ``decision_altered`` does not reach; the same
    flip planted there is caught by the cell's check."""
    import numpy as np
    from repro.core.sac import SAC
    orig = SAC.select_action_batch

    def flip(self, states, *, deterministic=False):
        a, proto = orig(self, states, deterministic=deterministic)
        a = np.array(a, copy=True)
        a[..., 0] = 1.0 - a[..., 0]
        a[..., 1] = 1.0
        return a, proto
    monkeypatch.setattr(SAC, "select_action_batch", flip)
    result, out = run_tiny(CELL)
    assert out["stats"]["batched_requests"] > 0
    assert not result["correct"], result["checks"]
    assert result["checks"]["mask_gap"]["value"] > 1e-4


def test_id_stream_is_sized_and_fails_loudly():
    from bench.drivers import serve_closed
    from bench.traffic import stream
    import numpy as np
    t = common.cell(CELL)["traffic"]
    n = serve_closed.ids_needed(t, 51)
    assert n >= t["id_cap_rate"] * (t["warmup_s"] + 51)
    s = stream.id_stream(t, 10, 5, np.random.default_rng(1))
    s.take(5)
    with pytest.raises(RuntimeError, match="exhausted"):
        s.take(1)


def test_max_outstanding_counts_an_answer_before_a_send_at_its_instant():
    from bench.drivers.serve import Window
    from bench.drivers.serve_closed import max_outstanding
    w = Window()
    w.sent, w.done = [0.0, 0.0, 1.0, 1.5], [1.0, 2.0, 3.0, float("nan")]
    assert max_outstanding(w) == 3


def snap(counters=None, histograms=None) -> dict:
    return {"obs": {"counters": counters or {}, "gauges": {},
                    "histograms": histograms or {}}}


def hist(total: float, count: int) -> dict:
    return {"buckets": [0.0, 1.0, 2.0], "counts": [0, 0, 0, count],
            "sum": total, "count": count, "min": None, "max": None}


def test_iou_pad_share_reads_one_minus_used_over_launched():
    o = snap({"serving.iou_entries_launched": 4096.0,
              "serving.iou_entries_used": 1024.0})
    assert reader("eval.iou_pad_share").read(o, {}) == 75.0


def test_providers_per_request_reads_the_mean_subset_size():
    o = snap(histograms={"serving.providers_selected": hist(36.0, 10)})
    assert reader("eval.providers_per_request").read(o, {}) == 3.6


@pytest.mark.parametrize("o", [
    {"obs": None},
    snap(),
    snap({"serving.iou_entries_launched": 0.0,
          "serving.iou_entries_used": 0.0},
         {"serving.providers_selected": hist(0.0, 0)}),
], ids=["obs_off", "no_counters", "zero"])
@pytest.mark.parametrize("name", ["eval.iou_pad_share",
                                  "eval.providers_per_request"])
def test_counter_readers_silent_without_counts(name, o):
    assert reader(name).read(o, {}) is None
