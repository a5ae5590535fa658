"""The kernel path's IoU launches on a fixed set of padded shapes: the
mapping (``iou_launch_shape``), the padded tables against the numpy twin,
and the thread plane's counters of the entries launched and used."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from repro.ensemble.pipeline import batch_iou_matrices, iou_launch_shape


def _boxes(rng, n: int) -> np.ndarray:
    lo = rng.random((n, 2)).astype(np.float32)
    return np.concatenate(
        [lo, lo + rng.uniform(0.01, 0.5, (n, 2)).astype(np.float32)], 1)


@pytest.mark.parametrize("n_images", [1, 3, 16])
@pytest.mark.parametrize("n_boxes", [0, 1, 7, 8, 9, 47, 81, 96])
def test_padded_launch_matches_numpy_twin(n_boxes, n_images):
    """Image 0 carries the largest count, the rest fewer (zero too); every
    unpadded entry equals the numpy twin's to 2^-20 (interpret mode and
    numpy round the division differently)."""
    rng = np.random.default_rng(n_boxes * 31 + n_images)
    counts = [n_boxes] + [int(c) for c in
                          rng.integers(0, n_boxes + 1, n_images - 1)]
    boxes = [_boxes(rng, n) for n in counts]
    got = batch_iou_matrices(boxes, use_kernel=True)
    want = batch_iou_matrices(boxes, use_kernel=False)
    assert len(got) == n_images
    for g, w, n in zip(got, want, counts):
        assert g.shape == w.shape == (n, n)
        np.testing.assert_allclose(g, w, rtol=0, atol=2.0 ** -20)


@pytest.mark.parametrize("max_boxes,most", [(96, 64), (40, 32)])
def test_launch_shapes_are_a_small_monotone_cover(max_boxes, most):
    """Every flush a shard can see (1..16 images, 1..max_boxes boxes)
    maps into at most ``most`` shapes, none smaller than its input, and
    more images or boxes never map to a smaller launch."""
    grid = list(itertools.product(range(1, 17), range(1, max_boxes + 1)))
    shapes = {iou_launch_shape(b, m) for b, m in grid}
    assert len(shapes) <= most
    for b, m in grid:
        pb, pm = iou_launch_shape(b, m)
        assert pb >= b and pm >= m
        nb, nm = iou_launch_shape(min(b + 1, 16), min(m + 1, max_boxes))
        assert nb >= pb and nm >= pm
    # beyond one flush the image count still rounds up, never down
    for b in range(17, 200):
        pb = iou_launch_shape(b, 1)[0]
        assert pb >= b and pb >= iou_launch_shape(b - 1, 1)[0]


def test_thread_plane_counts_iou_entries_and_providers_selected():
    """Observability on, kernel path (interpret mode on the CPU): the
    entries launched cover those used, the used ones are the sum of
    n^2 over the tables built, and each request's subset size lands in
    ``serving.providers_selected``."""
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import scalability_providers
    from repro.federation.traces import generate_traces
    from repro.obs import Obs
    from repro.serving.async_service import AsyncFederationService

    tr = generate_traces(scalability_providers(), 24, seed=7)
    env = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(16, 16),
                          seed=3))
    reqs = [int(i) for i in np.random.default_rng(11).integers(0, 24, 40)]
    obs = Obs(None)
    try:
        with AsyncFederationService(env, agent, max_batch=8, workers=2,
                                    obs=obs) as svc:
            results = svc.handle_many(reqs)
            snap = svc.metrics_snapshot()
            tables = [t for s in svc.core.shards
                      for t in s._tables.values()]
    finally:
        obs.close()
    counters = snap["counters"]
    used = sum(len(t.boxes) ** 2 for t in tables)
    assert used > 0
    assert counters["serving.iou_entries_used"] == used
    assert counters["serving.iou_entries_launched"] >= used
    h = snap["histograms"]["serving.providers_selected"]
    assert h["count"] == len(reqs)
    assert h["sum"] == sum(int((np.asarray(r.action) > 0.5).sum())
                           for r in results)


def test_precompute_reports_one_launch_per_call():
    """A core's precompute returns its launch's padded entries and its
    tables' own; nothing on the numpy path or for cached images."""
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces

    tr = generate_traces(default_providers(), 6, seed=1)
    counts = [sum(len(d) for d in dets) for dets in tr.dets]
    kern = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=True).core
    b, m = iou_launch_shape(6, max(counts))
    assert kern.precompute(range(6)) == (b * m * m,
                                         sum(n * n for n in counts))
    assert kern.precompute(range(6)) == (0, 0)          # all cached
    host = ArmolEnv(tr, mode="gt", beta=0.0, seed=0, use_kernel=False).core
    assert host.precompute(range(6)) == (0, 0)
