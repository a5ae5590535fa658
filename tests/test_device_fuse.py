"""The device fuse (``repro.ensemble.device_fuse``) against the host
path, ``ensemble_from_arrays``, on the same float32 IoU entries: a plain
JAX program on the CPU.  Counts, labels and providers must be identical;
boxes and scores may differ by float32 summation order only."""
from __future__ import annotations

import numpy as np
import pytest

from repro.ensemble import device_fuse
from repro.ensemble.boxes import Detections, iou_matrix
from repro.ensemble.pipeline import (ensemble_from_arrays,
                                     iou_launch_shape,
                                     merge_provider_detections)
from repro.federation.providers import (default_providers,
                                        scalability_providers)
from repro.federation.traces import generate_traces

ATOL = 1e-6
VOTINGS = ("affirmative", "consensus", "unanimous")
ROSTERS = {"tab2": default_providers, "tab3": scalability_providers}


def host_answer(merged, bits, voting, iou_thr=0.5):
    """``SubsetEvaluationCore.ensemble``'s answer for one (image, subset):
    the selected rows, their position within the subset, the slice of
    the image's IoU matrix."""
    boxes, scores, labels, provs = merged
    iou = iou_matrix(boxes, boxes)
    sel = np.flatnonzero(bits)
    idx = np.flatnonzero(bits[provs])
    position = np.cumsum(bits) - bits
    return ensemble_from_arrays(
        boxes[idx], scores[idx], labels[idx],
        position[provs[idx]].astype(np.int32), len(sel), voting=voting,
        iou_thr=iou_thr, iou=iou[idx[:, None], idx])


def device_answers(images, slots, bits, n_rows, voting, iou_thr=0.5):
    """Each request row's fused detections from one program run over
    ``images`` (per-provider detection lists) padded as a flush's IoU
    launch is."""
    import jax.numpy as jnp
    merged = [merge_provider_detections(d) for d in images]
    b, p = iou_launch_shape(len(merged), max(len(m[1]) for m in merged))
    iou = np.zeros((b, p, p), np.float32)
    for k, m in enumerate(merged):
        n = len(m[1])
        iou[k, :n, :n] = iou_matrix(m[0], m[0])
    fused, count = device_fuse.fuse(
        device_fuse.pack_table(merged, b, p), jnp.asarray(iou),
        device_fuse.pack_rows(np.asarray(slots), np.asarray(bits), n_rows),
        voting=voting, iou_thr=iou_thr)
    fused, count = np.asarray(fused), np.asarray(count)
    assert fused.shape == (n_rows, p, device_fuse.TABLE_COLS)
    return merged, [fused[r, :count[r]] for r in range(n_rows)]


def assert_same(got, want: Detections):
    assert len(got) == len(want)
    np.testing.assert_array_equal(got[:, 5].astype(np.int32), want.labels)
    np.testing.assert_array_equal(got[:, 6].astype(np.int32),
                                  want.providers)
    np.testing.assert_allclose(got[:, :4], want.boxes, rtol=0, atol=ATOL)
    np.testing.assert_allclose(got[:, 4], want.scores, rtol=0, atol=ATOL)


def check_rows(images, slots, bits, voting, n_rows=None, iou_thr=0.5):
    n_rows = len(slots) if n_rows is None else n_rows
    merged, got = device_answers(images, slots, bits, n_rows, voting,
                                 iou_thr)
    for r, (s, b) in enumerate(zip(slots, bits)):
        if s < 0 or not b.any():
            assert len(got[r]) == 0
            continue
        assert_same(got[r], host_answer(merged[s], b, voting, iou_thr))
    for r in range(len(slots), n_rows):       # padding rows
        assert len(got[r]) == 0


@pytest.mark.parametrize("voting", VOTINGS)
@pytest.mark.parametrize("roster", sorted(ROSTERS))
def test_random_masks_match_the_host_path(roster, voting):
    tr = generate_traces(ROSTERS[roster](), 6, seed=11, mean_objects=7.36)
    n = tr.n_providers
    rng = np.random.default_rng(3)
    slots = rng.integers(0, 6, 16)
    bits = rng.random((16, n)) < 0.5
    bits[rng.random(16) < 0.2] = True           # some full subsets
    check_rows(tr.dets, slots, bits, voting)


@pytest.mark.parametrize("voting", VOTINGS)
def test_one_provider_subsets_are_not_rescaled(voting):
    tr = generate_traces(scalability_providers(), 3, seed=5,
                         mean_objects=7.36)
    n = tr.n_providers
    slots = [k % 3 for k in range(n)]
    bits = np.eye(n, dtype=bool)
    check_rows(tr.dets, slots, bits, voting)


@pytest.mark.parametrize("voting", VOTINGS)
def test_a_selected_provider_with_no_box(voting):
    """The empty provider still counts among the selected, so it moves
    voting and the WBF rescale."""
    tr = generate_traces(default_providers(), 4, seed=2, mean_objects=4.0)
    images = [list(d) for d in tr.dets]
    images[1][2] = Detections.empty()
    images[3] = [Detections.empty()] * 3        # an image with no box
    slots = [1, 1, 1, 3, 0]
    bits = np.asarray([[1, 1, 1], [0, 1, 1], [0, 0, 1], [1, 1, 1],
                       [1, 0, 1]], bool)
    check_rows(images, slots, bits, voting)


@pytest.mark.parametrize("voting", VOTINGS)
def test_tied_scores_keep_the_stable_order(voting):
    """Every score equal: the visit order is table order, so which box
    represents a group (and its label and provider) follows it."""
    tr = generate_traces(scalability_providers(), 2, seed=8,
                         mean_objects=7.36)
    images = [[Detections(d.boxes, np.full(len(d), 0.5, np.float32),
                          d.labels) for d in dets] for dets in tr.dets]
    rng = np.random.default_rng(1)
    slots = rng.integers(0, 2, 8)
    bits = rng.random((8, tr.n_providers)) < 0.6
    check_rows(images, slots, bits, voting)


def test_an_iou_of_exactly_the_threshold_does_not_group():
    a = Detections(np.asarray([[0.0, 0.0, 0.5, 0.5]], np.float32),
                   np.asarray([0.9], np.float32), np.asarray([4], np.int32))
    b = Detections(np.asarray([[0.0, 0.0, 0.5, 0.25],
                               [0.0, 0.0, 0.5, 0.45]], np.float32),
                   np.asarray([0.8, 0.7], np.float32),
                   np.asarray([4, 4], np.int32))
    iou = iou_matrix(np.concatenate([a.boxes, b.boxes]),
                     np.concatenate([a.boxes, b.boxes]))
    assert iou[0, 1] == np.float32(0.5) and iou[0, 2] > 0.5
    _, got = device_answers([[a, b]], [0], np.ones((1, 2), bool), 1,
                            "affirmative")
    assert len(got[0]) == 2     # box 1 alone, boxes 0 and 2 together
    check_rows([[a, b]], [0], np.ones((1, 2), bool), "affirmative")


@pytest.mark.parametrize("thr", [0.3, 0.5, 0.7])
def test_thresholds_the_float32_entries_cannot_hold(thr):
    """A threshold float32 cannot hold splits the entries as the host's
    comparison with the Python float does."""
    t = device_fuse.f32_threshold(thr)
    xs = np.nextafter(np.float32(thr), np.float32([-1, 1, -1, 1]))
    xs = np.concatenate([xs, [np.float32(thr), np.float32(t)]])
    assert all((float(x) > thr) == (x > np.float32(t)) for x in xs)
    tr = generate_traces(default_providers(), 3, seed=9, mean_objects=7.36)
    rng = np.random.default_rng(4)
    check_rows(tr.dets, rng.integers(0, 3, 6),
               rng.random((6, 3)) < 0.7, "affirmative", iou_thr=thr)


def test_padded_images_and_rows_answer_nothing():
    """Three images launch as four; rows past the flush, rows whose
    image the launch lacks and empty masks all come back empty."""
    tr = generate_traces(default_providers(), 3, seed=6, mean_objects=7.36)
    assert iou_launch_shape(3, 1)[0] == 4
    slots = [2, -1, 0, 1, 2]
    bits = np.asarray([[1, 1, 0], [1, 1, 1], [0, 0, 0], [1, 0, 1],
                       [0, 1, 1]], bool)
    check_rows(tr.dets, slots, bits, "affirmative", n_rows=8)


def test_unknown_voting_is_refused():
    with pytest.raises(ValueError):
        device_fuse.fuse(None, None, None, voting="majority")
