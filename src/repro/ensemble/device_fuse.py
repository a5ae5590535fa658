"""A flush's cold ensembles fused on the device: greedy grouping, voting
and WBF for every request row of the flush in one jitted program.

The kernel path already computes a flush's fresh IoU tables in one
launch (``launch_self_iou``).  ``launch_fuse`` hands that launch's
device result, still on the device, to ``fuse_flush`` together with the
launched images' merged tables and, per request row, its image's slot
in the launch and its provider selection.  Each row's answer is the one
``ensemble_from_arrays`` gives with WBF over the same IoU entries:

  * visit the selected rows in stable descending-score order;
  * each box joins the first group, in creation order, whose
    representative has its label and an IoU above the threshold (the
    same float32 entry), else it opens a group;
  * voting counts each group's distinct providers;
  * WBF weighs each member by its score over the group's float32 sum;
    the fused score is the members' mean, times ``min(T, n) / n`` when
    ``n > 1`` providers are selected; label and provider (its position
    within the selected subset) come from the representative.

Every sum is a float32 addition (no matmul, which the TPU would round to
bfloat16), so boxes and scores match the host path to a few float32
ulps, and counts, labels and providers exactly.

Layouts (B launched images padded, P boxes padded, R request rows, N
providers):

  table   (B, P, 7) float32: box (x1, y1, x2, y2), score, label and
          provider of each table row; padding rows have provider -1.
  iou     (B, P, P) float32: the IoU launch's result.
  rows    (R, 1 + N) int32: the row's slot in the launch, then its N
          selection bits; a padding row selects nothing.
  result  fused (R, P, 7) float32: the row's kept groups in creation
          order (box, score, label, provider), and count (R,) int32.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.ensemble.pipeline import VOTING, iou_launch_shape

TABLE_COLS = 7
# compiles in flight at once while warming
WARM_THREADS = 4


def f32_threshold(thr: float) -> float:
    """The float32 ``t`` with ``x > t`` exactly when ``x > thr`` for every
    float32 ``x`` (the host path compares float32 entries with the
    Python float)."""
    t = np.float32(thr)
    if float(t) > thr:
        t = np.nextafter(t, np.float32(-np.inf))
    return float(t)


@functools.partial(jax.jit, static_argnames=("voting", "thr"))
def fuse_flush(table, iou, rows, *, voting: str, thr: float):
    """The program (one compile per (B, P, R, N), voting and threshold);
    ``thr`` is a float32 threshold (``f32_threshold``)."""
    R, P = rows.shape[0], table.shape[1]
    slot = rows[:, 0]
    bits = rows[:, 1:] > 0                                  # (R, N)
    t = table[slot]                                         # (R, P, 7)
    boxes, scores = t[..., :4], t[..., 4]
    labels = t[..., 5].astype(jnp.int32)
    prov = t[..., 6].astype(jnp.int32)
    provc = jnp.maximum(prov, 0)
    selected = (prov >= 0) & jnp.take_along_axis(bits, provc, axis=1)
    # the host path's stable argsort over the selected rows (which keep
    # their table order), with every other row after them
    order = jnp.argsort(jnp.where(selected, -scores, jnp.inf), axis=1,
                        stable=True).astype(jnp.int32)
    n_visit = jnp.sum(selected, axis=1, dtype=jnp.int32)
    ar = jnp.arange(P, dtype=jnp.int32)
    r_ = jnp.arange(R)

    def visit(k, state):
        gid, is_rep, n_groups, gsum, gsize, gprov, grep = state
        i = order[:, k]
        on = k < n_visit
        row = iou[slot, i]                                  # (R, P)
        cand = is_rep & (labels == labels[r_, i][:, None]) & (row > thr)
        first = jnp.min(jnp.where(cand, gid, P), axis=1)
        joins = first < P
        g = jnp.where(joins, first, n_groups)
        opens = on & ~joins
        at_i = (ar == i[:, None]) & on[:, None]
        gid = jnp.where(at_i, g[:, None], gid)
        is_rep = is_rep | (at_i & opens[:, None])
        at_g = (ar == g[:, None]) & on[:, None]
        # member-order float32 sums, as the host path's segment sums
        gsum = jnp.where(at_g, gsum + scores[r_, i][:, None], gsum)
        gsize = gsize + at_g
        gprov = jnp.where(at_g, gprov | (1 << provc[r_, i])[:, None],
                          gprov)
        grep = jnp.where(at_g & opens[:, None], i[:, None], grep)
        return gid, is_rep, n_groups + opens, gsum, gsize, gprov, grep

    zi = jnp.zeros((R, P), jnp.int32)
    gid, _, n_groups, gsum, gsize, gprov, grep = lax.fori_loop(
        0, jnp.max(n_visit), visit,
        (zi - 1, jnp.zeros((R, P), bool), jnp.zeros(R, jnp.int32),
         jnp.zeros((R, P), jnp.float32), zi, zi, zi))

    n_sel = jnp.sum(bits, axis=1, dtype=jnp.int32)[:, None]
    distinct = lax.population_count(gprov)
    keep = ar < n_groups[:, None]
    if voting == "consensus":
        keep &= 2 * distinct > n_sel
    elif voting == "unanimous":
        keep &= distinct == n_sel
    # WBF: weights over each group's sum, fused by masked float32 sums
    denom = jnp.maximum(gsum, jnp.float32(1e-12))
    w = scores / jnp.take_along_axis(denom, jnp.maximum(gid, 0), axis=1)
    member = gid[:, None, :] == ar[None, :, None]           # (R, G, P)
    fused = jnp.sum(jnp.where(member[..., None],
                              (boxes * w[..., None])[:, None], 0.0),
                    axis=2)                                 # (R, G, 4)
    mean = gsum / jnp.maximum(gsize, 1).astype(jnp.float32)
    # min(T, n) / n is exactly 1 where one provider is selected
    score = mean * (jnp.minimum(distinct, n_sel).astype(jnp.float32)
                    / jnp.maximum(n_sel, 1).astype(jnp.float32))
    rep_prov = prov[r_[:, None], grep]
    position = (jnp.cumsum(bits, axis=1, dtype=jnp.int32)
                - bits.astype(jnp.int32))
    out = jnp.concatenate(
        [fused, score[..., None],
         labels[r_[:, None], grep][..., None].astype(jnp.float32),
         position[r_[:, None], jnp.maximum(rep_prov, 0)][..., None]
         .astype(jnp.float32)], axis=-1)
    # kept groups first, in creation order
    pick = jnp.argsort(~keep, axis=1, stable=True)
    out = jnp.take_along_axis(out, pick[..., None], axis=1)
    return out, jnp.sum(keep, axis=1, dtype=jnp.int32)


def fuse(table, iou, rows, *, voting: str = "affirmative",
         iou_thr: float = 0.5):
    """Run ``fuse_flush``: the fused rows and their counts, as device
    arrays."""
    if voting not in VOTING:
        raise ValueError(voting)
    return fuse_flush(table, iou, rows, voting=voting,
                      thr=f32_threshold(iou_thr))


def pack_table(merged: Sequence[Tuple[np.ndarray, ...]], n_images: int,
               n_boxes: int) -> np.ndarray:
    """The (n_images, n_boxes, 7) table of the launched images' merged
    (boxes, scores, labels, providers), in launch order."""
    table = np.zeros((n_images, n_boxes, TABLE_COLS), np.float32)
    table[..., 6] = -1.0
    for k, (boxes, scores, labels, provs) in enumerate(merged):
        n = len(scores)
        table[k, :n, :4] = boxes
        table[k, :n, 4] = scores
        table[k, :n, 5] = labels
        table[k, :n, 6] = provs
    return table


def pack_rows(slots: np.ndarray, bits: np.ndarray, n_rows: int
              ) -> np.ndarray:
    """The (n_rows, 1 + N) rows: slot and selection bits of each request
    row; a row whose slot is -1 (its image not launched) and every
    padding row select nothing."""
    rows = np.zeros((n_rows, 1 + bits.shape[1]), np.int32)
    on = slots >= 0
    rows[:len(slots), 0] = np.where(on, slots, 0)
    rows[:len(slots), 1:] = bits & on[:, None]
    return rows


def launch_fuse(merged, iou, slots: np.ndarray, bits: np.ndarray,
                n_rows: int, *, voting: str, iou_thr: float):
    """Dispatch the program over a flush without waiting: ``merged`` per
    launched image, ``iou`` the launch's device result, ``slots`` and
    ``bits`` per request row.  Returns the (fused, count) device arrays,
    their copies to the host started."""
    b, p = iou.shape[:2]
    out = fuse(pack_table(merged, b, p), iou,
               pack_rows(slots, bits, n_rows), voting=voting,
               iou_thr=iou_thr)
    for a in out:
        a.copy_to_host_async()
    return out


def warm(n_rows: int, n_providers: int, max_images: int, max_boxes: int,
         *, voting: str, iou_thr: float) -> int:
    """Compile the program for every launch shape a flush of at most
    ``max_images`` images of at most ``max_boxes`` boxes can reach
    (``iou_launch_shape``), a few shapes at a time (the compiler runs
    outside the interpreter lock); returns the number of shapes."""
    shapes = sorted({iou_launch_shape(b, m)
                     for b in range(1, max_images + 1)
                     for m in range(1, max_boxes + 1)})
    rows = np.zeros((n_rows, 1 + n_providers), np.int32)

    def one(shape):
        b, p = shape
        jax.block_until_ready(fuse(
            pack_table([], b, p), jnp.zeros((b, p, p), jnp.float32), rows,
            voting=voting, iou_thr=iou_thr))

    with ThreadPoolExecutor(max_workers=WARM_THREADS) as pool:
        list(pool.map(one, shapes))
    return len(shapes)
