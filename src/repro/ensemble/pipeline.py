"""The 12 ensemble pathways: {affirmative,consensus,unanimous} voting x
{none,nms,soft-nms,wbf} ablation.  Paper default: Affirmative-WBF.

Two entry points:

  * ``ensemble_detections``        — one image, a list of per-provider
    ``Detections`` (the seed API, kept verbatim for callers and tests).
  * ``ensemble_detections_batch``  — many images in one call, array-first:
    merged arrays + one (optionally Pallas-kernel-backed) pairwise-IoU
    matrix per image, shared across the grouping/voting/ablation stages.

Both funnel into ``ensemble_from_arrays``, the array-first core used by the
subset-evaluation cache (``repro.federation.evaluation``) which slices a
single per-image IoU matrix across all candidate provider subsets.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.ensemble.ablation import nms, soft_nms, wbf
from repro.ensemble.boxes import Detections
from repro.ensemble.voting import group_detections, vote_filter

VOTING = ("affirmative", "consensus", "unanimous")
ABLATION = ("none", "nms", "softnms", "wbf")
PATHWAYS = [(v, a) for v in VOTING for a in ABLATION]
DEFAULT = ("affirmative", "wbf")


def resolve_use_kernel(use_kernel: Union[bool, str]) -> bool:
    """``"auto"`` -> Pallas IoU kernel on accelerator backends, numpy twin
    on CPU (where interpret-mode Pallas is orders of magnitude slower).
    The two are not bit-identical: on a TPU v5e their IoU entries differed
    by up to 2^-23 (``docs/architecture.md``, "Bit-parity contracts")."""
    if isinstance(use_kernel, str):
        if use_kernel != "auto":
            # a typo like "atuo" must not silently coerce to True (any
            # non-empty string is truthy) and flip the dispatch
            raise ValueError(
                f"use_kernel must be a bool or 'auto', got {use_kernel!r}")
        import jax
        return jax.default_backend() != "cpu"
    return bool(use_kernel)


def ensemble_from_arrays(boxes: np.ndarray, scores: np.ndarray,
                         labels: np.ndarray, providers: np.ndarray,
                         n_selected: int, *, voting: str = "affirmative",
                         ablation: str = "wbf", iou_thr: float = 0.5,
                         iou: Optional[np.ndarray] = None) -> Detections:
    """Array-first ensemble core: merged per-image arrays in, fused out.

    ``providers`` tags each detection with its position in the selected
    subset (0..n_selected-1); ``iou`` optionally supplies the precomputed
    pairwise IoU of ``boxes`` so batched/cached callers pay for it once.
    Arrays must already be normalized (float32 boxes/scores, int32 labels/
    providers) — every caller slices or concatenates normalized
    ``Detections`` storage.
    """
    merged = Detections.fast(boxes, scores, labels, providers)
    if len(merged) == 0:
        return merged
    groups = group_detections(merged, iou_thr=iou_thr, iou=iou)
    groups = vote_filter(merged, groups, method=voting,
                         n_selected=n_selected)
    if ablation == "wbf":
        return wbf(merged, groups, n_models=n_selected)
    if not groups:
        return Detections.empty()
    kept = merged.take(np.concatenate(groups))
    if ablation == "none":
        return kept
    if ablation == "nms":
        return nms(kept, iou_thr=iou_thr)
    if ablation == "softnms":
        return soft_nms(kept)
    raise ValueError(ablation)


def merge_provider_detections(per_provider: Sequence[Detections]):
    """Concat per-provider detections into merged arrays, tagging each row
    with its position in the selection (the single source of truth for the
    merged-array layout shared by the direct, batched, and cached paths).
    Returns (boxes, scores, labels, providers); ``per_provider`` must be
    non-empty."""
    boxes = np.concatenate([d.boxes for d in per_provider], axis=0)
    scores = np.concatenate([d.scores for d in per_provider])
    labels = np.concatenate([d.labels for d in per_provider])
    providers = np.repeat(np.arange(len(per_provider), dtype=np.int32),
                          [len(d) for d in per_provider])
    return boxes, scores, labels, providers


def ensemble_detections(per_provider: Sequence[Detections], *,
                        voting: str = "affirmative", ablation: str = "wbf",
                        iou_thr: float = 0.5,
                        use_kernel: bool = False) -> Detections:
    """Merge detections from the selected providers (paper Sec. IV-D).

    ``per_provider[i]`` is provider i's detections for one image, with
    labels already mapped to canonical group ids by the word-grouping stage.
    ``use_kernel`` takes the merged boxes' IoU from a kernel launch.
    """
    if not per_provider:
        return Detections.empty()
    boxes, scores, labels, providers = \
        merge_provider_detections(per_provider)
    iou = batch_iou_matrices([boxes], use_kernel=use_kernel)[0]
    return ensemble_from_arrays(boxes, scores, labels, providers,
                                len(per_provider), voting=voting,
                                ablation=ablation, iou_thr=iou_thr, iou=iou)


# The kernel path launches on a fixed set of (images, boxes) shapes, the
# same for every roster and seed, so a service warms a bounded grid: box
# counts rise to the next multiple of IOU_BOX_STEP, image counts to the
# next power of two up to IOU_IMAGE_STEP and to its next multiple above.
# A launch carries at least IOU_MIN_IMAGES images: a one-image batch is
# compiled apart and, on the CPU, rounds some entries one ulp away from a
# batched launch, while from two images up an image's table has the same
# bits whatever else its launch carries.
IOU_BOX_STEP = 8
IOU_IMAGE_STEP = 16
IOU_MIN_IMAGES = 2


def iou_launch_shape(n_images: int, n_boxes: int) -> Tuple[int, int]:
    """The padded (images, boxes) of a kernel launch over ``n_images``
    images whose largest box count is ``n_boxes``."""
    n_images, n_boxes = max(int(n_images), 1), max(int(n_boxes), 1)
    if n_images <= IOU_IMAGE_STEP:
        b = max(1 << (n_images - 1).bit_length(), IOU_MIN_IMAGES)
    else:
        b = -(-n_images // IOU_IMAGE_STEP) * IOU_IMAGE_STEP
    return b, -(-n_boxes // IOU_BOX_STEP) * IOU_BOX_STEP


def launch_self_iou(boxes_list: Sequence[np.ndarray]):
    """Dispatch the kernel path's one launch over a batch of images
    without waiting for it: the batch is padded to ``iou_launch_shape``
    (zero boxes and zero images), the vmapped Pallas call is issued and
    its copy to the host started.  Returns the padded (images, boxes,
    boxes) device array, whose ``[i, :n_i, :n_i]`` is image ``i``'s IoU,
    or ``None`` where no image has a box (nothing is launched)."""
    import jax
    from repro.kernels.iou_matrix.ops import batch_self_iou
    nmax = max((int(b.shape[0]) for b in boxes_list), default=0)
    if nmax == 0:
        return None
    padded = np.zeros(iou_launch_shape(len(boxes_list), nmax) + (4,),
                      np.float32)
    for i, b in enumerate(boxes_list):
        padded[i, :len(b)] = b
    full = batch_self_iou(padded, interpret=jax.default_backend() == "cpu")
    full.copy_to_host_async()
    return full


def batch_iou_matrices(boxes_list: Sequence[np.ndarray], *,
                       use_kernel: Union[bool, str] = "auto"
                       ) -> List[np.ndarray]:
    """Pairwise self-IoU for a batch of images in one launch.

    Kernel path: one ``launch_self_iou`` (one compile per padded shape),
    read back at once; numpy path computes per image (padding would cost
    more than it saves on CPU).
    """
    from repro.ensemble.boxes import iou_matrix
    if not boxes_list:
        return []
    if resolve_use_kernel(use_kernel):
        full = launch_self_iou(boxes_list)
        if full is None:
            return [np.zeros((0, 0), np.float32) for _ in boxes_list]
        full = np.asarray(full)
        return [full[i, :len(b), :len(b)] for i, b in enumerate(boxes_list)]
    return [iou_matrix(b, b) if len(b) else np.zeros((0, 0), np.float32)
            for b in boxes_list]


def ensemble_detections_batch(per_image: Sequence[Sequence[Detections]], *,
                              voting: str = "affirmative",
                              ablation: str = "wbf", iou_thr: float = 0.5,
                              use_kernel: Union[bool, str] = "auto"
                              ) -> List[Detections]:
    """Ensemble a whole split of images in one call.

    ``per_image[t]`` is the list of selected providers' ``Detections`` for
    image t.  All pairwise-IoU matrices are computed up front in one batched
    launch (Pallas kernel on accelerators), then the grouping greedy runs
    over each precomputed matrix.
    """
    merged_arrays = []
    for sel in per_image:
        if sel:
            boxes, scores, labels, provs = merge_provider_detections(sel)
        else:
            e = Detections.empty()
            boxes, scores, labels, provs = e.boxes, e.scores, e.labels, \
                e.providers
        merged_arrays.append((boxes, scores, labels, provs, len(sel)))
    ious = batch_iou_matrices([m[0] for m in merged_arrays],
                              use_kernel=use_kernel)
    return [ensemble_from_arrays(b, s, l, p, k, voting=voting,
                                 ablation=ablation, iou_thr=iou_thr, iou=iou)
            for (b, s, l, p, k), iou in zip(merged_arrays, ious)]
