"""Public wrapper: picks Pallas-on-TPU or interpret-on-CPU automatically."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.iou_matrix.kernel import iou_matrix_pallas


def iou_matrix_op(boxes_a, boxes_b, *, block_m: int = 128,
                  block_n: int = 512) -> jnp.ndarray:
    """(M,4) x (N,4) -> (M,N) IoU via the Pallas kernel (interpret on CPU).

    Block sizes are clamped to the input sizes (a 128-wide block over a
    3-box input is a lowering error on real backends).  A kernel that
    fails to lower or run raises: a measuring path must never move onto
    the host behind the caller's back.
    """
    a = jnp.asarray(boxes_a, jnp.float32).reshape(-1, 4)
    b = jnp.asarray(boxes_b, jnp.float32).reshape(-1, 4)
    M, N = int(a.shape[0]), int(b.shape[0])
    if M == 0 or N == 0:
        return jnp.zeros((M, N), jnp.float32)
    interpret = jax.default_backend() == "cpu"
    return iou_matrix_pallas(a, b, block_m=min(block_m, M),
                             block_n=min(block_n, N), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batch_self_iou(boxes, *, interpret: bool = False) -> jnp.ndarray:
    """(B, M, 4) -> (B, M, M): every image's pairwise self-IoU in one
    vmapped kernel launch (one compile per (B, M))."""
    return jax.vmap(lambda b: iou_matrix_pallas(
        b, b, interpret=interpret))(boxes)
