"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.iou_matrix.kernel import iou_matrix_pallas
from repro.kernels.iou_matrix.ref import iou_matrix_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_naive
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# IoU matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(1, 1), (7, 5), (33, 129), (128, 512),
                                 (130, 515)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_iou_kernel_shapes(m, n, dtype):
    a = RNG.random((m, 4)).astype(dtype)
    a[:, 2:] = a[:, :2] + RNG.random((m, 2)).astype(dtype)
    b = RNG.random((n, 4)).astype(dtype)
    b[:, 2:] = b[:, :2] + RNG.random((n, 2)).astype(dtype)
    got = iou_matrix_pallas(jnp.asarray(a, jnp.float32),
                            jnp.asarray(b, jnp.float32),
                            block_m=32, block_n=64, interpret=True)
    ref = iou_matrix_ref(jnp.asarray(a, jnp.float32),
                         jnp.asarray(b, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_iou_degenerate_boxes():
    a = np.asarray([[0.5, 0.5, 0.5, 0.5]], np.float32)   # zero area
    b = np.asarray([[0.0, 0.0, 1.0, 1.0]], np.float32)
    got = iou_matrix_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True)
    assert float(got[0, 0]) == 0.0


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,hd,bq,bk", [(32, 16, 8, 8), (64, 32, 16, 32),
                                        (128, 64, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, hd, bq, bk, dtype, causal):
    B, H = 2, 3
    q = jnp.asarray(RNG.standard_normal((B, H, S, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, S, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, S, hd)), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("window", [8, 24])
def test_flash_attention_sliding_window(window):
    B, H, S, hd = 1, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, hd)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=16, block_k=16, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_gqa_wrapper():
    B, S, H, K, hd = 2, 32, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    kr = jnp.repeat(k, H // K, 2)
    vr = jnp.repeat(v, H // K, 2)
    ref = jnp.moveaxis(attention_ref(jnp.moveaxis(q, 2, 1),
                                     jnp.moveaxis(kr, 2, 1),
                                     jnp.moveaxis(vr, 2, 1), causal=True),
                       1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(32, 8), (64, 16), (128, 128)])
@pytest.mark.parametrize("nh,hd,N", [(2, 8, 4), (4, 16, 8)])
def test_ssd_kernel_sweep(S, chunk, nh, hd, N):
    B = 2
    xh = jnp.asarray(RNG.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, S, nh)) * 0.5 + 0.05, jnp.float32)
    A = -jnp.asarray(RNG.random((nh,)) * 0.9 + 0.3, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    naive = ssd_naive(xh, dt, A, Bm, Cm)
    kern = ssd_scan(xh, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(naive),
                               atol=5e-4)


def test_chunked_jnp_matches_naive():
    B, S, nh, hd, N = 1, 48, 2, 8, 4
    xh = jnp.asarray(RNG.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, S, nh)) * 0.4 + 0.05, jnp.float32)
    A = -jnp.ones((nh,), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y, _ = ssd_chunked(xh, dt, A, Bm, Cm, 12)
    naive = ssd_naive(xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(naive), atol=5e-4)


def test_ssd_initial_state_continuation():
    """Running two halves with carried state == one full run."""
    B, S, nh, hd, N = 1, 64, 2, 8, 4
    xh = jnp.asarray(RNG.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, S, nh)) * 0.4 + 0.05, jnp.float32)
    A = -jnp.ones((nh,), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y_full, st_full = ssd_chunked(xh, dt, A, Bm, Cm, 16)
    y1, st1 = ssd_chunked(xh[:, :32], dt[:, :32], A, Bm[:, :32],
                          Cm[:, :32], 16)
    y2, st2 = ssd_chunked(xh[:, 32:], dt[:, 32:], A, Bm[:, 32:],
                          Cm[:, 32:], 16, initial_state=st1)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(st_full), np.asarray(st2),
                               atol=5e-4)


# ---------------------------------------------------------------------------
# kernel dispatch: resolve_use_kernel + iou_matrix_op failures
# ---------------------------------------------------------------------------

def test_resolve_use_kernel_rejects_bad_strings():
    """Regression: a typo like "atuo" used to silently resolve as truthy
    instead of failing loudly."""
    from repro.ensemble.pipeline import resolve_use_kernel
    for bad in ("atuo", "Auto", "yes", ""):
        with pytest.raises(ValueError, match="use_kernel"):
            resolve_use_kernel(bad)
    assert resolve_use_kernel("auto") == (jax.default_backend() != "cpu")
    assert resolve_use_kernel(True) is True
    assert resolve_use_kernel(False) is False


def test_iou_matrix_op_clamps_blocks_to_tiny_inputs():
    """Regression: default 128x128 blocks on a 3x2 problem used to reach
    the kernel with out-of-range tiles."""
    from repro.kernels.iou_matrix.ops import iou_matrix_op
    a = RNG.random((3, 4)).astype(np.float32)
    b = RNG.random((2, 4)).astype(np.float32)
    a[:, 2:] = a[:, :2] + 0.5
    b[:, 2:] = b[:, :2] + 0.5
    got = np.asarray(iou_matrix_op(a, b))           # default block sizes
    want = np.asarray(iou_matrix_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_iou_matrix_op_falls_back_on_lowering_failure(monkeypatch):
    """A kernel that fails to lower or run raises out of the op: there is
    no fallback to the numpy twin, so a chip run can never move onto the
    host in silence."""
    from repro.kernels.iou_matrix import ops

    def boom(*a, **kw):
        raise RuntimeError("no pallas lowering for this backend")

    a = RNG.random((5, 4)).astype(np.float32)
    b = RNG.random((7, 4)).astype(np.float32)
    a[:, 2:] = a[:, :2] + 0.5
    b[:, 2:] = b[:, :2] + 0.5
    monkeypatch.setattr(ops, "iou_matrix_pallas", boom)
    for _ in range(2):      # every call raises, not only the first
        with pytest.raises(RuntimeError, match="no pallas lowering"):
            ops.iou_matrix_op(a, b)
