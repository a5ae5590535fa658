"""Compile the main path's device programs for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present.  Nothing runs, so these
tests say nothing about results or times; they catch what interpret mode
cannot (tiling, fast-memory limits, lowering gaps) at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and each test worker
imports every test file.
"""
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

# merged boxes per image in the 400-image traces: 0-16 for the paper's
# 3-provider roster (median 7), 3-52 for the 10-provider one; 130 spans
# two row blocks of the kernel's grid
BOX_COUNTS = (1, 7, 16, 52, 130)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """Compiles for a described chip are written to a persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_iou_kernel_compiles_at_trace_box_counts(one_chip):
    from repro.kernels.iou_matrix.kernel import iou_matrix_pallas
    for m in BOX_COUNTS:
        box = _spec((m, 4), one_chip)
        compiled = jax.jit(iou_matrix_pallas).lower(box, box).compile()
        _assert_kernel(compiled)


def test_batched_iou_kernel_compiles(one_chip):
    from repro.kernels.iou_matrix.ops import batch_self_iou
    for b, m in ((1, 7), (4, 16), (16, 16), (16, 130)):
        compiled = batch_self_iou.lower(_spec((b, m, 4), one_chip)).compile()
        _assert_kernel(compiled)


def test_every_iou_launch_shape_of_a_flush_compiles(one_chip):
    """The padded shapes a shard's flush of 1..16 images reaches up to 96
    boxes (the Tab. III warm grid): at most 64 programs, each a kernel."""
    from repro.ensemble.pipeline import iou_launch_shape
    from repro.kernels.iou_matrix.ops import batch_self_iou
    shapes = {iou_launch_shape(b, m) for b in range(1, 17)
              for m in range(1, 97)}
    assert len(shapes) <= 64
    for b, m in sorted(shapes):
        compiled = batch_self_iou.lower(_spec((b, m, 4), one_chip)).compile()
        _assert_kernel(compiled)


def test_sac_act_compiles_for_the_ten_provider_roster(one_chip):
    from repro.core.sac import SACConfig, _act, _init_state
    cfg = SACConfig(state_dim=144, n_providers=10)
    state = jax.tree.map(lambda x: _spec(x.shape, one_chip, x.dtype),
                         jax.eval_shape(lambda: _init_state(cfg)))
    for rows in ((), (16,)):
        _act.lower(cfg, state.actor, state.key,
                   _spec((*rows, cfg.state_dim), one_chip), True).compile()


def test_sac_act_and_update_block_compile_at_paper_widths(one_chip):
    from repro.core.sac import SACConfig, _act, _init_state, _update_block
    cfg = SACConfig(state_dim=144, n_providers=3)
    assert cfg.hidden == (256, 256)
    state = jax.tree.map(lambda x: _spec(x.shape, one_chip, x.dtype),
                         jax.eval_shape(lambda: _init_state(cfg)))
    # the served program: actor and key in, (a, proto, key) out, the
    # mode static; one row and a full flush of 16
    for rows in ((), (16,)):
        states = _spec((*rows, cfg.state_dim), one_chip)
        for det in (True, False):
            _act.lower(cfg, state.actor, state.key, states, det).compile()
    K, B = 50, 256
    batches = {"s": _spec((K, B, cfg.state_dim), one_chip),
               "a": _spec((K, B, cfg.n_providers), one_chip),
               "r": _spec((K, B), one_chip),
               "s2": _spec((K, B, cfg.state_dim), one_chip),
               "d": _spec((K, B), one_chip)}
    mem = _update_block.lower(cfg, state, batches).compile() \
        .memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16e9          # fits one v5e chip's 16 GB


def test_device_fuse_compiles_at_flush_shapes(one_chip):
    """The device fuse of a flush of 16 request rows over its IoU launch:
    the smallest and largest launch shapes of the Tab. II roster (3
    providers, up to 40 boxes) and the largest of the Tab. III one (10
    providers, 96 boxes) under every voting."""
    from repro.ensemble import device_fuse
    for n, b, m, votings in ((3, 2, 8, ("affirmative",)),
                             (3, 16, 40, ("affirmative",)),
                             (10, 16, 96, device_fuse.VOTING)):
        for voting in votings:
            device_fuse.fuse_flush.lower(
                _spec((b, m, device_fuse.TABLE_COLS), one_chip),
                _spec((b, m, m), one_chip),
                _spec((16, 1 + n), one_chip, jnp.int32),
                voting=voting, thr=0.5).compile()
