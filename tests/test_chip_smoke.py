"""CPU rehearsal of ``chip_smoke.py``: its device check refuses the CPU,
and its phase functions pass at a tiny size (kernels in interpret mode,
the thread plane on the numpy twin as ``use_kernel="auto"`` resolves on
the CPU)."""
import importlib.util
import pathlib

import pytest

pytest.importorskip("jax")

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY_SERVE = ["--federation", "--async", "--transport", "thread",
              "--workers", "4", "--max-batch", "16", "--images", "40",
              "--requests", "64", "--policy", "rl"]
TINY_TRAIN = dict(images=40, epochs=2, steps_per_epoch=48, batch_size=32,
                  start_steps=16, update_after=16, update_every=16,
                  update_iters=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.device_check()


@pytest.mark.slow
def test_phases_pass_at_tiny_size(smoke, capsys):
    """Serve, process plane (spawns two workers) and training, each
    against its reference, in the order the smoke runs them."""
    with smoke.CompileCounter() as compiles:
        run, ref = smoke.phase_serve(TINY_SERVE, compiles,
                                     expect_kernel=False)
        smoke.phase_process(TINY_SERVE + smoke.PROCESS_OVERRIDES, run, ref,
                            compiles)
        smoke.phase_train(compiles, **TINY_TRAIN)
    out = capsys.readouterr().out
    assert "workers holding a jax backend: 0" in out
    assert "requests whose fused output differs: 0 of 64" in out
