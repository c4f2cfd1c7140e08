"""``chip_smoke.py`` phases at a tiny size on the CPU, and its refusal to
run without a TPU.

The phases take their sizes as arguments, so the same checks the chip run
makes (stage-4 error bound, numpy references within the bias bounds, kernel
cells bitwise against the XLA lowering, every request answered) run here
on fields small enough for the Pallas interpreter.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_2d_tiny(smoke):
    out = smoke.phase_2d(dims=(64, 96), n_fields=2)
    assert out["custom_calls"] == 0  # interpret mode: no TPU custom calls


def test_phase_3d_tiny(smoke):
    smoke.phase_3d(dims=(16, 24, 24), n_vars=3)


def test_phase_stream_tiny(smoke):
    smoke.phase_stream(dims=(32, 48), n_slabs=2, steps=2)


def test_phase_shard_tiny(smoke):
    smoke.phase_shard(dims=(32, 48, 40), n_shards=1)


def test_check_raises_on_failure(smoke):
    with pytest.raises(smoke.SmokeFailure):
        smoke.close("x", [1.0], [1.5], bound=0.1, weight=0.0, amax=1.0)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
