"""Test harness: 8 fake XLA-CPU devices in one process.

This is the TPU-world analogue of the reference's "gloo backend on CPU"
escape hatch (BASELINE.json config 1; SURVEY.md §4 "Multi-device without a
cluster"): every collective, mesh, and sharding test runs on the host
platform with 8 virtual devices and never touches the real chip.
"""

import os
import tempfile

# Flight-recorder dumps (obs/flight.py) fall back to a tmp dir when no
# dir is configured — never the CWD — but tests that trip dump triggers
# (watchdog/launch hang tests) should still land in one predictable
# per-session place, not the shared tmp fallback. Worker processes
# spawned by launch tests inherit this too; tests that assert on dump
# locations override it per-test (monkeypatch / LaunchConfig.flight_dir
# both win over this default).
os.environ.setdefault(
    "TPUNN_FLIGHT_DIR", tempfile.mkdtemp(prefix="tpunn-flight-test-"))

import jax

# 8 virtual CPU devices, set through the config so that a bare `pytest`
# works whatever the environment says: it takes effect as long as no
# backend has been initialised, and importing jax does not do that.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (trace capture, long training) excluded from "
        "the tier-1 `-m 'not slow'` run",
    )


@pytest.fixture(autouse=True, scope="session")
def _no_flight_dumps_in_repo_root():
    """Regression guard for the flight CWD-fallback bug: a test that
    tripped a dump trigger with TPUNN_FLIGHT_DIR unset used to leave
    flight_rank*.json in the repo root (one was committed by accident).
    The fallback is now a tmp dir; this keeps it that way."""
    import glob

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = set(glob.glob(os.path.join(root, "flight_rank*.json")))
    assert not before, (
        f"stale flight dumps in repo root before tests: {sorted(before)}")
    yield
    after = set(glob.glob(os.path.join(root, "flight_rank*.json")))
    assert not after, (
        f"test run littered flight dumps into the repo root: "
        f"{sorted(after)} — obs/flight.py must never fall back to CWD")


@pytest.fixture(scope="session")
def tiny_llama():
    """One CI-scale llama shared across the serving test files
    (test_serve.py, test_prefix_cache.py) so the serve jits compile
    once per session instead of once per module."""
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model

    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   mlp_dim=128, vocab_size=97),
    ))
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.key(1), tokens, train=False)["params"]
    return model, params


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=8))
