"""chip_smoke.py on the CPU, and the device rules of the entry points.

The chip run itself cannot happen here. What can: the script refuses a
CPU, its phase functions run end to end at tiny widths when called
with a test-only size argument (rehearsals 1 and 2 of the
on-chip-measurement guide), the compile cache lands where the rules
say, the shared device check refuses what it should, and the parents
that spawn chip-needing workers stay off JAX.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from pytorch_distributed_nn_tpu.runtime import device  # noqa: E402

TINY_LLAMA = dict(d_model=64, num_heads=4, num_kv_heads=2, mlp_dim=128,
                  vocab_size=97)
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


@pytest.fixture
def meter():
    return chip_smoke.Meter(None)


@pytest.fixture
def cache_config_restored():
    """Phases load entry points, and entry points place the compile
    cache: put the session's setting back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def _phase_line(capsys, phase):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    recs = [r for r in lines if r.get("phase") == phase]
    assert recs, lines
    return recs


def _run_py(code, env=CPU_ENV, timeout=300):
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- the script refuses a CPU ----------------------------------------------


def test_cpu_run_exits_nonzero_without_ok_line():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env=CPU_ENV, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "names no TPU" in r.stderr


def test_device_phase_refuses_cpu_even_when_asked_for(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.phase_device()


# -- the script's own arithmetic -------------------------------------------


@pytest.mark.parametrize("losses,ok", [
    ([3.0, 2.9, 2.8, 2.7], True),
    ([3.0, 3.2, 2.9, 2.7, 3.1, 2.6, 2.8, 2.9, 2.7, 2.6], True),  # noisy
    ([3.0, 3.1, 3.2, 3.3], False),
    ([3.0, float("nan"), 2.0, 1.0], False),
    ([3.0, 2.0], False),  # two of four steps logged
])
def test_check_losses(losses, ok):
    steps = [{"loss": x, "seconds": 0.1} for x in losses]
    want = 4 if len(losses) < 10 else 10
    if ok:
        assert chip_smoke.check_losses(steps, want, "t") == losses
    else:
        with pytest.raises(AssertionError):
            chip_smoke.check_losses(steps, want, "t")


def test_steady_rate_skips_the_compile_and_the_loader_warmup():
    steps = [{"seconds": s} for s in (40.0, 5.0, 0.5, 0.5)]
    assert chip_smoke.steady_rate(steps) == 2.0
    assert chip_smoke.steady_rate(steps[:2]) is None


@pytest.mark.parametrize("text,want", [
    # sync, async and the TPU compiler's reduce-scatter fusion
    ("x = f32[4] all-reduce(y)\nz = f32[4] all-gather-start(w)",
     {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 0,
      "all-to-all": 0}),
    ("f = fusion(a), kind=kCustom, calls=%all-reduce-scatter.2\n"
     "%all-reduce-scatter.2 (input: bf16[8]) -> bf16[2] {",
     {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 1,
      "all-to-all": 0}),
    ("r = f32[2] reduce-scatter(a)\nt = f32[2] all-to-all(b)",
     {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 1,
      "all-to-all": 1}),
])
def test_collective_counts(text, want):
    assert chip_smoke.collective_counts(text) == want


def test_meter_reports_a_phase_share(meter):
    import jax.numpy as jnp

    mark = meter.mark()
    jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    share = meter.since(mark)
    assert share["compile_s"] > 0 and share["seconds"] >= share["compile_s"]
    assert share["cache_entries_delta"] == 0  # no cache directory given


def test_train_argv_round_trips_through_the_config_parser():
    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides

    run = chip_smoke.TRAIN_RUNS[0]
    args = parse_overrides(chip_smoke.train_argv(run, Path("/x/m.jsonl")))
    assert args.pop("preset") == "resnet50_dp"
    cfg = get_config("resnet50_dp", **args)
    assert (cfg.steps, cfg.data.batch_size, cfg.optim.lr, cfg.log_every,
            cfg.metrics_path) == (20, 128, 0.0125, 1, "/x/m.jsonl")


# -- phases at tiny widths (rehearsal 1) -----------------------------------


def test_kernels_phase_tiny(meter, capsys):
    dev = jax.devices()[0]
    chip_smoke.phase_kernels(meter, dev, sizes=dict(
        flash=dict(B=1, T=64, H=4, Hkv=2, D=16),
        int8=((4, 64, 256),), quant_elems=4096))
    (rec,) = _phase_line(capsys, "kernels")
    # off the TPU the dispatchers take their reference branch, and the
    # phase says so instead of passing for a kernel run
    assert rec["flash"]["tpu_custom_calls"] == 0
    assert rec["int8_matmul"][0]["tpu_custom_calls"] == 0
    assert rec["quantize_int8"]["tpu_custom_calls"] == 0
    assert rec["compile_s"] > 0


def test_train_phase_tiny(meter, capsys, tmp_path, cache_config_restored):
    dev = jax.devices()[0]
    chip_smoke.phase_train(
        meter, dev, dict(preset="mlp_mnist", steps=6,
                         overrides={"data.batch_size": 64}),
        out=tmp_path)
    (rec,) = _phase_line(capsys, "train")
    assert rec["steps"] == 6 and rec["loss_last"] < rec["loss_first"]
    assert rec["tpu_custom_calls"] == {"train_step": 0}
    assert (tmp_path / "smoke_train_mlp_mnist.jsonl").exists()


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
def test_serve_phase_tiny(meter, capsys, quantized):
    dev = jax.devices()[0]
    chip_smoke.phase_serve(meter, dev, dict(
        name="tiny", layers=1, quantized=quantized, extra=TINY_LLAMA,
        cut="test", slots=2, max_seq=32, requests=3, max_new=4,
        min_prompt=5, max_prompt=14, rate_hz=100.0))
    (rec,) = _phase_line(capsys, "serve")
    assert rec["completed"] == 3
    # every request either matches sequential generate or left it at a
    # margin the phase itself gated
    assert rec["identical_to_generate"] + len(rec["diverged"]) == 3
    assert set(rec["tpu_custom_calls"]) >= {"decode_step"}
    assert rec["tokens_per_s"] > 0


def test_launcher_phase_tiny_leaves_parent_off_jax(tmp_path):
    """The launcher run is chip_smoke's first act: the phase refuses to
    start with a backend up, and ends with none."""
    r = _run_py(
        "import chip_smoke, pathlib\n"
        "chip_smoke.phase_launcher("
        "dict(preset='mlp_mnist', steps=4,"
        " overrides={'data.batch_size': 32}),"
        f" out=pathlib.Path({str(tmp_path)!r}), platform='cpu')\n")
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["phase"] == "launcher"
    assert rec["final"].startswith("final: step=3")


def test_launcher_phase_refuses_an_initialised_backend(tmp_path):
    jax.devices()  # this process has a backend
    with pytest.raises(AssertionError, match="before the launcher"):
        chip_smoke.phase_launcher(out=tmp_path, platform="cpu")


# -- the path across devices on virtual ones (rehearsal 2) -----------------


@pytest.mark.slow  # four Trainers: run by hand before a four-chip call
def test_parallel_phase_on_virtual_devices(meter, capsys):
    dev = jax.devices()[0]
    chip_smoke.phase_parallel(meter, dev, dict(
        # dp_explicit averages per-device means of the masked-token
        # loss (torch DDP's semantics): enough tokens per device keep
        # that within the tolerance
        preset="bert_base_buckets", steps=3, batch=64, rel_tol=1e-2,
        overrides={
            "data.seq_len": "32", "data.vocab_size": "128",
            "model.compute_dtype": "float32",
            # leaves under 2**14 elements stay replicated under zero
            "model.extra": json.dumps(dict(
                vocab_size=128, num_layers=2, d_model=128, num_heads=2,
                mlp_dim=256, max_len=32))}),
        n_devices=len(jax.devices()))
    recs = {r["strategy"]: r for r in _phase_line(capsys, "parallel")}
    assert set(recs) == {"dp_explicit", "zero3"}
    assert recs["dp_explicit"]["collectives"]["all-reduce"] > 0
    assert recs["zero3"]["params_split"] > 0
    assert recs["zero3"]["batch_devices"] == len(jax.devices())


# -- the compile cache -------------------------------------------------------


def test_compile_cache_env_wins(monkeypatch, cache_config_restored):
    monkeypatch.setenv(device.CACHE_ENV, "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    # JAX read the variable when it was imported; the code sets nothing
    assert device.configure_compile_cache() == before
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed(monkeypatch, cache_config_restored):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    want = str(ROOT / ".jax_cache")
    assert device.configure_compile_cache() == want
    assert device.configure_compile_cache() == want
    env = {k: v for k, v in CPU_ENV.items() if k != device.CACHE_ENV}
    code = ("from pytorch_distributed_nn_tpu.runtime.device import "
            "configure_compile_cache as c; print(c())")
    assert _run_py(code, env=env).stdout.strip() == want


def test_compile_cache_env_reaches_a_fresh_process(tmp_path):
    code = ("from pytorch_distributed_nn_tpu.runtime.device import "
            "configure_compile_cache as c; print(c())")
    r = _run_py(code, env={**CPU_ENV, device.CACHE_ENV: str(tmp_path)})
    assert r.stdout.strip() == str(tmp_path)


# -- the shared device check -------------------------------------------------


@pytest.mark.parametrize("platforms", [
    "cpu",      # asked for by name: still no chip
    "",         # unset: a CPU here is a fallback
    "tpu,cpu",  # the chip machine's setting, no chip
])
def test_require_tpu(monkeypatch, platforms):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="no TPU"):
        device.require_tpu()


# -- one process for each chip -----------------------------------------------


def test_second_chip_claim_fails_with_the_reason(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # claim_chip reads only this
    fd = device.claim_chip(lock_dir=str(tmp_path))
    try:
        r = _run_py(
            "from pytorch_distributed_nn_tpu.runtime.device import "
            f"claim_chip; claim_chip(lock_dir={str(tmp_path)!r})",
            env={**os.environ, "JAX_PLATFORMS": "tpu"})
        assert r.returncode != 0
        assert f"held by pid {os.getpid()}" in r.stderr
        assert "one process at a time" in r.stderr
        # another subset of the host's chips is another lock
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "1")
        os.close(device.claim_chip(lock_dir=str(tmp_path)))
    finally:
        os.close(fd)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.claim_chip(lock_dir=str(tmp_path)) is None


_OFF_JAX = ("from jax._src import xla_bridge as xb\n"
            "assert not xb.backends_are_initialized(), 'backend up'\n")


def test_launch_agent_never_initialises_a_backend():
    """The agent's worker needs the chip, so the agent may import jax
    (obs/ and runtime/ do) but not touch a device."""
    r = _run_py(
        "from pytorch_distributed_nn_tpu import launch\n" + _OFF_JAX +
        "res = launch.launch(['-c', 'print(1)'], launch.LaunchConfig("
        "nprocs=1, heartbeat_timeout_s=30.0))\n"
        "assert res.exit_code == 0, res\n" + _OFF_JAX)
    assert r.returncode == 0, r.stderr[-3000:]


def test_procfleet_coordinator_never_initialises_a_backend():
    r = _run_py(
        "from pytorch_distributed_nn_tpu.serve.procfleet import "
        "ProcessFleet\n" + _OFF_JAX +
        "with ProcessFleet(replicas=1, backend='stub',"
        " heartbeat_interval_s=0.05, heartbeat_timeout_s=5.0) as f:\n"
        "    f.start()\n"
        "    assert f.wait_ready(1, timeout=120)\n"
        "    t = f.submit([3, 5, 7], 8)\n"
        "    assert f.wait_all([t], timeout=60) and t.ok\n" + _OFF_JAX)
    assert r.returncode == 0, r.stderr[-3000:]
