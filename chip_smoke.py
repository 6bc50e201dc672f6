#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the two main paths once, through the entry points users call, at
published widths with random weights from a seed, and checks what comes
out by the repo's own means:

    python chip_smoke.py            # one chip: launcher, device,
                                    # kernels, train, serve
    python chip_smoke.py --chips 4  # four chips: dp_explicit and zero
                                    # against one chip, nothing else

One JSON object per phase goes to stdout (seconds, compile seconds,
compile-cache hits, rates, loss, peak device bytes, how many
``tpu_custom_call``s each compiled program holds). The LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only if every phase ran and every check held; any failure is an
exception, a non-zero exit and no such line. Without a TPU it fails.

One process holds the chip. The elastic launcher's worker needs it, so
the launcher run is the script's first act, before this process has
initialised a JAX backend; everything after runs in this process.
Nothing is written outside the compile cache and ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"
KERNEL = 'custom_call_target="tpu_custom_call"'
SEED = 0

# Llama-3-8B's block at its published widths is the llama3_8b builder's
# default (d 4096, 32 q / 8 kv heads of 128, MLP 14336, vocab 128256);
# only depth and parameter dtype are cut, as far as 16 GB forces:
# 32 layers in bf16 are 14.96 GiB of the chip's 15.75 GiB, which leaves
# no room for the KV cache, the logits and the init transients (~3 GiB
# while the 1 GiB lm_head is drawn). 24 layers are 11.7 GiB.
SERVE_BF16 = dict(
    name="llama3_8b_bf16", layers=24, quantized=False, extra={},
    cut="depth 32 -> 24 layers, params f32 -> bf16 (16 GB HBM); widths "
        "as published",
    slots=8, max_seq=256, requests=12, max_new=32,
    min_prompt=20, max_prompt=60, rate_hz=8.0)
# Weight-only int8 fits the chip at all 32 layers (7.5 GiB, run on the
# chip in PR 23), but not this script's time: from 8 layers on each
# int8 prefill program takes the TPU compiler ~45 s and more (2 s at
# 7), and at 32 the phase compiled for 1,085 s, cold. Depth is cut
# under that cliff; widths stay. (About 300 s of any cold run is the
# synthetic weights' six jit(_randint) programs, whatever the depth.)
SERVE_INT8 = dict(
    name="llama3_8b_int8", layers=4, quantized=True, extra={},
    cut="depth 32 -> 4 layers (compile time, not memory: 32 layers ran "
        "in PR 23 at 1,085 s of cold compile); int8 weights; widths as "
        "published",
    slots=8, max_seq=256, requests=8, max_new=16,
    min_prompt=20, max_prompt=60, rate_hz=8.0)
# one-chip batch 128; the presets' own global batches (1024, 256) are
# sized for eight chips, and ResNet's 1024 does not load on one
# (14.0 GB program, PR 23). Its lr 0.1 goes with that
# batch: at 128 it is scaled by 128/1024, without which the loss climbs
# for the first thirty steps (seen on the chip, PR 23).
TRAIN_RUNS = (
    dict(preset="resnet50_dp", steps=20,
         overrides={"data.batch_size": 128, "optim.lr": 0.0125}),
    dict(preset="bert_base_buckets", steps=10,
         overrides={"data.batch_size": 128}),
)
KERNEL_SIZES = dict(
    flash=dict(B=1, T=2048, H=32, Hkv=8, D=128),
    # (M, K, N): a decode round of 8 slots through the MLP up
    # projection and through the LM head
    int8=((8, 4096, 14336), (8, 4096, 128256)),
    quant_elems=25_557_032,  # ResNet-50's gradient, one bucket
)
# losses across chips against one chip: half a bf16 step (2^-8 = 0.4 %).
# Seen on four v5e chips in PR 23: 9e-5 (dp_explicit), 2e-5 (zero3).
PARALLEL = dict(preset="bert_base_buckets", steps=4, batch=128,
                rel_tol=2e-3)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Meter:
    """Compile seconds and persistent-cache traffic, from the program's
    own ``jax.monitoring`` listener (obs/jitwatch.py); ``since()`` gives
    one phase's share."""

    def __init__(self, cache_dir: str | None) -> None:
        from pytorch_distributed_nn_tpu.obs import jitwatch

        jitwatch.install()
        self._totals = jitwatch.process_totals
        self.cache_dir = cache_dir

    @property
    def compile_s(self) -> float:
        return self._totals()["compile_s"]

    @property
    def hits(self) -> int:
        return self._totals()["cache_hits"]

    @property
    def misses(self) -> int:
        return self._totals()["cache_misses"]

    def cache_entries(self) -> int:
        d = self.cache_dir
        if not d or not os.path.isdir(d):
            return 0
        return sum(1 for f in os.listdir(d) if f.endswith("-cache"))

    def mark(self) -> tuple:
        return (time.perf_counter(), self.compile_s, self.hits,
                self.misses, self.cache_entries())

    def since(self, mark: tuple) -> dict:
        t0, c0, h0, m0, e0 = mark
        return dict(
            seconds=round(time.perf_counter() - t0, 2),
            compile_s=round(self.compile_s - c0, 2),
            cache_hits=self.hits - h0, cache_misses=self.misses - m0,
            # negative where JAX_COMPILATION_CACHE_MAX_SIZE evicted
            cache_entries_delta=self.cache_entries() - e0)


def memory(dev) -> dict:
    """Device bytes now and the process's peak so far (the allocator's
    peak does not reset between phases). None where not reported."""
    ms = dev.memory_stats() or {}
    return dict(bytes_in_use=ms.get("bytes_in_use"),
                peak_bytes=ms.get("peak_bytes_in_use"))


def kernel_count(lowered) -> int:
    return lowered.compile().as_text().count(KERNEL)


def compiled_with_count(jitted, *args):
    """Compile once; the executable to call and its Pallas calls."""
    compiled = jitted.lower(*args).compile()
    return compiled, compiled.as_text().count(KERNEL)


def collective_counts(hlo_text: str) -> dict:
    """Collectives in a compiled program, sync or async form. The TPU
    compiler writes a reduce-scatter as a fusion that calls an
    ``%all-reduce-scatter`` computation, not as an op of that name."""
    counts = {op: hlo_text.count(f" {op}(") + hlo_text.count(
        f" {op}-start(") for op in ("all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all")}
    counts["reduce-scatter"] += hlo_text.count("calls=%all-reduce-scatter")
    return counts


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def train_argv(run: dict, metrics_path: Path) -> list[str]:
    args = ["--preset", run["preset"], "--steps", str(run["steps"]),
            "--log_every", "1", "--metrics_path", str(metrics_path)]
    for k, v in run["overrides"].items():
        args += [f"--{k}", str(v)]
    return args


def read_steps(metrics_path: Path) -> list[dict]:
    with open(metrics_path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    return [r for r in recs if r.get("event") == "train_step"]


def check_losses(steps: list[dict], want: int, what: str) -> list[float]:
    losses = [r["loss"] for r in steps]
    check(len(losses) == want, f"{what}: {len(losses)} of {want} steps "
                               f"logged")
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")
    # a few steps of SGD on fresh batches are noisy: compare the mean
    # of the last steps with the mean of the first
    k = min(5, len(losses) // 2)
    check(sum(losses[-k:]) < sum(losses[:k]),
          f"{what}: loss did not fall (mean of last {k} against first "
          f"{k}): {losses}")
    return losses


def steady_rate(steps: list[dict]) -> float | None:
    """Steps per second over the records after the second (the first
    holds the compile, the second may still wait on the loader)."""
    tail = [r["seconds"] for r in steps[2:]]
    return round(len(tail) / sum(tail), 3) if tail and sum(tail) else None


# ---------------------------------------------------------------------------
# launcher — first, while this process holds no backend
# ---------------------------------------------------------------------------

def phase_launcher(run: dict = TRAIN_RUNS[0], *, out: Path = OUT,
                   platform: str = "tpu",
                   timeout_s: float = 600.0) -> None:
    """``python -m pytorch_distributed_nn_tpu.launch --nprocs 1 --
    scripts/train.py``: agent, native store, heartbeats, one worker
    that takes the chip. The worker is pinned to ``platform`` so that a
    missing chip fails in seconds instead of training on the CPU."""
    from jax._src import xla_bridge

    check(not xla_bridge.backends_are_initialized(),
          "chip_smoke initialised a JAX backend before the launcher "
          "run: its worker could not take the chip")
    out.mkdir(parents=True, exist_ok=True)
    metrics = out / f"smoke_launch_{run['preset']}.jsonl"
    metrics.unlink(missing_ok=True)
    env = {**os.environ, "JAX_PLATFORMS": platform}
    cmd = [sys.executable, "-m", "pytorch_distributed_nn_tpu.launch",
           "--nprocs", "1", "--heartbeat-timeout", "300",
           "--flight-dir", str(out), "--",
           "scripts/train.py", *train_argv(run, metrics)]
    t0 = time.perf_counter()
    # own session: a timeout must take the agent AND its worker down
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE,
                            start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    seconds = time.perf_counter() - t0
    if proc.returncode != 0:
        sys.stderr.write(stderr[-4000:])
    check(proc.returncode == 0,
          f"launcher exited {proc.returncode} (stderr above)")
    final = [ln for ln in stdout.splitlines()
             if ln.startswith("final: step=")]
    check(len(final) == 1, f"no 'final: step=' line in {stdout!r}")
    steps = read_steps(metrics)
    losses = check_losses(steps, run["steps"], "launcher")
    check(not xla_bridge.backends_are_initialized(),
          "the launcher run initialised a backend in this process")
    emit("launcher", preset=run["preset"], overrides=run["overrides"],
         seconds=round(seconds, 2), final=final[0],
         # the worker's own clock: its first step holds the compile
         first_step_s=steps[0]["seconds"],
         steady_steps_per_s=steady_rate(steps),
         loss_first=losses[0], loss_last=losses[-1],
         worker_platform=platform,
         peak_bytes=None)  # another process's allocator: not measured


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device():
    import jax

    from pytorch_distributed_nn_tpu.runtime.device import require_tpu
    from pytorch_distributed_nn_tpu.utils.flops import peak_flops_per_chip

    dev = require_tpu()
    peak = peak_flops_per_chip(dev)  # raises for an unknown TPU kind
    ms = dev.memory_stats() or {}
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), peak_bf16_flops=peak,
         hbm_bytes=ms.get("bytes_limit"),
         jax=jax.__version__,
         cache_dir=jax.config.jax_compilation_cache_dir)
    return dev


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(meter: Meter, dev, sizes: dict = KERNEL_SIZES) -> None:
    """The three kernels of the main paths, through their dispatchers,
    each against the jnp reference of its own module. On a TPU the
    compiled call must hold the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu.ops.pallas import flash_attention as fa
    from pytorch_distributed_nn_tpu.ops.pallas import int8_matmul as i8
    from pytorch_distributed_nn_tpu.ops.pallas import quantize as qz

    mark = meter.mark()
    rng = np.random.default_rng(SEED)
    tpu = dev.platform == "tpu"
    out = {}

    # flash attention, forward and backward, GQA at Llama's head layout
    f = sizes["flash"]
    B, T, H, Hkv, D = f["B"], f["T"], f["H"], f["Hkv"], f["D"]
    q = jnp.asarray(rng.standard_normal((B, T, H, D)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)) * 0.3,
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.bfloat16)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], T, D)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def reference(q, k, v):
        kx, vx = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
        o = fa._attention_reference(to_bh(q), to_bh(kx), to_bh(vx),
                                    causal=True)
        return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    def with_grads(fn):
        def loss(q, k, v):
            o = fn(q, k, v).astype(jnp.float32)
            return (o * o).sum(), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    flash_vg, n_kernels = compiled_with_count(with_grads(flash), q, k, v)
    check(n_kernels >= 3 or not tpu,
          f"flash fwd+bwd holds {n_kernels} Pallas calls, want >= 3: "
          f"the reference branch ran")
    (_, o_got), g_got = flash_vg(q, k, v)
    ref_vg = with_grads(reference)
    (_, o_ref), g_ref = ref_vg(q, k, v)
    # the same values in f32 make the reference exact; in bf16 it
    # rounds its probabilities and their cotangent like the kernels do,
    # and the softmax backward's dp - delta amplifies that. So the
    # kernels are held to the bf16 reference's own distance from the
    # exact result (twice it, plus one bf16 step), not to a constant.
    (_, o_true), g_true = ref_vg(*(a.astype(jnp.float32)
                                   for a in (q, k, v)))
    err, err_ref = {}, {}
    for name, got, ref, true in zip(("out", "dq", "dk", "dv"),
                                    (o_got, *g_got), (o_ref, *g_ref),
                                    (o_true, *g_true)):
        got = got.astype(jnp.float32)
        check(bool(jnp.isfinite(got).all()), f"flash {name} not finite")
        scale = jnp.linalg.norm(true)
        err[name] = float(jnp.linalg.norm(got - true) / scale)
        err_ref[name] = float(
            jnp.linalg.norm(ref.astype(jnp.float32) - true) / scale)
        check(err[name] <= 2 * err_ref[name] + 2.0 ** -8,
              f"flash {name}: relative l2 error {err[name]:.4f} against "
              f"the bf16 reference's {err_ref[name]:.4f}")
    out["flash"] = dict(
        shape=f, tpu_custom_calls=n_kernels,
        rel_l2_err={n: round(e, 5) for n, e in err.items()},
        rel_l2_err_bf16_reference={n: round(e, 5)
                                   for n, e in err_ref.items()})

    # int8 dequant matmul at the decode shapes
    out["int8_matmul"] = []
    for (m, kk, n) in sizes["int8"]:
        w = jnp.asarray(rng.standard_normal((kk, n)) * 0.05, jnp.float32)
        x = jnp.asarray(rng.standard_normal((m, kk)), jnp.float32)
        qw, s = i8.quantize_weight(w)
        del w
        mm, n_kernels = compiled_with_count(
            jax.jit(lambda x, qw, s: i8.int8_matmul(
                x, qw, s, out_dtype=jnp.float32)), x, qw, s)
        check(n_kernels == 1 or not tpu,
              f"int8_matmul {(m, kk, n)} holds {n_kernels} Pallas "
              f"calls, want 1")
        got = mm(x, qw, s)[:, :n]
        want = i8._int8_matmul_reference(
            x, qw, s, out_dtype=jnp.float32)[:, :n]
        err = float(jnp.abs(got - want).max()
                    / (jnp.abs(want).max() + 1e-9))
        check(err < 2e-2, f"int8_matmul {(m, kk, n)}: rel err {err}")
        out["int8_matmul"].append(dict(
            mkn=(m, kk, n), tpu_custom_calls=n_kernels,
            rel_err=round(err, 6)))
        del qw, s

    # stochastic-rounding quantizer: the kernel draws from the chip's
    # PRNG and the reference from jax.random, so they agree in law, not
    # bit for bit: each value lands on one of its two neighbours, and
    # the rounding is unbiased
    n_el = sizes["quant_elems"]
    x = jnp.asarray(rng.standard_normal(n_el), jnp.float32)
    scale = float(jnp.abs(x).max() / 127.0)
    quant, n_kernels = compiled_with_count(
        jax.jit(lambda x: qz.quantize_int8(x, scale, seed=SEED)), x)
    check(n_kernels == 1 or not tpu,
          f"quantize_int8 holds {n_kernels} Pallas calls, want 1")
    stats = {}
    for name, qx in (("kernel", quant(x)),
                     ("reference", qz._quantize_reference(x, scale, SEED))):
        d = qx.astype(jnp.float32) - x / scale
        stats[name] = dict(max_abs=float(jnp.abs(d).max()),
                           bias=float(d.mean()))
    check(stats["kernel"]["max_abs"] < 1.0 + 1e-3,
          f"quantizer moved a value past its neighbours: {stats}")
    # the mean of n_el errors, each within (-1, 1) with variance <= 1/4
    bias_tol = 6 * 0.5 / n_el ** 0.5
    check(abs(stats["kernel"]["bias"]) < bias_tol,
          f"quantizer is biased: {stats} (tol {bias_tol})")
    out["quantize_int8"] = dict(elems=n_el, tpu_custom_calls=n_kernels,
                                **{f"{k}_{m}": round(val, 6)
                                   for k, st in stats.items()
                                   for m, val in st.items()})
    emit("kernels", **out, **meter.since(mark), **memory(dev))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def load_train_main():
    """``scripts/train.py``'s ``main`` — scripts/ is not a package."""
    spec = importlib.util.spec_from_file_location(
        "tpunn_scripts_train", ROOT / "scripts" / "train.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def phase_train(meter: Meter, dev, run: dict, *, out: Path = OUT) -> None:
    """A few steps through ``scripts/train.py``'s ``main``; then the
    same configuration's step is lowered once more to read how many
    Pallas kernels the compiled program holds (a cache hit)."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    mark = meter.mark()
    out.mkdir(parents=True, exist_ok=True)
    metrics = out / f"smoke_train_{run['preset']}.jsonl"
    metrics.unlink(missing_ok=True)
    rc = load_train_main()(train_argv(run, metrics))
    check(rc == 0, f"train main returned {rc}")
    gc.collect()  # main's Trainer is garbage: free its device state
    steps = read_steps(metrics)
    losses = check_losses(steps, run["steps"], run["preset"])
    ran = meter.since(mark)

    count_mark = meter.mark()
    # the same configuration main built, or the step differs (the lr
    # schedule's horizon is a constant of the program) and recompiles
    args = parse_overrides(train_argv(run, metrics))
    cfg = get_config(args.pop("preset"), **{
        k: v for k, v in args.items() if k != "metrics_path"})
    with Trainer(cfg) as trainer:
        x, y = trainer.loader.batch_at(0)
        n_kernels = kernel_count(trainer.step_fn.lower(
            trainer.state, x, y))
        n_params = sum(p.size for p in jax.tree.leaves(
            trainer.state.params))
    # nn/attention.py picks flash from T 1024 and BatchNorm's stats
    # default to XLA: neither ResNet-50 nor BERT at T 128 holds a kernel
    check(n_kernels == 0, f"{run['preset']}: {n_kernels} Pallas calls "
                          f"in the step, the routing rules say 0")
    del trainer, x, y
    gc.collect()
    batch = cfg.data.batch_size
    rate = steady_rate(steps)
    emit("train", preset=run["preset"], overrides=run["overrides"],
         params=n_params, steps=len(steps),
         loss_first=losses[0], loss_last=losses[-1],
         steady_steps_per_s=rate,
         steady_samples_per_s=round(rate * batch, 1) if rate else None,
         tpu_custom_calls={"train_step": n_kernels},
         # the second Trainer and its lowering, apart from the run
         kernel_count=meter.since(count_mark),
         **ran, **memory(dev))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def build_llama(spec: dict):
    """The llama3_8b builder at its defaults, less ``spec``'s cut of
    depth and dtype (``extra`` shrinks widths for the CPU rehearsal
    only). Returns (model, params)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.nn.quantized import (
        synthetic_int8_params,
    )

    cfg = get_config("llama3_8b_zero")
    cfg.model.remat = False
    cfg.model.dtype = "bfloat16"
    cfg.model.extra = {"num_layers": spec["layers"], **spec["extra"]}
    if spec["quantized"]:
        cfg.model.extra["quantized"] = True
    model = get_model(cfg.model)
    if spec["quantized"]:
        # what examples/int8_8b_inference.py does: there is no float
        # 8B to quantize in a sealed machine
        params = synthetic_int8_params(
            model, jnp.zeros((1, 1), jnp.int32), seed=SEED)
    else:
        # what scripts/serve.py does without a checkpoint
        params = model.init(jax.random.key(SEED),
                            jnp.zeros((1, 8), jnp.int32),
                            train=False)["params"]
    return model, params


def reference_tokens(model, params, prompt, max_new: int):
    """Sequential ``inference.generate`` of one prompt, left-padded to
    the engine's bucket so that a dozen ragged prompts share a few
    compiled programs instead of one pair each."""
    import numpy as np

    from pytorch_distributed_nn_tpu.inference.generate import generate
    from pytorch_distributed_nn_tpu.serve.engine import _bucket_len

    n = len(prompt)
    pad = _bucket_len(n)
    row = np.zeros((1, pad), np.int32)
    row[0, pad - n:] = prompt
    out = generate(model, params, row, max_new, prompt_lengths=[n])
    return np.asarray(out)[0, pad:]


def logit_margin(model, params, prefix, tok_a: int, tok_b: int):
    """|logit[a] - logit[b]| for the token after ``prefix`` under the
    reference's prefill, and the largest |logit| there."""
    import numpy as np

    from pytorch_distributed_nn_tpu.inference.generate import (
        init_cache,
        prefill_ragged,
    )
    from pytorch_distributed_nn_tpu.serve.engine import _bucket_len

    n = len(prefix)
    pad = _bucket_len(n)
    row = np.zeros((1, pad), np.int32)
    row[0, :n] = prefix  # prefill_ragged takes left-aligned rows
    logits, _ = prefill_ragged(model, params, init_cache(model, 1, pad),
                               row, np.asarray([n], np.int32))
    lg = np.asarray(logits[0], np.float32)
    return float(abs(lg[tok_a] - lg[tok_b])), float(np.abs(lg).max())


def serve_kernel_counts(model, params, engine, buckets) -> dict:
    """Pallas calls in the decode step and in each prefill bucket the
    run used, from the engine's own jits (compile-cache hits)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.serve import engine as eng

    def shape_of(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def vec(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype)

    p = shape_of(params)
    slots, max_seq = engine.max_slots, engine.max_seq_len
    counts = {"decode_step": kernel_count(eng._serve_step.lower(
        model, p, jax.eval_shape(lambda: init_cache(model, slots,
                                                    max_seq)),
        vec(slots), vec(slots), vec(slots, jnp.bool_), vec(slots),
        jax.ShapeDtypeStruct((), jnp.int32)))}
    for b in sorted(buckets):
        counts[f"prefill_{b}"] = kernel_count(eng._serve_prefill.lower(
            model, p, jax.eval_shape(lambda: init_cache(model, 1, b)),
            jax.ShapeDtypeStruct((1, b), jnp.int32), vec(1), vec(1)))
    return counts


def beside_serve_loop(server, client, timeout_s: float = 600.0):
    """``client()``'s result. The repo's clients wait on their requests
    without a limit, and a serve loop that died (its thread prints the
    traceback) completes none: so the client runs beside a watch on the
    loop, and a dead loop fails the phase instead of hanging it."""
    box = {}
    thread = threading.Thread(target=lambda: box.update(out=client()),
                              name="smoke-client", daemon=True)
    thread.start()
    deadline = time.monotonic() + timeout_s
    while thread.is_alive():
        check(server._thread.is_alive(), "the serve loop died")
        check(time.monotonic() < deadline, "serving timed out")
        thread.join(0.05)
    check("out" in box, "the client raised (traceback above)")
    return box["out"]


def phase_serve(meter: Meter, dev, spec: dict) -> None:
    """``scripts/serve.py``'s path — ServingEngine behind
    InferenceServer under the open-loop client — then every request's
    greedy tokens against sequential ``inference.generate``."""
    import jax
    import numpy as np

    from pytorch_distributed_nn_tpu.serve import (
        InferenceServer,
        ServingEngine,
        open_loop_client,
        ragged_prompt_sampler,
    )
    from pytorch_distributed_nn_tpu.serve.engine import _bucket_len

    mark = meter.mark()
    model, params = build_llama(spec)
    jax.block_until_ready(params)
    param_bytes = sum(p.size * p.dtype.itemsize
                      for p in jax.tree.leaves(params))
    init = meter.since(mark)
    vocab = model.vocab_size
    engine = ServingEngine(model, params, max_slots=spec["slots"],
                           max_seq_len=spec["max_seq"], block_size=16,
                           max_queue=64, max_prefills_per_round=2)
    server = InferenceServer(engine).start()
    try:
        # warm every prefill bucket the sampler can hit and the decode
        # step, as scripts/serve.py does, so the timed window holds no
        # compile
        warm_rng = np.random.default_rng(SEED)
        b = _bucket_len(spec["min_prompt"])
        while b <= _bucket_len(spec["max_prompt"]):
            prompt = warm_rng.integers(0, vocab, size=(
                min(b, spec["max_seq"] - 2),)).astype(np.int32)
            beside_serve_loop(server, lambda: server.generate(
                prompt, 2, timeout=600.0))
            b *= 2
        warm = meter.since(mark)
        warm_done, warm_rounds = (len(engine.completed),
                                  len(engine.round_seconds))
        window = meter.mark()
        sampler = ragged_prompt_sampler(
            vocab, min_len=spec["min_prompt"],
            max_len=spec["max_prompt"], seed=SEED)
        reqs = beside_serve_loop(server, lambda: open_loop_client(
            server, num_requests=spec["requests"],
            rate_hz=spec["rate_hz"], max_new_tokens=spec["max_new"],
            prompt_sampler=sampler))
        served = meter.since(window)
    finally:
        server.stop()

    for r in reqs:
        check(r.state == "done", f"{r.request_id}: {r.state} "
                                 f"{r.reject_reason}")
        check(len(r.tokens) == spec["max_new"], f"{r.request_id}: "
              f"{len(r.tokens)} tokens of {spec['max_new']}")
        check(bool(((r.tokens >= 0) & (r.tokens < vocab)).all()),
              f"{r.request_id}: token outside the vocabulary")
    timed = engine.completed[warm_done:]
    rounds = engine.round_seconds[warm_rounds:]
    tokens_out = sum(c["new_tokens"] for c in timed)

    # greedy tokens against sequential generate. serve/engine.py
    # promises bit-identity and the CPU keeps it; the chip does not
    # (PR 23: 9 of 12 bf16 requests leave the sequential path), because
    # the two paths run the same row through programs of other shapes.
    # They must still have been choosing between near-equal logits:
    # the first divergent position's margin is gated at 2 bf16 steps
    # (2^-7) of the largest logit there — activations are bf16, and the
    # chip's margins stayed under half a step.
    ref_mark = meter.mark()
    identical, diverged = 0, []
    for r in reqs:
        ref = reference_tokens(model, params, r.prompt, spec["max_new"])
        diff = np.nonzero(ref != r.tokens)[0]
        if diff.size == 0:
            identical += 1
            continue
        i = int(diff[0])
        margin, top = logit_margin(
            model, params, np.concatenate([r.prompt, ref[:i]]),
            int(ref[i]), int(r.tokens[i]))
        diverged.append(dict(request=r.request_id, position=i,
                             margin=round(margin, 6),
                             max_logit=round(top, 4)))
        check(margin <= 2.0 ** -7 * top,
              f"{r.request_id} leaves sequential generate at token {i} "
              f"with logit margin {margin} (largest logit {top}): more "
              f"than bf16 rounding explains")
    reference = meter.since(ref_mark)

    buckets = {_bucket_len(len(r.prompt)) for r in reqs}
    counts = serve_kernel_counts(model, params, engine, buckets)
    if spec["quantized"] and dev.platform == "tpu":
        # int8_matmul has no shape condition: q, k, v, o, gate, up and
        # down of every layer, and the LM head
        want = 7 * spec["layers"] + 1
        check(all(c == want for c in counts.values()),
              f"int8 programs hold {counts} dequant matmuls, want {want}")
    emit("serve", model=spec["name"], cut=spec["cut"],
         layers=spec["layers"], param_bytes=param_bytes,
         init_s=init["seconds"], init_compile_s=init["compile_s"],
         warmup_s=round(warm["seconds"] - init["seconds"], 2),
         warmup_compile_s=warm["compile_s"],
         warmup_cache_hits=warm["cache_hits"],
         warmup_cache_misses=warm["cache_misses"],
         requests=len(reqs), completed=len(timed),
         prompt_lens=[len(r.prompt) for r in reqs],
         new_tokens=spec["max_new"], slots=spec["slots"],
         window_s=served["seconds"],
         # compiles inside the timed window: the warm-up means 0
         window_compile_s=served["compile_s"],
         window_cache_misses=served["cache_misses"],
         tokens_per_s=round(tokens_out / served["seconds"], 2),
         decode_round_median_s=float(np.median(rounds)),
         ttft_median_s=float(np.median([c["ttft_s"] for c in timed])),
         identical_to_generate=identical, diverged=diverged,
         reference_s=reference["seconds"],
         reference_compile_s=reference["compile_s"],
         tpu_custom_calls=counts,
         **meter.since(mark), **memory(dev))
    del engine, server, params, reqs
    gc.collect()


# ---------------------------------------------------------------------------
# --chips 4: the path across chips, and what it is compared with
# ---------------------------------------------------------------------------

def phase_parallel(meter: Meter, dev, spec: dict = PARALLEL,
                   n_devices: int = 4) -> None:
    """BERT-base under dp_explicit (bucketed psum) and under zero
    stage 3 on an ``n_devices`` mesh, each against the same seed and
    global batch on one of those devices in this process."""
    import jax
    import numpy as np

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.runtime.mesh import make_mesh
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    devices = jax.devices()
    check(len(devices) == n_devices,
          f"--chips {n_devices} needs {n_devices} devices, JAX reports "
          f"{len(devices)}")
    strategies = {
        "dp_explicit": {"parallel.strategy": "dp_explicit"},
        "zero3": {"parallel.strategy": "zero", "parallel.zero_stage": "3",
                  "mesh.fsdp": "-1", "mesh.data": "1"},
    }
    base = {"data.batch_size": str(spec["batch"]),
            "steps": str(spec["steps"]), "log_every": "1",
            **spec.get("overrides", {})}

    def placed_on(tree) -> tuple[int, int]:
        """(leaves with shards on every device, leaves whose shards are
        smaller than the leaf) over the leaves worth sharding."""
        spread = split = 0
        for leaf in jax.tree.leaves(tree):
            if not hasattr(leaf, "addressable_shards") or leaf.ndim < 2:
                continue
            shards = leaf.addressable_shards
            if len({s.device for s in shards}) == n_devices:
                spread += 1
            if all(s.data.size < leaf.size for s in shards):
                split += 1
        return spread, split

    for name, over in strategies.items():
        mark = meter.mark()
        cfg = get_config(spec["preset"], **base, **over)
        runs = {}
        for label, devs in (("mesh", devices), ("one", devices[:1])):
            mesh = make_mesh(cfg.mesh.resolve(len(devs)), devices=devs)
            with Trainer(cfg, mesh=mesh) as trainer:
                x, y = trainer.loader.batch_at(0)
                info = dict(batch_devices=len(
                    {s.device for s in x.addressable_shards}))
                if label == "mesh":
                    text = trainer.step_fn.lower(
                        trainer.state, x, y).compile().as_text()
                    info["collectives"] = collective_counts(text)
                    info["tpu_custom_calls"] = text.count(KERNEL)
                    info["params_spread_split"] = placed_on(
                        trainer.state.params)
                    info["opt_spread_split"] = placed_on(
                        trainer.state.opt_state)
                    info["n_big_leaves"] = sum(
                        1 for p in jax.tree.leaves(trainer.state.params)
                        if p.ndim >= 2)
                trainer.train()
                info["losses"] = trainer.losses()
            runs[label] = info
            del trainer, x, y
            gc.collect()
        got, want = runs["mesh"], runs["one"]
        check(got["batch_devices"] == n_devices,
              f"{name}: the batch sits on {got['batch_devices']} "
              f"device(s), not {n_devices}")
        check(len(got["losses"]) == spec["steps"]
              and np.isfinite(got["losses"]).all(),
              f"{name}: losses {got['losses']}")
        rel = [abs(a - b) / abs(b)
               for a, b in zip(got["losses"], want["losses"])]
        check(max(rel) <= spec["rel_tol"],
              f"{name}: {n_devices}-device losses {got['losses']} leave "
              f"one-device losses {want['losses']} by {max(rel):.2e} "
              f"(> {spec['rel_tol']})")
        n_big = got["n_big_leaves"]
        c = got["collectives"]
        spread, split = got["params_spread_split"]
        check(spread == n_big, f"{name}: {spread} of {n_big} param "
              f"leaves have shards on all {n_devices} devices")
        if name == "zero3":
            o_spread, o_split = got["opt_spread_split"]
            check(split > 0 and o_split > 0,
                  f"zero3 sharded {split} param and {o_split} optimizer "
                  f"leaves: everything is replicated")
            # the CPU compiler leaves the gradient sum an all-reduce
            # and slices it; the TPU's turns that into reduce-scatter
            scatter = ("reduce-scatter" if dev.platform == "tpu"
                       else "all-reduce")
            check(c["all-gather"] > 0 and c[scatter] > 0,
                  f"zero3 step holds {c}: want all-gather and {scatter}")
        else:
            check(split == 0, f"dp_explicit split {split} param leaves")
            check(c["all-reduce"] > 0,
                  f"dp_explicit step holds {c}: want all-reduce")
        emit("parallel", strategy=name, preset=spec["preset"],
             devices=n_devices, global_batch=spec["batch"],
             losses=got["losses"], losses_one_device=want["losses"],
             max_rel_diff=max(rel), rel_tol=spec["rel_tol"],
             collectives=c, tpu_custom_calls=got["tpu_custom_calls"],
             param_leaves=n_big, params_on_all_devices=spread,
             params_split=split,
             opt_on_all_devices=got["opt_spread_split"][0],
             opt_split=got["opt_spread_split"][1],
             batch_devices=got["batch_devices"],
             **meter.since(mark), **memory(dev))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the dp_explicit/zero comparison on four "
                         "chips, and no other phase")
    args = ap.parse_args(argv)

    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked and "tpu" not in asked.split(","):
        # fail before anything is spawned or compiled for a CPU
        print(f"chip_smoke: JAX_PLATFORMS={asked!r} names no TPU",
              file=sys.stderr)
        return 2

    from pytorch_distributed_nn_tpu.runtime.device import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_launcher()
    meter = Meter(cache_dir)
    dev = phase_device()
    if args.chips == 4:
        phase_parallel(meter, dev)
    else:
        phase_kernels(meter, dev)
        for run in TRAIN_RUNS:
            phase_train(meter, dev, run)
        for spec in (SERVE_BF16, SERVE_INT8):
            phase_serve(meter, dev, spec)
    import jax

    emit("total", seconds=round(time.perf_counter() - t0, 2),
         compile_s=round(meter.compile_s, 2), cache_hits=meter.hits,
         cache_misses=meter.misses, **memory(dev))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
