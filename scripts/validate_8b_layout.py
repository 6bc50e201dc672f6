#!/usr/bin/env python
"""AOT-validate the TRUE Llama-3-8B config-5 layout on a virtual
v5e-16 topology (VERDICT.md round-1 Missing #5 / Next #6).

No pod is available here, so nothing is executed: the full 8B train
step (ZeRO-3 + remat, the real ``llama3_8b_zero`` preset) is lowered
and compiled for a 16-device mesh of virtual CPU devices with every
input abstract — zero bytes of parameters materialize. The compile
proves the SPMD partitioner accepts the layout (sharding propagation,
collective insertion) and its buffer assignment pins the per-chip
STATE bytes exactly (params + optimizer moments, dtype- and
sharding-exact).

The fits-in-HBM verdict uses those exact state bytes plus an ANALYTIC
activation model for the TPU execution path (remat boundaries + flash
attention + chunked xent). The CPU compile's temp bytes are reported
too but only as a non-representative upper bound: the CPU lowering
runs DENSE attention (no Pallas flash on host) and schedules for
speed, not memory — round 2's first full-8B compile measured 208 GiB
of CPU temps against a ~6 GiB analytic TPU activation peak, almost
all of it (B, H, T, T) dense-attention scores that the TPU path never
materializes.

Usage:
    python scripts/validate_8b_layout.py [--devices 16] [--hbm-gb 16]
        [--analytic-only] [--out LAYOUT_8B.json]
        [--a.b config overrides ...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")  # run from repo root without install


def analytic_activation_bytes(cfg, *, n_batch_shards: int,
                              layer_params: int) -> dict:
    """Per-chip activation/transient bytes of the TPU execution path.

    Model: remat keeps only per-layer boundary activations live through
    the backward; inside the one layer being recomputed, flash
    attention is O(B*T*d) (never (T, T) scores) and the SwiGLU MLP
    holds two (B, T, ff) intermediates; the loss keeps one
    (B, chunk, V) f32 logits block + its cotangent; ZeRO-3 keeps the
    current + prefetched layer's gathered params in compute dtype; the
    gradient tree adds one sharded f32 copy of the params plus one
    layer's unsharded f32 transient before its reduce-scatter.
    """
    e = cfg.model.extra
    L = e.get("num_layers", 32)
    d = e.get("d_model", 4096)
    ff = e.get("mlp_dim", 14336)
    V = e.get("vocab_size", cfg.data.vocab_size)
    B, T = cfg.data.batch_size, cfg.data.seq_len
    accum = max(cfg.parallel.grad_accum, 1)
    comp = 2  # bf16 compute dtype bytes
    B_loc = max(B // (n_batch_shards * accum), 1)
    chunk = min(cfg.xent_chunk or T, T)
    return {
        "boundary_acts": L * B_loc * T * d * comp,
        "layer_recompute_peak": B_loc * T * max(4 * d, 2 * ff) * comp,
        "logits_block": 2 * B_loc * chunk * V * 4,  # fwd + cotangent
        "gathered_layer_params": 2 * layer_params * comp,
        "layer_grad_transient": layer_params * 4,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=16)
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-chip HBM budget (v5e: 16)")
    ap.add_argument("--analytic-only", action="store_true",
                    help="skip the compile (exact-state bytes then come "
                         "from the sharding math alone)")
    ap.add_argument("--out", default="",
                    help="also write the result JSON here")
    args, rest = ap.parse_known_args(argv)

    import jax

    # virtual topology BEFORE any backend use
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.devices)

    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.parallel.zero import (
        lower_zero_train_step,
    )
    from pytorch_distributed_nn_tpu.runtime.mesh import make_mesh
    from pytorch_distributed_nn_tpu.train.losses import get_loss_fn
    from pytorch_distributed_nn_tpu.train.optim import make_optimizer
    from pytorch_distributed_nn_tpu.train.state import TrainState

    cfg = get_config("llama3_8b_zero", **parse_overrides(rest))
    mesh = make_mesh(cfg.mesh.resolve(args.devices))
    model = get_model(cfg.model)
    tx = make_optimizer(cfg.optim, total_steps=cfg.steps)
    loss_fn = get_loss_fn(cfg.data.dataset)

    B, T = cfg.data.batch_size, cfg.data.seq_len
    x_spec = jax.ShapeDtypeStruct((B, T), jnp.int32)
    y_spec = jax.ShapeDtypeStruct((B, T), jnp.int32)

    def abstract_state():
        variables = model.init(jax.random.key(0),
                               jnp.zeros((1, T), jnp.int32), train=False)
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"], tx=tx,
            rng=jax.random.key(1),
        )

    t0 = time.time()
    state = jax.eval_shape(abstract_state)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(state.params))
    print(f"# abstract state built: {n_params / 1e9:.2f}B params "
          f"({time.time() - t0:.0f}s)", file=sys.stderr)

    # ---- exact per-chip STATE bytes from the actual shardings --------
    from pytorch_distributed_nn_tpu.parallel.zero import state_shardings
    from pytorch_distributed_nn_tpu.runtime.mesh import data_axis_size

    shardings = state_shardings(state, mesh,
                                stage=cfg.parallel.zero_stage)

    def shard_bytes(leaf, sh):
        local = sh.shard_shape(tuple(leaf.shape))
        return int(np.prod(local or (1,))) * leaf.dtype.itemsize

    state_b = sum(
        shard_bytes(leaf, sh) for leaf, sh in zip(
            jax.tree.leaves(state), jax.tree.leaves(shardings)
        )
    )

    # one decoder layer's param count (for gather/grad transients)
    layer_params = sum(
        int(np.prod(leaf.shape))
        for path, leaf in
        jax.tree_util.tree_flatten_with_path(state.params)[0]
        if any(getattr(k, "key", "") == "layer0" for k in path)
    )

    acts = analytic_activation_bytes(
        cfg, n_batch_shards=data_axis_size(mesh),
        layer_params=layer_params,
    )
    grads_shard_b = sum(
        int(np.prod(sh.shard_shape(tuple(leaf.shape)) or (1,))) * 4
        for leaf, sh in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(shardings.params))
    )
    analytic_b = state_b + grads_shard_b + sum(acts.values())
    budget = args.hbm_gb * (1 << 30)

    def gib(b):
        return round(b / (1 << 30), 3)

    rec = {
        "metric": "llama3-8b zero-3 per-chip memory (AOT, virtual "
                  f"{args.devices}-chip mesh)",
        "value": gib(analytic_b),
        "unit": "GiB/chip",
        "vs_baseline": round(analytic_b / budget, 3),
        "n_params_b": round(n_params / 1e9, 3),
        "state_exact_gib": gib(state_b),
        "grads_shard_gib": gib(grads_shard_b),
        "activations_gib": {k: gib(v) for k, v in acts.items()},
        "hbm_budget_gib": args.hbm_gb,
        "fits": bool(analytic_b <= budget),
        "mesh": dict(mesh.shape),
        "batch_global": B, "seq_len": T,
        "xent_chunk": cfg.xent_chunk, "remat": cfg.model.remat,
        "grad_accum": max(cfg.parallel.grad_accum, 1),
    }

    # ---- AOT compile: SPMD-layout proof + state-bytes cross-check ----
    if not args.analytic_only:
        lowered = lower_zero_train_step(
            mesh, loss_fn, state, x_spec, y_spec,
            stage=cfg.parallel.zero_stage,
            accum=max(cfg.parallel.grad_accum, 1),
        )
        print(f"# lowered ({time.time() - t0:.0f}s); compiling (SPMD "
              f"partitioning + buffer assignment)...", file=sys.stderr)
        mem = lowered.compile().memory_analysis()
        print(f"# compiled OK ({time.time() - t0:.0f}s)", file=sys.stderr)
        arg_b = int(mem.argument_size_in_bytes)
        batch_b = 2 * B * T * 4 // max(data_axis_size(mesh), 1)
        rec["compiled"] = {
            "spmd_partitioning": "ok",
            "argument_gib": gib(arg_b),
            "output_gib": gib(int(mem.output_size_in_bytes)),
            "cpu_temp_gib_upper_bound": gib(int(mem.temp_size_in_bytes)),
            "note": "CPU lowering: dense attention + speed-first "
                    "scheduling; temp bytes are NOT the TPU activation "
                    "footprint (see module docstring)",
        }
        # arguments = state + the two token batches; cross-check the
        # sharding math against the compiler's buffer assignment
        drift = abs(arg_b - (state_b + batch_b)) / max(arg_b, 1)
        rec["compiled"]["state_bytes_drift"] = round(drift, 4)
        if drift > 0.02:
            print(f"# WARNING: sharding-math state bytes differ from "
                  f"compiler argument bytes by {drift:.1%}",
                  file=sys.stderr)

    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    if not rec["fits"]:
        print(f"# LAYOUT DOES NOT FIT: {gib(analytic_b)} GiB/chip > "
              f"{args.hbm_gb} GiB", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
