"""Training cells: the program's ``Trainer`` for a window of seconds.

Built as ``scripts/train.py`` builds it: ``get_config(preset,
**overrides)`` and ``Trainer(cfg)``, the mesh the preset resolves on
the chips present, batches from the program's host loader. The window
calls ``trainer.train(steps=k)`` (the loop users run; it ends on a
``block_until_ready``) until the seconds are up.

What the benchmark adds: the initial parameters, from ``--seed``
(``weights.py``), laid out as the trainer laid out its own; and the
readings of the first three steps that ``check.py`` compares with the
plain reference. Set-up builds one trainer, drives it through those
three steps by the window's own call, and hands the same object on.
"""

from __future__ import annotations

import time

from benchmark.lib import weights
from benchmark.lib.common import log

ADAM_B1 = 0.9  # the preset's OptimConfig.b1; checked against it below


def build(cfg: dict, ref, traf: dict, seed: int, n_chips: int, phases):
    """The trainer with the benchmark's weights in place of its own."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    batch = int(traf["per_chip_batch"]) * n_chips
    over = {"data.batch_size": batch, "data.seq_len": int(traf["seq_len"]),
            "steps": int(traf["schedule_steps"]), "seed": int(seed),
            **traf.get("overrides", {})}
    pc = get_config(cfg["program"]["preset"], **over)
    pc.data.vocab_size = int(cfg["vocab_size"])
    pc.model.extra = dict(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        ln_eps=cfg["layer_norm_eps"])
    if pc.optim.b1 != ADAM_B1:
        raise SystemExit("benchmark: the preset's b1 changed; the first "
                         "gradient is read from Adam's first moment")
    trainer = Trainer(pc)
    if len(trainer.mesh.devices.flat) != n_chips:
        raise SystemExit(f"benchmark: the trainer's mesh has "
                         f"{len(trainer.mesh.devices.flat)} devices, the "
                         f"cell asks for {n_chips}")
    phases.close("trainer")
    spec = ref.param_spec(cfg)
    params = weights.tree(seed, spec)
    weights.check_layout(params, jax.eval_shape(
        lambda: trainer.state.params))
    placed = jax.tree.map(
        lambda new, old: jax.device_put(new, old.sharding),
        params, trainer.state.params)
    trainer.state = trainer.state.replace(params=placed)
    jax.block_until_ready(trainer.state.params)
    del params
    phases.close("weights")
    return trainer, batch


def _leaf_norms(tree_a, tree_b=None) -> dict:
    """Per-leaf l2 norms of ``a`` (or of ``a - b``), in float32, by the
    leaf's ``/``-joined name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        if b is None:
            return jax.tree.map(
                lambda x: jnp.linalg.norm(x.astype(jnp.float32)), a)
        return jax.tree.map(
            lambda x, y: jnp.linalg.norm(
                x.astype(jnp.float32) - y.astype(jnp.float32)), a, b)

    out = jax.device_get(norms(tree_a, tree_b))
    return {name: float(v) for name, v in weights.named_leaves(out).items()}


def _first_moment(opt_state):
    """Adam's ``mu`` inside the optimizer state, wherever optax nests it."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise SystemExit("benchmark: expected one Adam first moment in "
                         f"the optimizer state, found {len(found)}")
    return found[0]


def first_steps(trainer, cfg: dict, ref, seed: int, phases) -> dict:
    """Three steps through ``trainer.train``; what the check compares:
    each step's loss, the first gradient's norm by leaf as the optimizer
    got it (Adam's first moment after one step is (1 - b1) g), and the
    norm by leaf of the parameters' change after the three."""
    import jax

    losses = []
    trainer.train(steps=1)
    losses.append(float(jax.device_get(trainer.last_metrics["loss"])))
    mu = _leaf_norms(_first_moment(trainer.state.opt_state))
    grad_norms = {k: v / (1.0 - ADAM_B1) for k, v in mu.items()}
    for _ in range(2):
        trainer.train(steps=1)
        losses.append(float(jax.device_get(trainer.last_metrics["loss"])))
    start = weights.tree(seed, ref.param_spec(cfg))
    start = jax.tree.map(lambda new, old: jax.device_put(new, old.sharding),
                         start, trainer.state.params)
    delta = _leaf_norms(trainer.state.params, start)
    del start
    phases.close("first_steps")
    return dict(losses=losses, grad_norms=grad_norms, delta_norms=delta)


def run_window(trainer, traf: dict, batch: int, seconds: float,
               tracer=None) -> dict:
    """``trainer.train(steps=k)`` until the seconds are up. The data
    wait is read from the trainer's own goodput meter (host clock around
    the loader's ``next``)."""
    k = int(traf["steps_per_call"])
    gp0 = trainer.goodput.summary() if trainer.goodput.steps else None
    step0 = trainer.data_step
    setup_done = time.perf_counter()
    t0 = time.monotonic()
    if tracer is not None:
        tracer.start_in_background(t0, seconds)
    calls = []
    while time.monotonic() - t0 < seconds:
        c0 = time.monotonic()
        trainer.train(steps=k)
        calls.append(time.monotonic() - c0)
    t1 = time.monotonic()
    if tracer is not None:
        tracer.join()
    gp1 = trainer.goodput.summary()
    steps = trainer.data_step - step0
    log(f"window: {steps} steps of batch {batch} in {t1 - t0:.3f} s "
        f"({len(calls)} calls of {k})")
    return dict(kind="train", t0=t0, t1=t1, setup_done=setup_done,
                steps=steps, batch=batch, call_seconds=calls,
                goodput_before=gp0, goodput_after=gp1)
