"""The Trainer: config → mesh → model → data → strategy → step loop.

This is the counterpart of the reference's per-strategy ``train.py``
drivers collapsed into one driver (SURVEY.md §1 Entrypoints row): the
hot loop is one jit-compiled step; everything else (logging cadence,
checkpointing, metrics host-sync) happens off the critical path.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any

import jax
import numpy as np

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.config import TrainConfig
from pytorch_distributed_nn_tpu.data import DataLoader, get_dataset
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.obs import aggregate as obs_aggregate
from pytorch_distributed_nn_tpu.obs import flight
from pytorch_distributed_nn_tpu.obs import runtime_gauges
from pytorch_distributed_nn_tpu.obs import watchtower
from pytorch_distributed_nn_tpu.obs import xray
from pytorch_distributed_nn_tpu.ops import collectives as cc
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.runtime import failure
from pytorch_distributed_nn_tpu.parallel import make_train_step
from pytorch_distributed_nn_tpu.runtime.mesh import make_mesh
from pytorch_distributed_nn_tpu.train.losses import get_loss_fn
from pytorch_distributed_nn_tpu.train.optim import make_optimizer
from pytorch_distributed_nn_tpu.train.state import TrainState, param_count

log = logging.getLogger(__name__)


@dataclasses.dataclass
class StepRecord:
    step: int
    loss: float
    seconds: float


@dataclasses.dataclass
class EvalRecord:
    step: int
    loss: float
    accuracy: float


# Eval batches come from the SAME (seed, step)-keyed generator as
# training — same class templates / token process, i.e. the same task —
# but from a step range training can never reach, so the samples are
# held out. (A different *seed* would change the templates themselves:
# a different task, on which no trained model can score.) File-backed
# datasets additionally honor data.holdout_frac for a true row/token
# split — see data/datasets.py.
from pytorch_distributed_nn_tpu.data.datasets import (
    EVAL_STEP_OFFSET as _EVAL_STEP_OFFSET,
)


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None) -> None:
        self.cfg = cfg
        # chaos engine (TPUNN_CHAOS): armed once per process, inert and
        # allocation-free on the step path when the env is unset
        chaos.maybe_init()
        # watchtower (TPUNN_WATCH): online anomaly/SLO detection over
        # the hooks below — same inert-when-unset contract as chaos
        watchtower.maybe_init()
        # xray (TPUNN_XRAY): anomaly-triggered device profiling; pages
        # raised by the tower above start bounded captures
        xray.maybe_init()
        self._preemptible = False
        self.mesh = mesh if mesh is not None else make_mesh(
            cfg.mesh.resolve(len(jax.devices()))
        )
        # sequence parallelism: model-level ring attention builds its
        # nested shard_map against the ambient mesh — scoped per call
        # (a process-global set_mesh would leak into unrelated code)
        self._seq_parallel = self.mesh.shape.get("seq", 1) > 1
        self.dataset = get_dataset(
            cfg.data.dataset,
            seed=cfg.seed,
            batch_size=cfg.data.batch_size,
            seq_len=cfg.data.seq_len,
            vocab_size=cfg.data.vocab_size,
            path=cfg.data.path,
            token_dtype=cfg.data.token_dtype,
            sample=cfg.data.sample,
            holdout_frac=cfg.data.holdout_frac,
            image_size=cfg.data.image_size,
            num_workers=cfg.data.num_workers,
        )
        self.loader = DataLoader(self.dataset, self.mesh,
                                 prefetch=cfg.data.prefetch)
        self.loss_fn = get_loss_fn(
            cfg.data.dataset, label_smoothing=cfg.label_smoothing
        )
        self.model = get_model(cfg.model)
        self.state = self._init_state()
        step_fn, place_fn = make_train_step(cfg, self.mesh, self.loss_fn,
                                            model=self.model)
        if self._seq_parallel:
            step_fn = self._with_mesh(step_fn)
            place_fn = self._with_mesh(place_fn)
        self.step_fn = step_fn
        self.state = place_fn(self.state)
        self.history: list[StepRecord] = []
        self.eval_history: list[EvalRecord] = []
        self.last_metrics = None  # most recent step/dispatch metrics
        self._eval_step = None  # built lazily on first evaluate()
        self._eval_batches: dict[int, tuple] = {}  # device-resident cache
        self.data_step = 0  # next dataset step to consume (resume-aware)
        # unified telemetry (obs/): goodput meter + registry instruments
        # feeding the JSONL stream and the Prometheus exposition
        self.goodput = obs.GoodputMeter()
        # which call traced, lowered or compiled, on which thread
        obs.jitwatch.install()
        _reg = obs.get_registry()
        self._c_steps = _reg.counter(
            "train_steps_total", "optimizer steps completed")
        self._c_samples = _reg.counter(
            "train_samples_total", "training samples consumed")
        self._g_loss = _reg.gauge("train_loss", "last logged train loss")
        self._h_step = _reg.histogram(
            "train_step_seconds", "wall time per step window")
        runtime_gauges.export_mesh_gauges(self.mesh, _reg)
        self.metrics = None
        if cfg.metrics_path:
            from pytorch_distributed_nn_tpu.utils.metrics import (
                MetricsLogger,
            )

            self.metrics = MetricsLogger(cfg.metrics_path)
            # flight dumps land next to the run's JSONL unless the
            # elastic agent's TPUNN_FLIGHT_DIR contract says otherwise
            import pathlib

            flight.set_dump_dir(pathlib.Path(cfg.metrics_path).parent)
            if watchtower.enabled():
                # alerts ride the same JSONL stream as the metrics
                # they fired on (the tower armed before this logger
                # existed)
                watchtower.tower().metrics = self.metrics
        self.ckpt = None
        try:
            if cfg.checkpoint_dir:
                from pytorch_distributed_nn_tpu.train.checkpoint import (
                    CheckpointManager,
                )

                self.ckpt = CheckpointManager(cfg.checkpoint_dir)
                if cfg.resume and self.ckpt.latest_step() is not None:
                    with self.goodput.phase("checkpoint"):
                        self.state, meta = self.ckpt.restore(self.state)
                    self.data_step = meta["data_step"]
                    log.info("resumed from step %d (data_step %d)",
                             meta["step"], self.data_step)
        except Exception:
            # a failed restore must not leak the metrics file handle
            # (MetricsLogger is a context manager; Trainer mirrors it)
            if self.metrics is not None:
                self.metrics.close()
            raise
        # preemption notice handling (SIGTERM → finish step → sync save
        # → GRACEFUL_EXIT_CODE); no-op outside the agent/TPUNN_PREEMPT.
        # Installed last so a failed constructor can't leak the handler.
        self._preemptible = failure.install_preemption_handler()

    # context manager: `with Trainer(cfg) as t:` closes the metrics
    # JSONL handle and drains async checkpoint writes on ANY exit path
    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _with_mesh(self, fn):
        """Run ``fn`` with this trainer's mesh as the ambient mesh (the
        nested shard_map of model-level ring attention resolves against
        it at trace time)."""
        def wrapped(*args, **kwargs):
            with jax.set_mesh(self.mesh):
                return fn(*args, **kwargs)

        return wrapped

    def _init_state(self) -> TrainState:
        cfg = self.cfg
        rng = jax.random.key(cfg.seed)
        x0, _ = self.dataset.batch(0)
        # init on one example — shapes only; keeps init cheap for big nets
        init = self.model.init
        if self._seq_parallel:  # ring attention traces a shard_map
            init = self._with_mesh(init)
        # local_devices: under multi-process jax.devices()[0] is rank
        # 0's device — non-addressable elsewhere (and segfaults CPU
        # backends when used as default_device on other ranks)
        with jax.default_device(jax.local_devices()[0]):
            variables = init(rng, x0[:1], train=False)
        params = variables.pop("params")
        model_state = dict(variables)
        # per-step transients (MoE aux losses / router diagnostics), not
        # state to carry — forward() re-collects them every step
        model_state.pop("losses", None)
        model_state.pop("diagnostics", None)
        tx = make_optimizer(cfg.optim, total_steps=cfg.steps)
        state = TrainState.create(
            apply_fn=self.model.apply, params=params, tx=tx,
            model_state=model_state,
            rng=jax.random.key(cfg.seed + 1),  # dropout stream != init key
        )
        log.info("model %s: %.2fM params", cfg.model.name,
                 param_count(params) / 1e6)
        return state

    def train(self, steps: int | None = None) -> list[StepRecord]:
        cfg = self.cfg
        obs.jitwatch.mark_loop_thread()  # its events are the loop's
        if steps is None:
            # default = the REMAINING budget: a resumed run finishes at
            # cfg.steps total, it doesn't run cfg.steps more (the LR
            # schedule was built for cfg.steps)
            steps = max(cfg.steps - self.data_step, 0)
        if cfg.multistep_k > 1:
            return self._train_multistep(steps)
        self.loader.start_step = self.data_step  # don't replay batches
        it = iter(self.loader)
        try:
            return self._train_loop(it, steps)
        finally:
            # join the prefetch producer: a daemon thread left blocked
            # mid-queue-put at interpreter exit SIGABRTs
            it.close()

    def _train_loop(self, it, steps: int) -> list[StepRecord]:
        cfg = self.cfg
        gp = self.goodput
        t_last = time.perf_counter()
        g_last = self.data_step  # step count behind each logged record
        for i in range(steps):
            gp.step_start()
            with gp.phase("data"):
                x, y = next(it)
            self.data_step += 1
            g = self.data_step  # 1-based global step just dispatched
            # step-boundary marker in the flight ring: trace-time
            # collective records inherit this step, and per-rank step
            # timestamps drive obs_doctor's straggler percentiles
            flight.mark_step(g)
            chaos.on_step(g)  # fault injection point (crash/slow/preempt)
            xray.on_step(g)  # capture window clock / interval trigger
            if i == 0 and gp.wire_bytes_per_step is None:
                # trace-time collective accounting rides the first
                # dispatch (the call that traces step_fn): recorded
                # wire bytes are the goodput breakdown's cross-check
                # for the collective share
                # and the call that traces is the one moment the step's
                # program can be noted (obs/scopes.py: shapes, no buffer)
                args = (self.state, x, y)
                with cc.recording() as comm_records:
                    with gp.phase("compute"):
                        with flight.dispatch("train_step", step=g):
                            with obs.jitwatch.noting((self.step_fn, args)):
                                self.state, metrics = self.step_fn(*args)
                del args
                if comm_records:
                    gp.wire_bytes_per_step = cc.wire_bytes(comm_records)
                    # per-op attribution cross-checks collective time
                    # against these analytic wire bytes
                    xray.on_wire_bytes(gp.wire_bytes_per_step)
                if xray.enabled():
                    # analytic per-chip step FLOPs turn the capture's
                    # time shares into achieved FLOP/s + roofline
                    # fractions; the cost model is only worth its
                    # (one-off) HLO pass when a capture could use it
                    try:
                        from pytorch_distributed_nn_tpu.utils.flops \
                            import train_flops_per_sample

                        xray.on_flops(
                            train_flops_per_sample(cfg)
                            * cfg.data.batch_size
                            / max(len(jax.devices()), 1))
                    except Exception as e:  # noqa: BLE001
                        log.debug("xray flops context unavailable: %s",
                                  e)
            else:
                with gp.phase("compute"):
                    with flight.dispatch("train_step", step=g):
                        self.state, metrics = self.step_fn(self.state,
                                                           x, y)
            self.last_metrics = metrics
            self._c_steps.inc()
            self._c_samples.inc(cfg.data.batch_size)
            # Progress watchdog food (launch.py --progress-timeout).
            # Dispatch is async, but a hung device op stalls this loop
            # within a few iterations via dispatch-queue backpressure,
            # so per-iteration notification tracks real device progress.
            failure.notify_progress()
            if (self.ckpt is not None and cfg.checkpoint_every
                    and g % cfg.checkpoint_every == 0):
                with gp.phase("checkpoint"):
                    self.ckpt.save(self.state, data_step=self.data_step)
            if cfg.eval_every and g % cfg.eval_every == 0:
                with gp.phase("eval"):
                    self.evaluate()
            logged = cfg.log_every and ((g - 1) % cfg.log_every == 0
                                        or i == steps - 1)
            if logged:
                # the device_get is the loop's execution fence: device
                # time queued behind async dispatch surfaces here, so
                # it counts as compute, not "other"
                with gp.phase("compute"):
                    loss = float(jax.device_get(metrics["loss"]))
                now = time.perf_counter()
                rec = StepRecord(step=g - 1, loss=loss,
                                 seconds=now - t_last)
                t_last = now
                self.history.append(rec)
                self._g_loss.set(loss)
                watchtower.on_loss(g - 1, loss)
                if self.metrics is not None:
                    covered = g - g_last  # actual steps in this record
                    self.metrics.emit(
                        "train_step", step=rec.step, loss=rec.loss,
                        seconds=round(rec.seconds, 4),
                        samples_per_sec=round(
                            covered * cfg.data.batch_size
                            / max(rec.seconds, 1e-9), 2),
                    )
                g_last = g
                if jax.process_index() == 0:
                    log.info("step %d loss %.4f (%.3fs)", g - 1, loss,
                             rec.seconds)
            bd = gp.step_end(step=g - 1)
            self._h_step.observe(bd.wall_s)
            watchtower.on_train_step(g - 1, bd.wall_s)
            if logged:
                self._flush_telemetry(step=g - 1)
            if failure.preempt_requested():
                self._graceful_preempt(g)
        # sync before returning so wall-clock timings are honest
        jax.block_until_ready(self.state.params)
        # Post-loop work (checkpoint drain, eval) is unbounded: back to
        # liveness-only heartbeats so it can't read as a hang.
        failure.notify_done()
        return self.history

    def _graceful_preempt(self, step: int) -> None:
        """Preemption notice arrived (SIGTERM → runtime.failure flag):
        the in-flight step has completed, so force a SYNCHRONOUS
        checkpoint save and exit with the graceful code the elastic
        agent does not charge against the restart budget. Raises
        ``SystemExit`` — the ``with Trainer(...)`` context and the
        worker script's normal exit path still run."""
        log.warning("preemption notice at step %d: saving final "
                    "checkpoint and exiting gracefully", step)
        flight.record("preempt", "graceful_exit", step=step)
        if self.ckpt is not None:
            with self.goodput.phase("checkpoint"):
                self.ckpt.save(self.state, data_step=self.data_step,
                               force=True)
                self.ckpt.wait()  # synchronous: the process is dying
        obs.get_registry().counter(
            "preempt_exits_total", "graceful preemption exits").inc()
        if self.metrics is not None:
            self.metrics.emit("preempt", step=step - 1,
                              data_step=self.data_step,
                              saved=self.ckpt is not None)
        failure.notify_done()
        flight.dump_now("preempt:graceful_exit", force=True)
        raise SystemExit(failure.GRACEFUL_EXIT_CODE)

    def _flush_telemetry(self, step: int) -> None:
        """Log-cadence telemetry fanout: goodput window -> JSONL,
        heartbeat/runtime gauges refreshed, registry snapshot to the
        Prometheus textfile and (under the agent) the native store."""
        win = self.goodput.window_summary()
        if self.metrics is not None:
            self.metrics.emit("goodput", step=step, **win)
        obs.jitwatch.publish()  # the collector's counters
        runtime_gauges.update_heartbeat_gauges()
        reg = obs.get_registry()
        gp_gauge = reg.gauge("goodput_frac",
                             "compute+collective share of wall time")
        gp_gauge.set(win["goodput_frac"])
        watchtower.on_goodput(step, win["goodput_frac"])
        if self.cfg.prom_path:
            reg.write_prometheus(self.cfg.prom_path)
        obs_aggregate.maybe_publish(reg)

    def _get_multistep(self, k: int):
        """Compiled k-fused step, cached per k (the final dispatch of a
        budget not divisible by multistep_k runs a shorter scan)."""
        from pytorch_distributed_nn_tpu.train.multistep import (
            make_multistep,
        )

        if not hasattr(self, "_mstep_cache"):
            self._mstep_cache = {}
        if k not in self._mstep_cache:
            fn = make_multistep(self.step_fn, k)
            self._mstep_cache[k] = (self._with_mesh(fn)
                                    if self._seq_parallel else fn)
        return self._mstep_cache[k]

    def _train_multistep(self, steps: int) -> list[StepRecord]:
        """The device-side training loop: ``multistep_k`` optimizer
        steps per dispatch (train/multistep.py). Math-identical to the
        per-step loop on the same batches; logging stays per-step via
        the scan's stacked metrics, while checkpoint/eval cadences
        round UP to the next dispatch boundary (the scan cannot pause
        mid-flight). ``multistep_pool`` > 0 swaps fresh per-step
        batches for a cycled device-resident pool (benchmark mode —
        repeats data to exclude host transfer from the measurement).
        """
        cfg = self.cfg
        k, pool = cfg.multistep_k, cfg.multistep_pool
        window_sizes = [k] * (steps // k)
        if steps % k:
            window_sizes.append(steps % k)
        if pool:
            if not hasattr(self, "_pool_batches"):
                self._pool_batches = self.loader.stacked_batch_at(
                    self.data_step, min(pool, k))
            xs_pool, ys_pool = self._pool_batches
            batches = None
        else:
            # fresh data: prefetching stacked iterator, so the next
            # window's host generation + transfer overlaps this
            # window's device scan
            batches = self.loader.iter_stacked(
                window_sizes, start_step=self.data_step)
        t_last = time.perf_counter()
        g_last = self.data_step
        remaining = steps
        try:
            return self._multistep_loop(batches, pool, xs_pool if pool
                                        else None,
                                        ys_pool if pool else None, k,
                                        steps, t_last, g_last)
        finally:
            if batches is not None:
                # same prefetch-producer join as train(): an abandoned
                # stacked iterator leaves a daemon thread blocked in
                # q.put -> SIGABRT at interpreter exit
                batches.close()

    def _multistep_loop(self, batches, pool, xs_pool, ys_pool, k,
                        steps, t_last, g_last):
        cfg = self.cfg
        gp = self.goodput
        remaining = steps
        while remaining > 0:
            k_eff = min(k, remaining)
            gp.step_start()
            with gp.phase("data"):
                if pool:
                    xs, ys = xs_pool, ys_pool
                    if jax.tree.leaves(xs)[0].shape[0] > k_eff:
                        xs = jax.tree.map(lambda a: a[:k_eff], xs)
                        ys = jax.tree.map(lambda a: a[:k_eff], ys)
                else:
                    xs, ys = next(batches)
            flight.mark_step(self.data_step + 1, note=f"k={k_eff}")
            chaos.on_step(self.data_step + 1)  # fault injection point
            xray.on_step(self.data_step + 1)  # capture window clock
            with gp.phase("compute"):
                with flight.dispatch("multistep", step=self.data_step + 1,
                                     note=f"k={k_eff}"):
                    self.state, metrics = self._get_multistep(k_eff)(
                        self.state, xs, ys)
            self.data_step += k_eff
            remaining -= k_eff
            g = self.data_step  # 1-based step count after this window
            self.last_metrics = metrics
            self._c_steps.inc(k_eff)
            self._c_samples.inc(k_eff * cfg.data.batch_size)
            failure.notify_progress()
            if (self.ckpt is not None and cfg.checkpoint_every
                    and g // cfg.checkpoint_every
                    > (g - k_eff) // cfg.checkpoint_every):
                with gp.phase("checkpoint"):
                    self.ckpt.save(self.state, data_step=self.data_step)
            if (cfg.eval_every and g // cfg.eval_every
                    > (g - k_eff) // cfg.eval_every):
                with gp.phase("eval"):
                    self.evaluate()
            logged = []
            if cfg.log_every:
                # per-step losses from the scan's stacked metrics: one
                # (k_eff,) fetch covers every logged step in the window
                logged = [s for s in range(g - k_eff + 1, g + 1)
                          if (s - 1) % cfg.log_every == 0
                          or (remaining == 0 and s == g)]
                if logged:
                    with gp.phase("compute"):  # fence: device catches up
                        losses = np.asarray(jax.device_get(
                            metrics["all"]["loss"]), np.float32)
                    now = time.perf_counter()
                    window_dt = now - t_last
                    window_span = max(g - g_last, 1)  # steps since last
                    for s in logged:
                        covered = s - g_last
                        rec = StepRecord(
                            step=s - 1,
                            loss=float(losses[s - (g - k_eff) - 1]),
                            seconds=window_dt * covered / window_span,
                        )
                        self.history.append(rec)
                        if self.metrics is not None:
                            self.metrics.emit(
                                "train_step", step=rec.step,
                                loss=rec.loss,
                                seconds=round(rec.seconds, 4),
                                samples_per_sec=round(
                                    covered * cfg.data.batch_size
                                    / max(rec.seconds, 1e-9), 2),
                            )
                        g_last = s
                        if jax.process_index() == 0:
                            log.info("step %d loss %.4f (%.3fs)",
                                     rec.step, rec.loss, rec.seconds)
                    t_last = now
                    self._g_loss.set(float(losses[-1]))
                    watchtower.on_loss(g - 1, float(losses[-1]))
            bd = gp.step_end(step=g - 1, steps_covered=k_eff)
            self._h_step.observe(bd.wall_s)
            watchtower.on_train_step(g - 1, bd.wall_s / max(k_eff, 1))
            if logged:
                self._flush_telemetry(step=g - 1)
            if failure.preempt_requested():
                self._graceful_preempt(g)
        # execution fence: ONE scalar device_get of the final fused
        # loss (which depends on every prior step) — cheaper than
        # block_until_ready over every param leaf.
        if self.last_metrics is not None:
            float(jax.device_get(self.last_metrics["loss"]))
        failure.notify_done()
        return self.history

    def _build_eval(self) -> None:
        import jax.numpy as jnp

        cfg = self.cfg
        if cfg.parallel.strategy == "pipeline":
            # forward-only pipelined eval on the stacked stage params
            from pytorch_distributed_nn_tpu.parallel.pipeline import (
                make_pipeline_eval_step,
            )

            self._eval_step = make_pipeline_eval_step(
                cfg, self.mesh, self.loss_fn, self.model
            )
            return
        from pytorch_distributed_nn_tpu.parallel.dp import forward

        loss_fn = self.loss_fn
        xent_chunk = self.cfg.xent_chunk

        # mirror api.make_train_step: when the whole sequence fits in
        # one chunk, training used the dense loss — eval must too
        if xent_chunk and self.cfg.data.seq_len > xent_chunk:
            # long-context LM: dense (B, T, V) eval logits would OOM the
            # same way training would — evaluate chunked too
            from pytorch_distributed_nn_tpu.train.losses import (
                chunked_lm_eval,
            )

            def eval_step(state, x, y):
                hidden, _, _ = forward(
                    state, state.params, x, train=False,
                    apply_kwargs={"return_hidden": True},
                )
                kernel = state.params["lm_head"]["kernel"]
                loss, acc = chunked_lm_eval(hidden, kernel, y,
                                            chunk=xent_chunk)
                return loss, acc
        else:
            def eval_step(state, x, y):
                # dp.forward is the one place that knows how to assemble
                # variables/mutable collections; eval must not fork it
                logits, _, _ = forward(state, state.params, x,
                                       train=False)
                loss = loss_fn(logits, y)
                # masked accuracy: labels < 0 mean "ignore" (BERT MLM)
                valid = y >= 0
                hit = jnp.logical_and(logits.argmax(-1) == y, valid)
                acc = hit.sum() / jnp.maximum(valid.sum(), 1)
                return loss.astype(jnp.float32), acc.astype(jnp.float32)

        self._eval_step = jax.jit(eval_step)
        if self._seq_parallel:
            self._eval_step = self._with_mesh(self._eval_step)

    def evaluate(self, num_batches: int | None = None) -> EvalRecord:
        """Forward-only pass over the held-out stream; returns (and
        records) mean loss and masked accuracy. ``EvalRecord.step`` uses
        the same 0-based convention as ``StepRecord`` (-1 = before any
        training)."""
        n = self.cfg.eval_batches if num_batches is None else num_batches
        if n <= 0:
            raise ValueError(f"evaluate needs >= 1 batches, got {n}")
        # Disarm the progress watchdog across the (unbounded) eval-step
        # compile; per-batch completions below re-arm and feed it.
        failure.notify_done()
        if self._eval_step is None:
            self._build_eval()
        losses, accs = [], []
        with obs.span("train/eval", batches=n):
            for i in range(n):
                if i not in self._eval_batches:
                    # the stream is deterministic, so each batch is
                    # generated and transferred once and reused by
                    # every eval pass
                    self._eval_batches[i] = self.loader.batch_at(
                        _EVAL_STEP_OFFSET + i
                    )
                x, y = self._eval_batches[i]
                loss, acc = self._eval_step(self.state, x, y)
                losses.append(float(jax.device_get(loss)))
                accs.append(float(jax.device_get(acc)))
                failure.notify_progress()  # eval batches are progress
        rec = EvalRecord(step=self.data_step - 1,
                         loss=float(np.mean(losses)),
                         accuracy=float(np.mean(accs)))
        self.eval_history.append(rec)
        if self.metrics is not None:
            self.metrics.emit("eval", step=rec.step, loss=rec.loss,
                              accuracy=rec.accuracy)
        if jax.process_index() == 0:
            log.info("eval @ step %d: loss %.4f acc %.4f",
                     rec.step, rec.loss, rec.accuracy)
        return rec

    def save_checkpoint(self, *, force: bool = True) -> bool:
        if self.ckpt is None:
            raise RuntimeError("no checkpoint_dir configured")
        return self.ckpt.save(self.state, data_step=self.data_step,
                              force=force)

    def close(self) -> None:
        if self._preemptible:
            failure.uninstall_preemption_handler()
        if self.ckpt is not None:
            self.ckpt.close()
        if self.metrics is not None:
            if self.goodput.steps:
                # whole-run breakdown as the stream's closing record
                self.metrics.emit("goodput_summary",
                                  **self.goodput.summary())
            self.metrics.close()
        if self.cfg.prom_path:
            obs.get_registry().write_prometheus(self.cfg.prom_path)

    def losses(self) -> list[float]:
        return [r.loss for r in self.history]


def run_preset(preset: str, **overrides: Any) -> list[StepRecord]:
    from pytorch_distributed_nn_tpu.config import get_config

    trainer = Trainer(get_config(preset, **overrides))
    return trainer.train()
