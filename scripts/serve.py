#!/usr/bin/env python
"""Serving entrypoint: continuous-batching engine + synthetic load.

Usage:
    python scripts/serve.py --preset llama3_longcontext \
        [--checkpoint-dir runs/ckpt] [--slots 4] [--max-seq-len 256] \
        [--requests 32] [--rate 20] [--max-new 16] \
        [--closed-loop] [--users 4] [--metrics-out serve.jsonl]

Runs the loopback server (serve/server.py) against a synthetic ragged
workload and prints one JSON summary line (requests, rejects,
tokens/s, TTFT and per-token latency percentiles, batch occupancy, KV
utilization). Without --checkpoint-dir the model is randomly
initialized — the scheduler/latency behavior under test does not
depend on the weights.

SIGTERM drains gracefully: queued requests are rejected, in-flight
sequences finish, and the process exits GRACEFUL_EXIT_CODE (83) so an
agent classifies the shutdown like a trainer preemption. Load-shed
drills: TPUNN_CHAOS='serve_reject@p=0.3' (docs/serving.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np


from pytorch_distributed_nn_tpu.obs.stats import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="llama3_longcontext")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch slots (concurrent sequences)")
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV pool block size in tokens")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--max-prefills", type=int, default=2,
                    help="admissions per decode round")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop arrival rate, req/s")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request queue deadline in seconds "
                         "(0 = none)")
    ap.add_argument("--closed-loop", action="store_true",
                    help="closed-loop clients instead of open-loop")
    ap.add_argument("--users", type=int, default=4,
                    help="closed-loop user count")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="",
                    help="JSONL path for serve_request/serve_summary "
                         "events (scripts/obs_report.py reads these)")
    args, rest = ap.parse_known_args(argv)

    from pytorch_distributed_nn_tpu.config import (
        get_config,
        parse_overrides,
    )
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.obs import audit, meter, trace, watchtower
    from pytorch_distributed_nn_tpu.runtime import chaos
    from pytorch_distributed_nn_tpu.runtime.failure import (
        GRACEFUL_EXIT_CODE,
    )
    from pytorch_distributed_nn_tpu.serve import (
        InferenceServer,
        ServingEngine,
        closed_loop_client,
        install_sigterm_drain,
        open_loop_client,
        ragged_prompt_sampler,
    )
    from pytorch_distributed_nn_tpu.utils.metrics import MetricsLogger

    install_sigterm_drain()

    cfg = get_config(args.preset, **parse_overrides(rest))
    model = get_model(cfg.model)
    if args.checkpoint_dir:
        cfg.checkpoint_dir = args.checkpoint_dir
        cfg.steps = 0
        from pytorch_distributed_nn_tpu.train.trainer import Trainer

        trainer = Trainer(cfg)
        if trainer.ckpt is None or trainer.ckpt.latest_step() is None:
            print(f"no checkpoint found in {args.checkpoint_dir}",
                  file=sys.stderr)
            return 1
        params = jax.device_get(trainer.state.params)
        trainer.close()
    else:
        print("[serve] no --checkpoint-dir: random init (load test "
              "only)", file=sys.stderr)
        params = model.init(
            jax.random.key(cfg.seed),
            jnp.zeros((1, 8), jnp.int32), train=False,
        )["params"]

    # no --metrics-out: keep stdout to the single summary line below
    metrics = MetricsLogger(args.metrics_out) if args.metrics_out else None
    engine = ServingEngine(
        model, params, max_slots=args.slots,
        max_seq_len=args.max_seq_len, block_size=args.block_size,
        max_queue=args.max_queue,
        max_prefills_per_round=args.max_prefills, metrics=metrics,
    )
    vocab = getattr(model, "vocab_size", 1000)
    max_prompt = max(args.min_prompt,
                     min(args.max_prompt,
                         args.max_seq_len - args.max_new))
    sampler = ragged_prompt_sampler(
        vocab, min_len=args.min_prompt, max_len=max_prompt,
        seed=args.seed)

    server = InferenceServer(engine).start()
    # Warm the compile caches (every prefill pad bucket the sampler can
    # hit, plus the decode step) so TTFT measures serving, not XLA.
    from pytorch_distributed_nn_tpu.serve.engine import _bucket_len

    warm_rng = np.random.default_rng(args.seed)
    b = _bucket_len(args.min_prompt)
    top = min(_bucket_len(max_prompt), args.max_seq_len)
    while b <= top:
        # max_new=2 forces one decode round: the prefill-produced first
        # token alone would retire the row before _serve_step compiles
        L = min(b, args.max_seq_len - 2)
        server.generate(
            warm_rng.integers(0, vocab, size=(L,)).astype(np.int32), 2)
        b *= 2
    warm_done = len(engine.completed)
    warm_rounds = len(engine.round_seconds)
    # armed after warmup so a serve_reject@ drill can't shed the
    # compile-cache warm requests and pollute the timed TTFTs — and so
    # the watchtower's TTFT burn-rate window never sees compile time
    chaos.maybe_init()
    watchtower.maybe_init(metrics=metrics)
    trace.maybe_init(metrics=metrics)  # TPUNN_TRACE — Causeway
    meter.maybe_init(metrics=metrics)  # TPUNN_METER — Abacus
    audit.maybe_init(metrics=metrics)  # TPUNN_AUDIT — Lighthouse
    t0 = time.monotonic()
    try:
        if args.closed_loop:
            per_user = max(args.requests // max(args.users, 1), 1)
            reqs = closed_loop_client(
                server, num_users=args.users,
                requests_per_user=per_user,
                max_new_tokens=args.max_new, prompt_sampler=sampler)
        else:
            reqs = open_loop_client(
                server, num_requests=args.requests, rate_hz=args.rate,
                max_new_tokens=args.max_new, prompt_sampler=sampler,
                deadline_s=args.deadline or None)
    finally:
        server.stop()
    wall = time.monotonic() - t0

    done = [r for r in reqs if r.ok]
    rejects: dict[str, int] = {}
    for r in reqs:
        if r.state == "rejected":
            rejects[r.reject_reason] = rejects.get(r.reject_reason, 0) + 1
    timed = engine.completed[warm_done:]  # warmup excluded
    ttfts = [c["ttft_s"] for c in timed]
    tok_lat = engine.round_seconds[warm_rounds:]
    summary = dict(
        requests=len(reqs), completed=len(done),
        rejected=sum(rejects.values()), reject_reasons=rejects,
        preempted=server.preempted,
        wall_s=round(wall, 3),
        tokens_out=int(sum(c["new_tokens"] for c in timed)),
        tokens_per_s=round(
            sum(c["new_tokens"] for c in timed) / max(wall, 1e-9), 2),
        ttft_p50_s=percentile(ttfts, 0.50),
        ttft_p95_s=percentile(ttfts, 0.95),
        token_lat_p50_s=percentile(tok_lat, 0.50),
        token_lat_p95_s=percentile(tok_lat, 0.95),
        token_lat_p99_s=percentile(tok_lat, 0.99),
        **{k: v for k, v in engine.summary().items()
           if k in ("rounds", "occupancy", "kv_util")},
    )
    if metrics is not None:
        metrics.emit("serve_summary", **summary)
        metrics.close()
    print(json.dumps(summary))
    if server.preempted:
        return GRACEFUL_EXIT_CODE
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
