"""Replica worker subprocess — the process-backed fleet's data plane.

One process = one replica = one crash domain for real. The coordinator
(:mod:`serve.procfleet`) spawns this module with ``python -m``, hands it
a store endpoint + namespace + replica index, and from then on every
word between them travels through the store:

- ``req/<idx>/<k>``   — the k-th request dispatched to this replica
  (``k`` allocated by the coordinator's atomic counter; the worker
  consumes strictly in order, so a dispatch is never lost or doubled);
- ``prog/<rid>``      — tokens emitted so far for a running request,
  republished every decode round; this is what a coordinator (original
  or recovered) stitches from when this replica dies mid-stream;
- ``done/<rid>``      — the final token list; written exactly once per
  request life;
- ``gauge/<idx>``     — queue depth / KV headroom, the remote mirror of
  the scheduler+pool gauges :meth:`serve.router.Router._score` reads;
- ``ctl/<idx>``       — coordinator control: ``drain`` (finish what you
  hold, exit ``GRACEFUL_EXIT_CODE``) or ``stop`` (fleet shutdown);
- ``hb/0/<idx>``      — the REAL :class:`runtime.failure.HeartbeatReporter`
  beating through the same store (progress-watchdog mode, so a wedged
  decode loop reads as a hang even while the beat thread lives);
- ``enroll/<idx>``    — the worker's birth certificate (pid, host,
  role), written once at startup. A locally-spawned worker's record is
  redundant (the coordinator holds the ``Popen``); a worker spawned on
  another host through a :class:`serve.procfleet.TemplateProvisioner`
  has NO process object on the coordinator — this record is how the
  coordinator learns its pid/host at all (``_check_enrollment``);
- ``kvwire/<rid>/*``  — the versioned, checksummed KV handoff wire
  (:mod:`serve.kv_wire`): a ``--role prefill`` worker pushes the
  request's KV tree here after publishing ``done`` (done FIRST — a
  death mid-push is exactly a crash after completion, the coordinator
  hands off and the decode leg runs cold); a ``--role decode`` worker
  pulls it at admit and ingests warm, or re-prefills cold when the
  wire is absent/torn past its bounded deadline. Never wedges.

Roles (``--role prefill|decode|unified``) do not change how this
process serves — the coordinator's stage-aware router is what routes
legs to pools — but a prefill worker pushes the wire on completion and
a decode worker pulls it at admission, and the role rides the enroll
record and the coordinator's ``serve_fleet_replicas{role}`` gauge.

Exit codes are the elastic-agent contract: ``0`` on ``stop``,
``failure.GRACEFUL_EXIT_CODE`` (83) on drain/SIGTERM,
``chaos.CRASH_EXIT_CODE`` (43) on an injected or real crash — the
coordinator's per-replica :class:`launch.RestartPolicy` classifies them
exactly like the training agent does.

Backends: ``stub`` decodes with :func:`serve.stub.stub_next_token`
(deterministic, model-free — restart drills and tier-1); ``tiny``
builds a deterministic tiny model (:func:`build_tiny_model`) and
drives a real :class:`serve.engine.ServingEngine`;
``preset`` builds a REAL model from a named :data:`config.PRESETS`
entry (``--preset``, validated with an error naming every available
preset) with optional Orbax params at ``--ckpt``, behind the same
engine loop.

Store failures (``store_partition`` / ``store_flaky`` chaos, a real
blip) degrade to counted retries (``store_errors_total{op}``) — the
worker keeps decoding through a partition and republishes state when
the store comes back; only the detector's staleness math may declare
it dead.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import sys
import time

import numpy as np

from pytorch_distributed_nn_tpu.obs import audit, meter, trace
from pytorch_distributed_nn_tpu.runtime import chaos, failure
from pytorch_distributed_nn_tpu.runtime.device import (
    claim_chip,
    configure_compile_cache,
)
from pytorch_distributed_nn_tpu.serve import kv_wire
from pytorch_distributed_nn_tpu.serve.store import (
    PrefixStore,
    StoreJournal,
    make_store,
)
from pytorch_distributed_nn_tpu.serve.stub import stub_next_token

configure_compile_cache()

log = logging.getLogger(__name__)


class _StubBackend:
    """Model-free decode: one deterministic stub token per active
    request per round. ``token_ms`` paces the round so drills see a
    realistic service rate (queues actually build under flash crowds)."""

    def __init__(self, *, max_slots: int, token_ms: float) -> None:
        self.max_slots = int(max_slots)
        self.token_ms = float(token_ms)
        self._active: list[dict] = []

    @property
    def slots_free(self) -> int:
        return self.max_slots - len(self._active)

    @property
    def has_work(self) -> bool:
        return bool(self._active)

    def admit(self, rec: dict) -> None:
        self._active.append({"rec": rec, "tokens": []})

    def step(self) -> tuple[list, list]:
        """One decode round → ``(progress, completed)`` where progress
        is ``[(rec, tokens_so_far)]`` and completed
        ``[(rec, tokens, status)]``."""
        if not self._active:
            return [], []
        if self.token_ms:
            time.sleep(self.token_ms / 1000.0)
        progress, completed, still = [], [], []
        for ent in self._active:
            rec, toks = ent["rec"], ent["tokens"]
            toks.append(stub_next_token(list(rec["prompt"]) + toks))
            if len(toks) >= int(rec["max_new_tokens"]):
                completed.append((rec, toks, "done"))
            else:
                progress.append((rec, toks))
                still.append(ent)
        self._active = still
        return progress, completed

    def gauges(self) -> dict:
        # slot-granular "KV": free slots over total, the same headroom
        # shape the router scores on real pools
        return {"free_blocks": self.slots_free,
                "num_blocks": self.max_slots, "block_size": 1}

    def export_kv(self, rec: dict, toks: list) -> dict:
        """The stub's 'KV state' is just the token stream —
        :func:`stub_next_token` is a pure function of the prefix, so
        warm and cold decode legs are bit-identical by construction.
        The tree still rides the real wire (chunking, checksums, chaos
        tears) so every drill exercises the full transfer path. Shaped
        ``(1, N)`` — ``kv_transfer`` bills ndim>=2 leaves (the paged
        block convention), so even the stub's bytes are on the books."""
        return {"tokens": np.asarray(
            list(rec["prompt"]) + list(toks), np.int32).reshape(1, -1)}

    def ingest_kv(self, rec: dict, tree: dict) -> int:
        return 0  # nothing to warm; the pull outcome is the point


class _EngineBackend:
    """A real :class:`serve.engine.ServingEngine` over the
    deterministic tiny model (:func:`build_tiny_model`): same
    config, same seed-0 params in every process, so greedy decode
    is bit-identical across replicas and coordinator lives."""

    def __init__(self, *, max_slots: int, max_seq_len: int,
                 block_size: int, max_queue: int, tag: str,
                 model=None, params=None) -> None:
        from pytorch_distributed_nn_tpu.serve.engine import ServingEngine

        self._np = np
        if model is None:
            model, params = build_tiny_model()
        self.engine = ServingEngine(
            model, params, max_slots=max_slots, max_seq_len=max_seq_len,
            block_size=block_size, max_queue=max_queue, tag=tag)
        self._reqs: list[tuple[dict, object]] = []

    @property
    def slots_free(self) -> int:
        return max(self.engine.max_slots - len(self._reqs), 0)

    @property
    def has_work(self) -> bool:
        return bool(self._reqs) or self.engine.has_work

    def admit(self, rec: dict) -> None:
        kw = {}
        if rec.get("decode"):
            # Prism: rebuild the spec from its wire dict (loud on
            # unknown keys — a version-skewed coordinator fails the
            # dispatch, never silently mis-samples)
            from pytorch_distributed_nn_tpu.serve.decoding import (
                DecodeSpec,
            )
            kw["decode"] = DecodeSpec.from_wire(rec["decode"])
            kw["decode_step0"] = int(rec.get("step0", 0))
        req = self.engine.submit(
            self._np.asarray(rec["prompt"], self._np.int32),
            int(rec["max_new_tokens"]),
            request_id=rec["request_id"],
            resubmit=bool(rec.get("life", 0)),
            tenant=rec.get("tenant", "default"), **kw)
        self._reqs.append((rec, req))

    def step(self) -> tuple[list, list]:
        if self.engine.has_work:
            self.engine.step()
        progress, completed, still = [], [], []
        for rec, req in self._reqs:
            if req.done.is_set():
                toks = ([int(t) for t in req.tokens]
                        if req.tokens is not None else [])
                status = "done" if req.state == "done" else "rejected"
                completed.append((rec, toks, status))
                continue
            toks = []
            for slot in self.engine._slots:
                if slot is not None and slot.req is req:
                    toks = [int(t) for t in slot.tokens]
                    break
            progress.append((rec, toks))
            still.append((rec, req))
        self._reqs = still
        return progress, completed

    def gauges(self) -> dict:
        pool = self.engine.scheduler.pool
        return {"free_blocks": pool.free_blocks,
                "num_blocks": pool.num_blocks,
                "block_size": pool.block_size}

    def export_kv(self, rec: dict, toks: list) -> dict:
        """Host-side KV tree for the wire: the request's resident
        prefix chain exported from this engine's block store
        (:meth:`serve.engine.ServingEngine.export_blocks` — the same
        source the threaded DisaggFleet streams from). Single-threaded
        serve loop: nothing can evict between the chain match and the
        export, so no pin window is needed here."""
        tokens = np.asarray(list(rec["prompt"]) + list(toks), np.int32)
        tree: dict = {"tokens": tokens}
        pc = self.engine.prefix_cache
        if pc is None:
            return tree
        adapter = int(rec.get("adapter", 0))
        m = pc.resident_chain(tokens, adapter)
        blocks = list(m.blocks)
        if blocks:
            tree["kv"] = self.engine.export_blocks(blocks)
            tree["nblk"] = np.asarray(len(blocks), np.int32)
        return tree

    def ingest_kv(self, rec: dict, tree: dict) -> int:
        """Warm this engine from a pulled wire tree: adopt prefix-cache
        blocks for the shipped tokens and scatter the streamed rows in
        (:meth:`serve.engine.ServingEngine.ingest_blocks`). Returns
        blocks written; 0 means the decode leg prefills cold anyway —
        warmth is an optimization, never a correctness input."""
        if "kv" not in tree or self.engine.prefix_cache is None:
            return 0
        tokens = np.asarray(tree["tokens"], np.int32)
        bs = self.engine.scheduler.pool.block_size
        n = int(np.asarray(tree["nblk"]).reshape(-1)[0])
        return int(self.engine.ingest_blocks(
            tokens[:n * bs], tree["kv"], int(rec.get("adapter", 0))))


def build_tiny_model():
    """The deterministic tiny decoder every process-backed replica
    serves: a 4-layer Llama at d_model 256 with seed-0 init —
    identical params in every process by construction, so the process
    fleet's greedy streams are bit-comparable to the threaded
    fleet's."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.models import get_model

    cfg = get_config("llama3_8b_zero")
    cfg.model.extra = dict(num_layers=4, d_model=256, num_heads=8,
                           num_kv_heads=4, mlp_dim=1024,
                           vocab_size=1024)
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    model = get_model(cfg.model)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


def build_preset_model(preset: str, ckpt: str = ""):
    """``--backend preset``: a REAL model from the named
    :data:`config.PRESETS` entry, seed-0 params or an Orbax
    params-tree checkpoint at ``ckpt`` (a ``StandardSave`` of the
    params pytree — the serving analogue of the trainer's ``arrays``
    item). Config validation is loud and names every available preset,
    so a typo in a deploy script fails the worker at spawn with the
    fix in the message, not with a silent stub."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.config import PRESETS, get_config
    from pytorch_distributed_nn_tpu.models import get_model

    if not preset:
        raise SystemExit(
            "fleet-worker: --backend preset needs --preset NAME; "
            f"available presets: {', '.join(sorted(PRESETS))}")
    if preset not in PRESETS:
        raise SystemExit(
            f"fleet-worker: unknown --preset {preset!r}; available "
            f"presets: {', '.join(sorted(PRESETS))}")
    cfg = get_config(preset)
    model = get_model(cfg.model)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    if ckpt:
        from pathlib import Path

        import orbax.checkpoint as ocp

        path = Path(ckpt).absolute()
        if not path.exists():
            raise SystemExit(
                f"fleet-worker: --ckpt {ckpt!r} does not exist")
        params = ocp.StandardCheckpointer().restore(path, target=params)
    return model, params


def _publish(ps, key: str, rec: dict, *, op: str) -> bool:
    """Counted-retry store write: a partition degrades the publish to
    a ``store_errors_total{op}`` bump, never a dead worker."""
    try:
        ps.set(key, json.dumps(rec, sort_keys=True).encode())
        return True
    except (OSError, TimeoutError):
        failure.count_store_error(op)
        return False


def _publish_done(ps, rec: dict, tokens: list, status: str,
                  *, retries: int = 100) -> None:
    """The one write that must not be silently dropped: retry through
    a partition window. If the store stays gone the coordinator's
    staleness math re-admits the request elsewhere and greedy decode
    regenerates the identical stream — correctness never rests on this
    write landing, only latency does."""
    payload = {"life": int(rec.get("life", 0)), "status": status,
               "tokens": [int(t) for t in tokens]}
    if "trace" in rec:  # Causeway echo — absent when unarmed
        payload["trace"] = rec["trace"]
    key = f"done/{rec['request_id']}"
    for _ in range(retries):
        if _publish(ps, key, payload, op="worker_done"):
            return
        time.sleep(0.05)
    log.warning("giving up publishing %s after %d retries", key, retries)


def _push_wire(ps, idx: int, rec: dict, toks: list, backend) -> None:
    """Prefill leg completed: stream its KV tree to the store wire.

    Called strictly AFTER :func:`_publish_done` — the done record is
    the correctness commit; the wire is warmth. A death anywhere in
    here (``kill_transfer@`` chaos fires inside ``kv_transfer``, a real
    SIGKILL) is therefore exactly a crash after completion: the
    coordinator's handoff proceeds from the done payload and the
    decode leg pulls a dead wire — cold re-prefill, identical tokens."""
    tree = backend.export_kv(rec, toks)
    ctx = None
    if "trace" in rec:  # Causeway: the transfer bills to this leg
        ctx = trace.TraceContext.from_wire(rec["trace"])
    kv_wire.push(ps, rec["request_id"], tree,
                 src=f"r{idx}", dst="decode",
                 src_index=idx, dst_index=-1,
                 trace=ctx, tenant=rec.get("tenant", ""))


def _pull_wire(ps, idx: int, rec: dict, backend, journal) -> None:
    """Decode leg admitted: pull the prefill leg's KV tree and warm
    this backend, or fall through cold. The warm/cold disposition is
    journaled (counted write — a partitioned journal never blocks the
    admission) so drills and ``obs_doctor`` can see which path ran."""
    tree = kv_wire.pull(ps, rec["request_id"])
    outcome = "warm" if tree is not None else "cold"
    blocks = backend.ingest_kv(rec, tree) if tree is not None else 0
    failure.store_call(
        lambda: journal.append({
            "event": "kv_pull", "request_id": rec["request_id"],
            "replica": idx, "outcome": outcome, "blocks": blocks}),
        op="worker_journal", deadline_s=1.0, fallback=None)


def _serve_loop(args, ps, idx: int, reporter, backend) -> int:
    journal = StoreJournal(ps, "journal")
    queue: list[dict] = []
    next_k = args.start_k
    draining = False
    rounds = 0
    idle_s = max(args.poll_ms, 0.5) / 1000.0
    while True:
        rounds += 1
        # chaos kill/hang drill — may raise ReplicaKillError (caught in
        # main → exit CRASH_EXIT_CODE) or block (heartbeat goes stale)
        chaos.on_replica_round(idx, rounds)
        reporter.notify_progress()
        if failure.preempt_requested():
            draining = True  # SIGTERM → finish what we hold, exit 83
        try:
            if ps.check(f"ctl/{idx}"):
                cmd = ps.get(f"ctl/{idx}", timeout_ms=1000).decode()
                if cmd == "stop":
                    return 0
                if cmd == "drain":
                    draining = True
        except (OSError, TimeoutError):
            failure.count_store_error("worker_ctl")
        try:
            while ps.check(f"req/{idx}/{next_k}"):
                queue.append(json.loads(ps.get(
                    f"req/{idx}/{next_k}", timeout_ms=1000).decode()))
                next_k += 1
        except (OSError, TimeoutError):
            failure.count_store_error("worker_pull")
        while queue and backend.slots_free > 0:
            rec0 = queue.pop(0)
            # Causeway: stamp the admit time for this leg's decode
            # span before the backend owns the record
            trace.on_worker_admit(rec0, host=idx)
            if rec0.get("stage") == "decode":
                # warm from the handoff wire, or prefill cold — the
                # pull is bounded (deadline + counted re-pulls), so
                # a dead/torn wire can never wedge the admission
                _pull_wire(ps, idx, rec0, backend, journal)
            backend.admit(rec0)
        progress, completed = backend.step()
        for rec, toks in progress:
            if toks:
                payload = {"life": int(rec.get("life", 0)),
                           "tokens": [int(t) for t in toks]}
                if "trace" in rec:  # Causeway echo
                    payload["trace"] = rec["trace"]
                _publish(ps, f"prog/{rec['request_id']}", payload,
                         op="worker_prog")
        for rec, toks, status in completed:
            trace.on_worker_done(rec, toks, status, host=idx)
            # Lighthouse: the leg fingerprint (seeded by the chain the
            # coordinator dispatched as rec["fp"]) is published BEFORE
            # done — the coordinator's verify at finalize never races
            # the evidence; key/write absent entirely when unarmed
            if status == "done":
                fp_payload = audit.on_worker_done(rec, toks, host=idx)
                if fp_payload is not None:
                    _publish(ps, f"fp/{rec['request_id']}", fp_payload,
                             op="worker_fp")
            # done FIRST, then the wire: the coordinator's handoff
            # rests on the done record alone — see _push_wire
            _publish_done(ps, rec, toks, status)
            if rec.get("stage") == "prefill" and status == "done":
                _push_wire(ps, idx, rec, toks, backend)
        trace.maybe_publish(ps, rank=idx)
        meter.maybe_publish(ps, rank=idx)
        audit.maybe_publish(ps, rank=idx)
        _publish(ps, f"gauge/{idx}", dict(
            queue_depth=len(queue), max_queue=args.max_queue,
            pid=os.getpid(), round=rounds, draining=draining,
            **backend.gauges()), op="worker_gauge")
        if draining and not backend.has_work and not queue:
            return failure.GRACEFUL_EXIT_CODE
        if not backend.has_work:
            time.sleep(idle_s)


def _parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="process-fleet replica worker (serve/procfleet.py "
                    "spawns this; not a user-facing CLI)")
    p.add_argument("--store", required=True,
                   help="store endpoint, host:port")
    p.add_argument("--namespace", default="fleet")
    p.add_argument("--replica-index", type=int, required=True)
    p.add_argument("--backend", choices=("stub", "tiny", "preset"),
                   default="stub")
    p.add_argument("--role", choices=("unified", "prefill", "decode"),
                   default="unified",
                   help="this replica's disaggregation pool — routing "
                        "is the coordinator's job; the role drives the "
                        "KV wire push (prefill) / pull (decode) and "
                        "rides the enroll record")
    p.add_argument("--preset", default="",
                   help="config.PRESETS name for --backend preset "
                        "(validated; the error names every preset)")
    p.add_argument("--ckpt", default="",
                   help="optional Orbax params checkpoint for "
                        "--backend preset")
    p.add_argument("--max-slots", type=int, default=4)
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--max-seq-len", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--token-ms", type=float, default=2.0,
                   help="stub decode pacing per round")
    p.add_argument("--hb-interval", type=float, default=0.1)
    p.add_argument("--progress-window", type=float, default=None)
    p.add_argument("--poll-ms", type=float, default=2.0)
    p.add_argument("--start-k", type=int, default=0,
                   help="first dispatch seq to consume — a restarted "
                        "index resumes the stream where the store "
                        "counter left it, skipping requests the dead "
                        "life already owned (the coordinator re-admits "
                        "those under a new life)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    logging.basicConfig(
        level=logging.INFO,
        format=f"[fleet-worker r{args.replica_index}] %(message)s")
    # tiny/preset run on a jax backend: off the CPU that is the chip,
    # which one process holds — claim it (the open descriptor is the
    # lock) or exit saying who has it, before anything else starts
    chip_lock = claim_chip() if args.backend != "stub" else None  # noqa: F841
    chaos.maybe_init()
    failure.install_preemption_handler(force=True)
    client = make_store(args.store)
    ps = PrefixStore(client, args.namespace) if args.namespace else client
    idx = int(args.replica_index)
    # arm tracing from TPUNN_TRACE (inherited via worker_env) — each
    # worker process owns its own span ring, published at trace/<idx>
    trace.maybe_init(rank=idx)
    # arm metering from TPUNN_METER (inherited via worker_env) — each
    # worker process bills its own engine, published at meter/<idx>
    meter.maybe_init(rank=idx)
    # arm auditing from TPUNN_AUDIT (inherited, or re-exported by a
    # programmatically-armed coordinator) — leg fingerprints publish
    # at fp/<rid>, the summary at audit/<idx>
    audit.maybe_init(rank=idx)
    reporter = failure.HeartbeatReporter(
        ps, rank=idx, incarnation=0,
        interval_s=args.hb_interval,
        progress_window_s=args.progress_window)
    if args.backend == "stub":
        backend = _StubBackend(max_slots=args.max_slots,
                               token_ms=args.token_ms)
    else:
        model = params = None
        if args.backend == "preset":
            model, params = build_preset_model(args.preset, args.ckpt)
        backend = _EngineBackend(
            max_slots=args.max_slots, max_seq_len=args.max_seq_len,
            block_size=args.block_size, max_queue=args.max_queue,
            tag=f"r{idx}", model=model, params=params)
        # chaos flip@replica=K keys on this (silent-corruption drill)
        backend.engine.replica_index = idx
    # enrollment handshake: tell the coordinator who actually
    # materialized behind this index — for a cross-host spawn
    # (TemplateProvisioner) this record is the ONLY way it learns
    # the pid/host; for a local spawn it is a harmless echo
    _publish(ps, f"enroll/{idx}", dict(
        pid=os.getpid(), host=socket.gethostname(), role=args.role),
        op="worker_enroll")
    code = chaos.CRASH_EXIT_CODE
    try:
        code = _serve_loop(args, ps, idx, reporter, backend)
    except chaos.ReplicaKillError:
        log.warning("replica %d: injected kill", idx)
        code = chaos.CRASH_EXIT_CODE
    except Exception:
        log.exception("replica %d crashed", idx)
        code = chaos.CRASH_EXIT_CODE
    finally:
        reporter.stop()
        try:
            client.close()
        except OSError:
            pass
    return code


if __name__ == "__main__":
    sys.exit(main())
