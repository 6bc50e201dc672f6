#!/usr/bin/env python
"""Operate a process-backed serving fleet (serve/procfleet.py).

The multi-host deployment loop (docs/serving.md has the full runbook):

    # host A: the store every fleet word travels through
    python scripts/fleet_deploy.py store --port 7777

    # host B: coordinator + replica subprocesses
    python scripts/fleet_deploy.py start --store hostA:7777 \
        --replicas 3 --backend tiny --autoscale 1

    # disaggregated pools: prefill + decode replicas, KV handoff
    # streamed cross-process over the store wire (serve/kv_wire.py)
    python scripts/fleet_deploy.py start --store hostA:7777 \
        --fleet-prefill 1 --fleet-decode 2

    # cross-host provisioning: each worker spawn goes through the
    # template ({cmd} = the shell-quoted worker command); the worker
    # enrolls itself back through the store (pid, host, role)
    python scripts/fleet_deploy.py start --store hostA:7777 \
        --replicas 2 --spawn-template 'ssh hostC {cmd}'

    # host B died? any host: take over WITHOUT restarting workers —
    # live replicas are adopted pid-for-pid, stranded requests are
    # re-admitted with their emitted prefix, Helm's journal continues
    python scripts/fleet_deploy.py recover --store hostA:7777

    # anywhere: what does the store say the fleet looks like?
    python scripts/fleet_deploy.py status --store hostA:7777

One chip-backed replica per host: ``--backend tiny`` and ``preset``
run on a jax backend, and a chip belongs to one process — a second such
worker on the same host exits saying which pid holds the chip
(runtime/device.claim_chip). Put further replicas on other hosts
(``--spawn-template``), give each its own ``TPU_VISIBLE_CHIPS``, or run
them on the CPU (``JAX_PLATFORMS=cpu``). The coordinator itself never
touches a device.

``start``/``recover`` run until SIGINT/SIGTERM, then drain and stop.
``status`` is read-only: one JSON object from the store's own state
(membership, coordinator beat age, journal depths) — exactly what a
recovering coordinator would see.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()


def _cmd_store(args) -> int:
    from pytorch_distributed_nn_tpu.runtime import native

    server = native.StoreServer(args.port)
    print(json.dumps({"event": "store_up", "port": server.port}),
          flush=True)
    stop = []
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.5)
    finally:
        server.stop()
    return 0


def _run_fleet(fleet) -> int:
    stop = []
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *a: stop.append(1))
    fleet.start()
    try:
        while not stop and not fleet.dead:
            time.sleep(0.5)
    finally:
        summary = fleet.summary()
        if not fleet.dead:
            fleet.stop()
        print(json.dumps({"event": "fleet_exit",
                          "coordinator_dead": fleet.dead,
                          **summary}, sort_keys=True), flush=True)
    # a dead coordinator is an incident, not a clean exit — the
    # operator (or a supervisor) should run `recover` next
    return 1 if fleet.dead else 0


def _cmd_start(args) -> int:
    from pytorch_distributed_nn_tpu.serve.procfleet import (
        ProcessFleet,
        TemplateProvisioner,
    )

    if bool(args.fleet_prefill) != bool(args.fleet_decode):
        print("error: disaggregation needs BOTH --fleet-prefill and "
              "--fleet-decode >= 1", file=sys.stderr)
        return 2
    provisioner = (TemplateProvisioner(args.spawn_template)
                   if args.spawn_template else None)
    fleet = ProcessFleet(
        replicas=args.replicas, backend=args.backend,
        prefill=args.fleet_prefill, decode=args.fleet_decode,
        role=args.role, provisioner=provisioner,
        preset=args.preset, ckpt=args.ckpt,
        namespace=args.namespace, store_endpoint=args.store or None,
        autoscale_spec=args.autoscale,
        heartbeat_timeout_s=args.heartbeat_timeout)
    print(json.dumps({"event": "coordinator_up", "mode": "fresh",
                      "incarnation": fleet.incarnation,
                      "disagg": fleet.disagg,
                      "store": fleet.store_endpoint,
                      "namespace": args.namespace},
                     sort_keys=True), flush=True)
    return _run_fleet(fleet)


def _cmd_recover(args) -> int:
    from pytorch_distributed_nn_tpu.serve.procfleet import ProcessFleet

    if not args.store:
        print("error: recover needs --store (the fleet's state lives "
              "there, not here)", file=sys.stderr)
        return 2
    fleet = ProcessFleet.recover_from(
        store_endpoint=args.store, namespace=args.namespace,
        backend=args.backend, preset=args.preset, ckpt=args.ckpt,
        autoscale_spec=args.autoscale,
        heartbeat_timeout_s=args.heartbeat_timeout)
    print(json.dumps({"event": "coordinator_up", "mode": "recover",
                      "incarnation": fleet.incarnation,
                      "gap_s": round(fleet.gap_s, 3),
                      "recovery": fleet.recovery,
                      "store": fleet.store_endpoint,
                      "namespace": args.namespace},
                     sort_keys=True), flush=True)
    return _run_fleet(fleet)


def _cmd_status(args) -> int:
    from pytorch_distributed_nn_tpu.serve.store import (
        PrefixStore, StoreJournal, make_store,
    )

    if not args.store:
        print("error: status needs --store", file=sys.stderr)
        return 2
    client = make_store(args.store)
    ns = PrefixStore(client, args.namespace)
    out: dict = {"store": args.store, "namespace": args.namespace}
    members = []
    if ns.check("members"):
        members = json.loads(ns.get("members", timeout_ms=2000).decode())
    out["members"] = members
    out["coordinator_incarnations"] = ns.add("coord/inc", 0)
    if ns.check("coord/beat"):
        out["coordinator_beat_age_s"] = round(
            time.time() - float(ns.get("coord/beat", timeout_ms=2000)),
            3)
    out["journal_len"] = len(StoreJournal(ns, "journal"))
    out["helm_journal_len"] = len(StoreJournal(ns, "helm"))
    beats = {}
    for m in members:
        key = f"hb/0/{m['index']}"
        if ns.check(key):
            beats[str(m["index"])] = round(
                time.time() - float(ns.get(key, timeout_ms=2000)), 3)
    out["beat_age_s"] = beats
    # Lighthouse (obs/audit.py): per-replica integrity state. Both
    # keys are absent on an unarmed fleet — status output is
    # byte-stable either way the fleet was launched.
    audits = {}
    for m in members:
        key = f"audit/{m['index']}"
        if not ns.check(key):
            continue
        p = json.loads(ns.get(key, timeout_ms=2000).decode())
        ent = dict(fingerprints=p.get("fingerprints", 0),
                   divergences=p.get("divergences", 0),
                   probe_failures=p.get("probe_failures", 0))
        if p.get("last_fp_t"):
            ent["last_fp_age_s"] = round(
                time.time() - float(p["last_fp_t"]), 3)
        audits[str(m["index"])] = ent
    if audits:
        out["audit"] = audits
    quarantined = [dict(replica=m["index"], reason=m["quarantined"])
                   for m in members if m.get("quarantined")]
    if quarantined:
        out["quarantined"] = quarantined
    client.close()
    print(json.dumps(out, sort_keys=True))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_store = sub.add_parser("store", help="run a standalone native "
                                           "store server")
    p_store.add_argument("--port", type=int, default=0,
                         help="listen port (0 = ephemeral, printed)")
    for name in ("start", "recover", "status"):
        p = sub.add_parser(name)
        p.add_argument("--store", default="",
                       help="store endpoint host:port (start only: "
                            "empty = own an in-process server)")
        p.add_argument("--namespace", default="fleet")
        if name != "status":
            p.add_argument("--backend",
                           choices=("stub", "tiny", "preset"),
                           default="tiny")
            p.add_argument("--preset", default="",
                           help="config.PRESETS name for --backend "
                                "preset (worker validates; the error "
                                "names every preset)")
            p.add_argument("--ckpt", default="",
                           help="optional Orbax params checkpoint for "
                                "--backend preset")
            p.add_argument("--autoscale", default="",
                           help="TPUNN_AUTOSCALE-grammar Helm spec "
                                "(empty = no autoscaler); on a "
                                "disaggregated fleet Helm scales each "
                                "pool on its own pressure")
            p.add_argument("--heartbeat-timeout", type=float,
                           default=5.0)
        if name == "start":
            p.add_argument("--replicas", type=int, default=2)
            p.add_argument("--fleet-prefill", type=int, default=0,
                           help="disaggregated prefill pool size "
                                "(needs --fleet-decode too); KV "
                                "handoff streams over serve/kv_wire")
            p.add_argument("--fleet-decode", type=int, default=0,
                           help="disaggregated decode pool size")
            p.add_argument("--role",
                           choices=("unified", "prefill", "decode"),
                           default="unified",
                           help="role for ALL --replicas workers "
                                "(enrolling one pool of a fleet whose "
                                "other pool runs elsewhere)")
            p.add_argument("--spawn-template", default="",
                           help="cross-host spawn command template; "
                                "{cmd} = shell-quoted worker command, "
                                "{index}/{role} available (e.g. "
                                "'ssh hostC {cmd}'); workers enroll "
                                "back through the store")
    args = ap.parse_args()
    return {"store": _cmd_store, "start": _cmd_start,
            "recover": _cmd_recover, "status": _cmd_status}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
