"""Device time by part, the reader's side: the loader on the trace
recorded on the chip, the buckets' seconds on a hand-made list, and the
fourteen metric files on runs that have nothing to read.

``data/recorded_spans.xplane.pb`` is ``test_host_spans.py``'s: a
two-layer decoder behind the program's server on a TPU v5e. The map of
its programs is not recorded (the program builds it from its own
compiled text, ``tests/test_scopes.py``), so every event of it is
``unscoped`` here, which is what a reader must make of a program it
does not know.
"""

from pathlib import Path

import pytest

from benchmark.lib import common, scope_shares
from benchmark.lib import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).parent / "data" / "recorded_spans.xplane.pb"
BENCH = common.load_json(ROOT / "BENCHMARK.json")
MINE = [m for m in BENCH["per_layer"]
        if m["name"].startswith("device_") and "_share" in m["name"]
        and not m["name"].startswith("device_idle")]


def _scopes():
    from pytorch_distributed_nn_tpu.obs import scopes
    return scopes


def test_the_loader_keeps_every_event_with_its_text_and_its_program():
    devs = scope_shares.load_ops(str(RECORDED))
    plain = tr.load(str(RECORDED))
    assert sorted(devs) == sorted(plain)
    dev, ref = devs[0], plain[0]
    assert len(dev["ops"]) == len(ref["ops"]) == 1868
    assert len(dev["async"]) == len(ref["async"])
    module, text, s, e = dev["ops"][0]
    assert text.startswith("%") and " = " in text and e > s
    programs = {m for m, *_ in dev["ops"]}
    assert {"jit__serve_step", "jit__serve_prefill"} <= programs
    assert not any("(" in m for m in programs)     # no fingerprint
    # an operation lies in the execution that was running when it began
    assert sum(1 for m, *_ in dev["ops"] if not m) < 0.01 * len(dev["ops"])


def test_a_program_the_map_does_not_know_is_all_unscoped():
    scopes = _scopes()
    dev = scope_shares.load_ops(str(RECORDED))[0]
    got = scopes.join(dev["ops"], dict(modules={}))
    assert set(got["by_part"]) == {"unscoped"}
    # the parts partition the busy time, the union of the operations
    assert got["busy"] == pytest.approx(tr.busy_ns(tr.load(
        str(RECORDED))[0]), rel=1e-9)
    assert got["ambiguous"] == 0.0 and len(got["unscoped"]) == 10


def test_the_recorded_events_join_a_map_by_program_and_name():
    scopes = _scopes()
    dev = scope_shares.load_ops(str(RECORDED))[0]
    # every instruction of the step program, by the name the loader kept
    names = {scopes.split_instruction(t)[0] for m, t, *_ in dev["ops"]
             if m == "jit__serve_step"}
    table = {n: [("", "jit(_serve_step)/M/mixer", "mixer")] for n in names}
    got = scopes.join(dev["ops"], dict(modules={"jit__serve_step": table}))
    step = got["by_program"]["jit__serve_step"]
    assert set(step) == {"mixer"}
    assert got["by_part"]["mixer"] == pytest.approx(step["mixer"][0])
    assert got["busy"] == pytest.approx(sum(got["by_part"].values()))


def test_a_buckets_seconds_and_what_nothing_overlaps():
    scopes = _scopes()
    sc = "jit(step)/grad_reduce/bucket"
    modules = {"jit_step": {
        "all-reduce-start.1": [("f32[8]", sc + "1", "grad_reduce")],
        "all-reduce-done.1": [("f32[8]", sc + "1", "grad_reduce")],
        "all-reduce-start.2": [("f32[8]", sc + "2", "grad_reduce")],
        "all-reduce-done.2": [("f32[8]", sc + "2", "grad_reduce")],
        "fusion.1": [("f32[8]", "jit(step)/transpose(jvp(B))/l0",
                      "backward")],
    }}
    ev = lambda n, s, e: ("jit_step", f"%{n} = f32[8]{{0}} op(%p)",  # noqa: E731
                          float(s), float(e))
    dev = dict(
        ops=[ev("all-reduce-start.1", 0, 1), ev("fusion.1", 1, 6),
             ev("all-reduce-done.1", 8, 9), ev("all-reduce-start.2", 9, 10),
             ev("all-reduce-done.2", 20, 21)],
        # in flight from the start to the done
        **{"async": [ev("all-reduce-start.1", 0, 9),
                     ev("all-reduce-start.2", 9, 21)]})
    got = scope_shares.bucket_seconds(dev, modules, scopes)
    # bucket 1: 9 ns on the device, the backward's fusion beside 5 of them
    assert got["bucket1"] == pytest.approx((9e-9, 4e-9))
    # bucket 2: 12 ns, nothing beside it
    assert got["bucket2"] == pytest.approx((12e-9, 12e-9))


def test_an_untraced_run_and_a_run_without_a_file_say_nothing():
    assert scope_shares.of_run(dict(trace=None)) is None
    run = dict(trace=dict(window_s=1.0), workload="no_such_cell_ever")
    assert scope_shares.of_run(run) is None
    assert run["_scope_shares"] is None        # looked for once a run
    assert scope_shares.share_pct(run, "mixer") is None


def test_a_process_that_noted_no_program_says_nothing():
    scopes = _scopes()
    scopes.reset()
    assert scope_shares.analyze(str(RECORDED)) is None


def test_fourteen_entries_fourteen_files():
    assert len(MINE) == 14
    assert {m["layer"] for m in MINE} == {"Model programs"}
    assert {m["source"] for m in MINE} == {"device_trace"}
    assert {m["unit"] for m in MINE} == {"%"}


@pytest.mark.parametrize("entry", MINE, ids=lambda m: m["name"])
def test_a_reader_that_finds_nothing_says_nothing(entry):
    """What the parent commit's traced run gives the driver: None, no
    exception; and every file reads the part its name says."""
    path = ROOT / "benchmark" / "metrics" / f"{entry['name']}.py"
    mod = common.load_module(
        path, "scope_metric_" + entry["name"].replace(".", "_"))
    assert mod.read(dict(trace=None)) is None
    part = entry["name"].removeprefix("device_").partition("_share")[0]
    got = mod.read(dict(trace={}, _scope_shares=dict(
        busy=200.0, by_part={part: 50.0, "other": 150.0})))
    assert got == 25.0
    assert mod.read(dict(trace={}, _scope_shares=dict(
        busy=200.0, by_part={"other": 200.0}))) == 0.0
