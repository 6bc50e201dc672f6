"""The trace reduction: on hand-made intervals and on a recorded trace.

``data/recorded.xplane.pb`` was taken on a TPU v5e by
``record_trace.py``: programs a, a, b, a host sleep of 20 ms, then a, b.
"""

from pathlib import Path

import pytest

from benchmark.lib import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "recorded.xplane.pb"


def test_union_total_and_subtract():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_names():
    assert tr.op_name("%fusion.3 = bf16[8]{0} fusion(%x), kind=kLoop") \
        == "fusion"
    assert tr.op_name("%all-reduce-start.1 = (f32[4]) all-reduce-start(") \
        == "all-reduce-start"
    assert tr.op_name("%convolution_multiply_fusion = f32[] fusion(") \
        == "convolution_multiply_fusion"
    assert tr.module_name("jit__serve_step(1234567)") == "jit__serve_step"
    # JAX names a collective's instruction after its primitive
    psum = ("%psum.5 = f32[1024,768]{1,0:T(8,128)} all-reduce(f32[1024,768]"
            "{1,0:T(8,128)} %fusion.9), channel_id=3, to_apply=%add")
    assert tr.op_name(psum) == "psum" and tr.opcode(psum) == "all-reduce"
    assert tr.event_name(psum) == "all-reduce"
    start = ("%copy-start = (bf16[8,8]{1,0:T(8,128)(2,1)S(1)}, bf16[8,8]{1,0},"
             " u32[]{:S(2)}) copy-start(bf16[8,8]{1,0:T(8,128)(2,1)} %x.1)")
    assert tr.opcode(start) == "copy-start"
    assert tr.event_name(start) == "copy-start"
    assert tr.event_name("%fusion.3 = bf16[8]{0} fusion(%x), kind=kLoop") \
        == "fusion"
    assert tr.is_collective("all-reduce-start")
    assert tr.is_collective("reduce-scatter")
    assert not tr.is_collective("fusion")


def _dev():
    # two steps; in each: compute 0-10, an async all-reduce in flight
    # 4-16, compute 12-14 hides two of its six exposed units, and the
    # core waits in all-reduce-done 14-16
    ops, asy, mods = [], [], []
    for base in (0.0, 100.0):
        ops += [("fusion", base + 0, base + 10),
                ("all-reduce-start", base + 4, base + 4.5),
                ("fusion", base + 12, base + 14),
                ("all-reduce-done", base + 14, base + 16)]
        asy += [("all-reduce-start", base + 4, base + 16)]
        mods += [("jit_step", base + 0, base + 16)]
    return {"ops": sorted(ops, key=lambda e: e[1]), "async": asy,
            "modules": mods}


def test_busy_idle_gaps_and_exposed_collectives_by_hand():
    dev = _dev()
    # busy: [0,10] + [12,16] per step = 14, twice
    assert tr.busy_ns(dev) == 28
    # collective in flight 4-16; compute covers 4-10 and 12-14: exposed
    # 10-12 and 14-16 = 4 per step
    assert tr.collective_exposed_ns(dev) == 8
    assert tr.collectives_launched(dev) == 2
    assert tr.idle_gaps(dev) == [["after:jit_step_before:jit_step",
                                  84 / 1e9]]
    assert tr.module_seconds(dev) == {"jit_step": (2, 32 / 1e9)}
    s = tr.summarize({0: dev, 1: dev}, window_s=116 / 1e9)
    assert s["busy_s"] == pytest.approx(28 / 1e9)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(88 / 116)


def test_recorded_trace():
    devs = tr.load(str(RECORDED))
    assert sorted(devs) == [0]
    dev = devs[0]
    assert [m[0] for m in dev["modules"]] == [
        "jit_prog_a", "jit_prog_a", "jit_prog_b", "jit_prog_a", "jit_prog_b"]
    mods = tr.module_seconds(dev)
    assert mods["jit_prog_a"][0] == 3 and mods["jit_prog_b"][0] == 2
    # each prog_a ran ~50 us on the device, each prog_b ~7 us
    assert 45e-6 < mods["jit_prog_a"][1] / 3 < 56e-6
    assert 6e-6 < mods["jit_prog_b"][1] / 2 < 9e-6
    busy = tr.busy_ns(dev) / 1e9
    assert busy == pytest.approx(
        mods["jit_prog_a"][1] + mods["jit_prog_b"][1], rel=0.05)
    # the host slept 20 ms between a "b" and the next "a": the longest
    # gap by far, and named by the programs on either side
    gaps = tr.idle_gaps(dev)
    assert gaps[0][0] == "after:jit_prog_b_before:jit_prog_a"
    assert 0.020 < gaps[0][1] < 0.030
    span = (max(e for _, _, e in dev["ops"])
            - min(s for _, s, _ in dev["ops"])) / 1e9
    s = tr.summarize(devs, span)
    idle = 1 - s["busy_s"] / s["window_s"]
    assert 0.98 < idle < 1.0          # ~165 us busy in ~22.5 ms
    assert s["device_ops"][0][0] == "fusion"
    assert s["collectives_launched"] == 0
    assert s["collective_exposed_s"] == 0
