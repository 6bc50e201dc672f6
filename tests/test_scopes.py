"""obs.scopes: the program's own map from compiled instruction to scope.

What a dispatch that traced notes, what a steady one does not, what the
map makes of a compiled text (a two-layer Mistral-shaped model's and a
hand-written one), how device events join to parts so that they
partition the busy time, that the noted entries keep no buffer alive,
and what an xray capture writes of it.
"""

import gc
import json
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.obs import flight, jitwatch, scopes, xray
from pytorch_distributed_nn_tpu.serve import DecodeSpec, ServingEngine

VOCAB = 89   # a shape no other test file serves: its programs trace here


@pytest.fixture(autouse=True)
def _fresh():
    scopes.reset()
    flight.reset_recorder(enabled=True)
    obs.reset_registry()
    yield
    scopes.reset()


@pytest.fixture(scope="module")
def mistral_shaped():
    """Grouped-query, rotary, SwiGLU, through ``models/llama.py``."""
    model = get_model(ModelConfig(
        name="llama3_8b", compute_dtype="float32", dtype="float32",
        extra=dict(num_layers=2, d_model=48, num_heads=4, num_kv_heads=2,
                   mlp_dim=96, vocab_size=VOCAB, rope_theta=1e6)))
    params = model.init(jax.random.key(2), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, VOCAB, size=(n,)).astype(np.int32)


def _names():
    return [name for name, _ in scopes.noted()]


# -- classify ---------------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(_serve_step)/Llama/layer3/mixer/attn/dot_general", "mixer"),
    ("jit(_serve_step)/Llama/layer3/mixer/attn/cache_write/scatter",
     "cache_write"),
    ("jit(_serve_step)/SdarMoe/layer0/sdar/moe/router/top_k", "ffn"),
    ("jit(_serve_step)/Jamba/layer1/jamba/mamba/in_proj/dot_general",
     "mixer"),
    ("jit(_serve_step)/Lfm2Moe/layer0/lfm2/conv/cache_write/select_n",
     "cache_write"),
    ("jit(_serve_step)/Brumby/layer0/brumby/retention/out/dot_general",
     "mixer"),
    ("jit(_serve_step)/AXK1/layer2/axk1/shared_expert/mul", "ffn"),
    ("jit(_serve_step)/Llama/head/lm_head/dot_general", "head"),
    ("jit(_serve_step)/head/argmax", "head"),
    ("jit(_serve_step)/Llama/tok_embed/jit(_take)/gather", "other"),
    ("jit(_insert_row)/dynamic_update_slice", "cache_write"),
    ("jit(_zero_cache)/Llama/layer0/mixer/attn/broadcast_in_dim",
     "cache_write"),
    ("jit(step)/jvp(Bert)/layer0/mixer/attn/dot_general", "forward"),
    ("jit(step)/transpose(jvp(Bert))/layer0/ffn/mlp_in/dot_general",
     "backward"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/grad_reduce/bucket2/psum", "grad_reduce"),
    ("params['layer0']['attn']['key']['kernel']", "unscoped"),
    ("lengths", "unscoped"),
    ("", "unscoped"),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name)[1] == want


def test_the_deepest_component_that_names_a_part_wins():
    scope, part = scopes.classify(
        "jit(_serve_prefill)/SdarMoe/layer0/sdar/attn/attn/cache_write/"
        "dynamic_update_slice")
    assert part == "cache_write" and scope.endswith("attn/cache_write")
    assert scopes.layer_part("jit(step)/transpose(jvp(Bert))/layer0/ffn") \
        == "ffn"
    assert set(scopes.COMPONENTS.values()) | {
        "forward", "backward", "other", "unscoped"} == set(scopes.PARTS)


# -- the parser, on a hand-written text --------------------------------------

TEXT = '''HloModule jit_toy, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "toy.py"

%fused_named (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%p0), metadata={op_name="jit(toy)/Toy/layer0/ffn/exp"}
}

%fused_anonymous (p1: f32[8], p2: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  %p2 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%p1, %p2), metadata={op_name="jit(toy)/Toy/layer0/mixer/attn/mul"}
  %add.7 = f32[8]{0} add(%mul.1, %p2), metadata={op_name="jit(toy)/Toy/layer0/mixer/attn/add"}
  ROOT %neg.2 = f32[8]{0} negate(%add.7), metadata={op_name="jit(toy)/Toy/layer0/ffn/neg"}
}

%body (carry: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %x = f32[8]{0} get-tuple-element(%carry), index=1
  %fusion.9 = f32[8]{0:T(256)} fusion(%x), kind=kLoop, calls=%fused_named, metadata={op_name="jit(toy)/Toy/head/while/body/exp"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%i, %fusion.9)
}

%cond (carry.1: (s32[], f32[8])) -> pred[] {
  %carry.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.5 (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%arg)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0:T(256)} fusion(%copy-done.1), kind=kLoop, calls=%fused_named, metadata={op_name="jit(toy)/Toy/layer0/ffn/exp"}
  %fusion.2 = f32[8]{0:T(256)} fusion(%fusion.1, %copy-done.1), kind=kLoop, calls=%fused_anonymous
  %zero = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%zero, %fusion.2)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(toy)/Toy/head/while"}
  %custom-call.3 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/Toy/layer1/sdar/moe/grouped_experts/pallas_call"}
  %lonely.1 = f32[8]{0} copy(%zero)
  ROOT %get-tuple-element.8 = f32[8]{0} get-tuple-element(%while.4), index=1
}
'''


def test_parse_a_hand_written_text():
    module, got = scopes.parse(TEXT)
    assert module == "jit_toy"
    # a fusion is its own op_name
    assert got["fusion.1"] == ("f32[8]", "jit(toy)/Toy/layer0/ffn", "ffn")
    # without one: the commonest part among its fused instructions
    assert got["fusion.2"][2] == "mixer"
    assert got["fusion.2"][1] == "jit(toy)/Toy/layer0/mixer/attn"
    # a while and its body: both executed, both in the map
    assert got["while.4"][2] == "head"
    assert got["fusion.9"] == ("f32[8]", "jit(toy)/Toy/head/while/body",
                               "head")
    # a Pallas kernel is one instruction, under its scope
    assert got["custom-call.3"][2] == "ffn"
    # what the compiler put in belongs to what consumes it, two hops on
    assert got["copy-done.1"][2] in ("ffn", "mixer")
    assert got["copy-start.1"][2] == got["copy-done.1"][2]
    assert got["copy-start.1"][1].startswith("<-")
    assert got["copy-start.1"][0] == "(f32[8],f32[8],u32[])"
    # nothing near it has a part
    assert got["lonely.1"] == ("s32[]", "", "unscoped") \
        or got["lonely.1"][2] == "unscoped"
    # no time of their own, or inside a fusion: not in the map
    for name in ("arg", "zero", "tuple.1", "get-tuple-element.8", "exp.1",
                 "mul.1", "i", "x"):
        assert name not in got


def test_a_program_that_is_one_part_whole():
    _, got = scopes.parse(TEXT.replace("jit_toy", "jit__insert_row"),
                          whole="cache_write")
    assert {r[2] for r in got.values()} == {"cache_write"}


def test_split_instruction_reads_a_trace_events_name():
    text = ("%copy-start.19 = (s32[1,16]{1,0:T(1,128)S(1)}, "
            "s32[1,16]{1,0:T(1,128)}, u32[]{:S(2)}) "
            "copy-start(s32[1,16]{1,0:T(1,128)} %tokens.1)")
    name, shape, opcode, _ = scopes.split_instruction(text)
    assert (name, shape, opcode) == (
        "copy-start.19", "(s32[1,16],s32[1,16],u32[])", "copy-start")
    name, shape, opcode, _ = scopes.split_instruction(
        "%broadcast_in_dim.1 = bf16[1,16,2,16]{3,2,1,0:T(2,128)(2,1)} "
        "broadcast(bf16[]{:T(256)S(6)} %copy), dimensions={}")
    assert (name, shape, opcode) == (
        "broadcast_in_dim.1", "bf16[1,16,2,16]", "broadcast")


# -- join, on a hand-made event list -----------------------------------------

MAPS = dict(modules={
    "jit__serve_step": {
        "fusion.1": [("bf16[8,256]", "jit(_serve_step)/M/l0/mixer", "mixer")],
        "fusion.2": [("bf16[8,512]", "jit(_serve_step)/M/l0/ffn", "ffn")],
        "while.1": [("(s32[],bf16[8])", "jit(_serve_step)/M/head", "head")],
        "fusion.7": [("bf16[8]", "jit(_serve_step)/M/head/while/body",
                      "head")],
    },
    # two bucket programs of one name: fusion.3 is the mixer's in one and
    # the ffn's in the other, told apart by shape; fusion.4 is not
    "jit__serve_prefill": {
        "fusion.3": [("bf16[1,16,64]", "jit(_serve_prefill)/M/l0/mixer",
                      "mixer"),
                     ("bf16[1,32,128]", "jit(_serve_prefill)/M/l0/ffn",
                      "ffn")],
        "fusion.4": [("bf16[1,64]", "jit(_serve_prefill)/M/l0/mixer",
                      "mixer"),
                     ("bf16[1,64]", "jit(_serve_prefill)/M/head", "head")],
        "fusion.5": [("bf16[1,16]", "jit(_serve_prefill)/M/l0/mixer",
                      "mixer"),
                     ("bf16[1,32]", "jit(_serve_prefill)/M/l1/mixer/attn",
                      "mixer")],
    },
})


def _ev(module, name, shape, s, e, opcode="fusion"):
    return (module, f"%{name} = {shape}{{0:T(256)}} {opcode}(f32[8]{{0}} "
                    f"%p)", float(s), float(e))


def test_join_partitions_the_busy_time():
    step, pre = "jit__serve_step", "jit__serve_prefill"
    ops = [
        _ev(step, "fusion.1", "bf16[8,256]", 0, 10),
        _ev(step, "fusion.2", "bf16[8,512]", 10, 25),
        # a while's body operations lie inside the while's own event
        _ev(step, "while.1", "(s32[], bf16[8])", 30, 60, "while"),
        _ev(step, "fusion.7", "bf16[8]", 32, 40),
        _ev(step, "fusion.7", "bf16[8]", 45, 58),
        # idle 60-100; the prefill's buckets
        _ev(pre, "fusion.3", "bf16[1,16,64]", 100, 104),
        _ev(pre, "fusion.3", "bf16[1,32,128]", 104, 110),
        _ev(pre, "fusion.4", "bf16[1,64]", 110, 113),      # ambiguous
        _ev(pre, "fusion.5", "bf16[1,32]", 113, 115),      # one part: fine
        _ev(pre, "fusion.99", "bf16[2]", 115, 116),        # not in the map
        _ev("jit_unknown", "fusion.1", "bf16[8,256]", 120, 127),
        _ev("", "fusion.2", "bf16[8,512]", 130, 131),      # in no execution
    ]
    got = scopes.join(ops, MAPS)
    assert got["by_part"] == dict(mixer=16.0, ffn=21.0, head=30.0,
                                  unscoped=12.0)
    assert got["busy"] == sum(got["by_part"].values()) == 79.0
    assert got["ambiguous"] == 3.0
    assert got["unscoped"][0] == ("fusion.1", 7.0)
    assert dict(got["unscoped"])["fusion.4"] == 3.0
    assert got["by_program"][step]["head"] == (30.0, 3)
    # what a breakdown's one name is: mixer, ffn, head, and the unknown
    assert got["by_name"]["fusion"] == dict(
        mixer=16.0, ffn=21.0, head=21.0, unscoped=12.0)
    assert got["by_name"]["while"] == dict(head=9.0)
    assert got["by_program"][pre]["mixer"] == (6.0, 2)
    assert got["by_program"]["jit_unknown"]["unscoped"] == (7.0, 1)


def test_join_gives_an_overlap_to_the_event_that_started_last():
    step = "jit__serve_step"
    ops = [_ev(step, "fusion.1", "bf16[8,256]", 0, 10),
           _ev(step, "fusion.2", "bf16[8,512]", 6, 14)]   # sticks out
    got = scopes.join(ops, MAPS)
    assert got["by_part"] == dict(mixer=6.0, ffn=8.0)
    assert got["busy"] == 14.0
    assert scopes.join([], MAPS)["busy"] == 0.0
    assert scopes.join(ops, {})["by_part"] == dict(unscoped=14.0)


def test_join_splits_a_training_step_by_what_lies_under_it():
    maps = dict(modules={"jit_step": {
        "fusion.1": [("f32[8]", "jit(step)/jvp(B)/l0/mixer/attn",
                      "forward")],
        "fusion.2": [("f32[8]", "jit(step)/transpose(jvp(B))/l0/ffn",
                      "backward")],
        "fusion.3": [("f32[8]", "jit(step)/optimizer", "optimizer")]}})
    ops = [_ev("jit_step", "fusion.1", "f32[8]", 0, 4),
           _ev("jit_step", "fusion.2", "f32[8]", 4, 10),
           _ev("jit_step", "fusion.3", "f32[8]", 10, 11)]
    got = scopes.join(ops, maps)
    assert got["by_part"] == dict(forward=4.0, backward=6.0, optimizer=1.0)
    assert got["by_layer"] == {"forward/mixer": 4.0, "backward/ffn": 6.0}


def test_lookup_name_over_every_module():
    mods = MAPS["modules"]
    assert scopes.lookup_name(mods, "fusion.7")[1] == "head"
    assert scopes.lookup_name(mods, "%fusion.5")[1] == "mixer"
    assert scopes.lookup_name(mods, "fusion.4") == ("", "unscoped")
    assert scopes.lookup_name(mods, "nothing.1") == ("", "unscoped")


# -- note: once a variant, never in a steady round ---------------------------

def test_a_steady_round_notes_nothing(mistral_shaped):
    model, params = mistral_shaped
    eng = ServingEngine(model, params, max_slots=3, max_seq_len=256)
    eng.submit(_prompt(5), 240)
    for _ in range(100):
        eng.step()
    assert _names().count("_serve_step") == 1
    assert _names().count("_serve_prefill") == 1
    calls = scopes.note_calls
    for _ in range(50):
        eng.step()
    assert scopes.note_calls == calls      # no call, not a deduplicated one
    # a second bucket of the prefill, and a second variant of the step
    # (a sampled request's round carries the sampling state)
    eng.submit(_prompt(20, 1), 4)
    eng.submit(_prompt(6, 2), 4, decode=DecodeSpec(temperature=0.8, seed=3))
    for _ in range(20):
        eng.step()
    assert _names().count("_serve_prefill") == 3   # 16, 32, and sampled
    assert _names().count("_serve_step") == 2
    calls = scopes.note_calls
    for _ in range(20):
        eng.step()
    assert scopes.note_calls == calls


def test_the_map_of_a_mistral_shaped_model(mistral_shaped):
    model, params = mistral_shaped
    # (a bucket the test before did not compile: a program this process
    # already holds does not trace, and is not noted again)
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=128)
    eng.submit(_prompt(40), 6)
    for _ in range(12):
        eng.step()
    maps = scopes.build()
    assert maps is not scopes.build() and maps == scopes.build()
    progs = {p["program"]: p for p in maps["programs"]}
    assert {"_serve_step", "_serve_prefill", "_insert_row", "_zero_cache",
            "_write_rows"} <= set(progs)
    assert all(p["cache"] in ("memory", "hit", "miss") for p in
               progs.values())
    step = maps["modules"]["jit__serve_step"]

    def parts_of(needle):
        return {r[2] for rs in step.values() for r in rs if needle in r[1]}

    assert parts_of("/mixer/attn/query") == {"mixer"}
    assert parts_of("bkgts,bskd->btkgd") == {"mixer"}     # scores x values
    assert parts_of("/ffn/gate_proj") == {"ffn"}
    assert parts_of("/ffn/down_proj") == {"ffn"}
    assert parts_of("/attn/cache_write") == {"cache_write"}
    assert parts_of("/head/lm_head") == {"head"}
    assert parts_of("jit(_serve_step)/head") == {"head"}   # the argmax
    assert parts_of("tok_embed") == {"other"}
    scatters = [r for name, rs in step.items() for r in rs
                if "scatter" in name]
    assert scatters and {r[2] for r in scatters} == {"cache_write"}
    pre = maps["modules"]["jit__serve_prefill"]
    assert {"mixer", "ffn", "head", "cache_write"} <= {
        r[2] for rs in pre.values() for r in rs}
    for whole in ("jit__insert_row", "jit__zero_cache", "jit__write_rows"):
        assert {r[2] for rs in maps["modules"][whole].values()
                for r in rs} == {"cache_write"}


def test_the_noted_entries_keep_no_buffer_alive(mistral_shaped):
    model, params = mistral_shaped
    eng = ServingEngine(model, params, max_slots=2, max_seq_len=40)
    eng.submit(_prompt(5), 3)
    for _ in range(8):
        eng.step()
    leaves = [weakref.ref(leaf) for leaf in jax.tree.leaves(eng._cache)]
    assert leaves and all(r() is not None for r in leaves)
    del eng
    gc.collect()
    assert all(r() is None for r in leaves)      # the cache is gone
    assert "_serve_step" in _names()             # its programs are not
    for _, args in scopes.noted():
        for leaf in jax.tree.leaves(args):
            assert not isinstance(leaf, (jax.Array, np.ndarray))
    # and the map is still to be had, from shapes alone
    assert scopes.build()["modules"]["jit__serve_step"]


def test_note_keeps_a_committed_sharding_and_no_other():
    x = jnp.ones((4, 4))                                  # uncommitted
    y = jax.device_put(jnp.ones((4,)), jax.devices()[1])  # committed
    f = jax.jit(lambda a, b, k: a @ b * k)
    assert scopes.note(f, (x, y, 3))
    assert not scopes.note(f, (x, y, 3))                  # the same variant
    assert scopes.note(f, (x, y, 4))       # a host scalar is kept as it is
    (_, (a, b, k)), _ = scopes.noted()
    assert a.sharding is None and b.sharding == y.sharding and k == 3
    assert not scopes.note(lambda a: a, (x,))   # no program: passed over


def test_a_trainers_step_is_noted_once():
    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    cfg = get_config("mlp_mnist", **{"steps": 6, "log_every": 3})
    trainer = Trainer(cfg)
    trainer.train(steps=3)
    calls = scopes.note_calls
    assert calls == 1 and len(scopes.noted()) == 1
    trainer.train(steps=3)
    assert scopes.note_calls == calls
    trainer.close()
    del trainer
    gc.collect()
    [module] = scopes.build()["modules"].values()
    parts = {r[2] for rs in module.values() for r in rs}
    assert {"forward", "backward", "optimizer"} <= parts


# -- an xray capture ---------------------------------------------------------

def test_an_xray_capture_writes_the_map(tmp_path, monkeypatch,
                                        mistral_shaped, capsys):
    import importlib.util

    monkeypatch.setenv(xray.ENV_XRAY, "steps=3")
    xray.reset()
    eng_x = xray.maybe_init(rank=0, base_dir=tmp_path)
    assert eng_x is not None
    try:
        model, params = mistral_shaped
        eng = ServingEngine(model, params, max_slots=2, max_seq_len=24)
        eng.submit(_prompt(5), 12)
        for _ in range(3):
            eng.step()
        cap = xray.capture_now("manual", step=eng.scheduler.round)
        assert cap is not None
        for _ in range(8):
            eng.step()
        assert eng_x._active is None
    finally:
        xray.reset()
    smap = json.loads(open(os.path.join(cap, xray.SCOPE_MAP_NAME)).read())
    assert "jit__serve_step" in smap["modules"]
    assert smap["version"] == scopes.VERSION
    summary = json.loads(open(os.path.join(cap, xray.SUMMARY_NAME)).read())
    att = summary["attribution"]
    if att["source"] == "trace":
        assert att["rows"] and all("part" in r and "scope" in r
                                   for r in att["rows"])
        assert abs(sum(att["by_part"].values()) - att["total_s"]) < 1e-9
        assert set(att["by_part"]) <= set(scopes.PARTS)
    # the renderer prints the block
    spec = importlib.util.spec_from_file_location(
        "obs_xray_script", os.path.join(
            os.path.dirname(__file__), "..", "scripts", "obs_xray.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    if att["source"] == "trace":
        assert "by_part (time only)" in out and " part " in out


def test_build_attribution_gives_every_row_its_part():
    rows = [dict(name="fusion.1", ph="X", dur=40.0),
            dict(name="fusion.7", ph="X", dur=10.0),
            dict(name="fusion.4", ph="X", dur=5.0),
            dict(name="no_such_instruction", ph="X", dur=5.0)]
    import gzip
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with gzip.open(os.path.join(d, "perfetto_trace.json.gz"), "wt") as f:
            json.dump(dict(traceEvents=rows), f)
        att = xray.build_attribution(trace_dir=d, scope_map=MAPS)
        bare = xray.build_attribution(trace_dir=d)
    parts = {r["op"]: r["part"] for r in att["rows"]}
    assert parts == {"fusion.1": "mixer", "fusion.7": "head",
                     "fusion.4": "unscoped",
                     "no_such_instruction": "unscoped"}   # not dropped
    assert att["by_part"] == pytest.approx(
        dict(mixer=40e-6, head=10e-6, unscoped=10e-6))
    assert bare["by_part"] == {} and "part" not in bare["rows"][0]
    table = xray.render_op_table(att)
    assert "by_part (time only): mixer" in table
    assert "by_part" not in xray.render_op_table(bare)
