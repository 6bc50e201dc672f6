"""Mosaic compiles of the main-path kernels at real widths, and the
serve step's cache write as the chip's compiler leaves it, without a
chip.

The TPU's compiler is installed and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``). Interpret
mode cannot show what this shows: a slice off the tiling, too much
VMEM, a kernel that cannot be partitioned. The dispatchers see the CPU
here and would take their reference branch, so the kernel functions are
compiled directly. Nothing runs: a compile that passes is not a chip
run. Skipped as a whole where the topology cannot be described (no
libtpu, or its lock is held by another process).
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means: skip
        pytest.skip(f"v5e:2x2 topology cannot be described: {e}")


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: the next one would
    warn and compile again. Off around this module."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _flash(heads, kv_heads, T, D, causal):
    """Forward and both backward passes: ``_flash_diff`` under grad."""
    from pytorch_distributed_nn_tpu.ops.pallas import flash_attention as fa

    def build(arg):
        def loss(q, k, v):
            o = fa._flash_diff(q, k, v, causal, fa._pick_block(T, 1024),
                               fa._pick_block(T, 1024))
            return o.astype(jnp.float32).sum()

        return (jax.grad(loss, argnums=(0, 1, 2)),
                [arg((heads, T, D), jnp.bfloat16),
                 arg((kv_heads, T, D), jnp.bfloat16),
                 arg((kv_heads, T, D), jnp.bfloat16)], 3)
    return build


def _int8(m, k, n):
    from pytorch_distributed_nn_tpu.ops.pallas import int8_matmul as i8

    def build(arg):
        kp, np_ = i8.padded_kn(k, n)
        return (lambda x, q, s: i8._int8_matmul_tpu(
                    x, q, s, out_dtype=jnp.bfloat16),
                [arg((m, kp), jnp.bfloat16), arg((kp, np_), jnp.int8),
                 arg((1, np_), jnp.float32)], 1)
    return build


def _quantize(n):
    from pytorch_distributed_nn_tpu.ops.pallas import quantize as qz

    def build(arg):
        return (lambda x, s: qz._quantize_tpu(x, s, 0),
                [arg((n,), jnp.float32), arg((), jnp.float32)], 1)
    return build


def _bn_stats(shape, dot):
    from pytorch_distributed_nn_tpu.ops.pallas import bn_stats as bn

    def build(arg):
        def run(*xs):
            return bn._run([bn._view_2d(x)[0] for x in xs], dot=dot)

        return run, [arg(shape, jnp.bfloat16)] * (2 if dot else 1), 1
    return build


def _ring_block(bh, tl, D):
    from pytorch_distributed_nn_tpu.ops.pallas import ring_attention as ra

    def build(arg):
        def run(q, k, v, m, l, acc, offs):
            return ra._ring_block_pallas(
                q, k, v, m, l, acc, offs, causal=True, block_q=512,
                block_k=512, interpret=False)

        qkv = arg((bh, tl, D), jnp.bfloat16)
        stat = arg((bh, tl, ra.STAT_LANES), jnp.float32)
        return run, [qkv, qkv, qkv, stat, stat,
                     arg((bh, tl, D), jnp.float32),
                     arg((2,), jnp.int32)], 1
    return build


def _prefix_attention(T, S, heads=(64, 64), widths=(192, 128)):
    """A prefill's attention at the served tiles: MLA's expanded path
    (64 heads of 192 / 128) unless told otherwise."""
    from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa

    (H, Hkv), (dk, dv) = heads, widths

    def build(arg):
        def run(q, k, v, pos):
            bq, bk = pa.tiles(T, S, pa.QUERY_BLOCK, pa.KEY_BLOCK)
            assert pa._kernel_tiles(dk, dv, bq, bk)
            return pa._pallas(q, k, v, pos, scale=dk ** -0.5, block_q=bq,
                              block_k=bk)

        return run, [arg((1, H, T, dk), jnp.bfloat16),
                     arg((1, Hkv, S, dk), jnp.bfloat16),
                     arg((1, Hkv, S, dv), jnp.bfloat16),
                     arg((1, T), jnp.int32)], 1
    return build


def _round_attention(slots, S, heads, rows):
    """A decode round's attention over the flat rows of a served cache:
    ``heads`` K/V heads of 128 lanes a position (heads of 64 two a
    tile), ``rows`` query rows a head (the group's query heads times the
    fed positions; fewer than a register's sublanes are padded)."""
    from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa

    def build(arg):
        def run(q, k, v, pos):
            bk = next(n for n in (pa.ROUND_KEY_BLOCKS_ONE_HEAD
                                  if heads == 1 else pa.ROUND_KEY_BLOCKS)
                      if S % n == 0)
            return pa.round_attention(q, k, v, pos, scale=128 ** -0.5,
                                      block_k=bk)

        return run, [arg((slots, heads, rows, 128), jnp.bfloat16),
                     arg((slots, S, heads * 128), jnp.bfloat16),
                     arg((slots, S, heads * 128), jnp.bfloat16),
                     arg((slots, rows), jnp.int32)], 1
    return build


def _mamba_operands(arg, T, c_dtype):
    f32 = jnp.float32
    return [arg((1, 16, 5120), f32), arg((1, T, 5120), f32),
            arg((1, T, 5120), c_dtype), arg((1, T, 16), f32),
            arg((1, T, 16), f32), arg((16, 5120), f32)]


def _mamba_scan(T):
    """A Mamba mixer's scan at Jamba2-3B's widths as ``lax.scan`` (the
    dispatcher sees the CPU here): what a shape off the kernel's tiling
    and an uncached call still run, compiled so that the chip's compiler
    has seen the loop over positions with its ``(16, 5120)`` float32
    carry."""
    from pytorch_distributed_nn_tpu.nn import mamba

    def build(arg):
        return mamba.selective_scan, _mamba_operands(arg, T, jnp.float32), 0
    return build


def _selective_scan(T):
    """A prefill's recurrence as the kernel, at the tiles
    ``nn/mamba.selective_scan`` runs it in and ``c`` in bf16 as served."""
    from pytorch_distributed_nn_tpu.ops.pallas import selective_scan as ss

    def build(arg):
        chunk, lanes = ss.tiles(T, 5120)
        assert ss.kernel_tiles(16, 5120)

        def run(*xs):
            return ss.scan(*xs, chunk=chunk, lanes=lanes)

        return run, _mamba_operands(arg, T, jnp.bfloat16), 1
    return build


def _retention_step(rows):
    """A decode round's state update and outputs at Brumby-14B's widths
    (8 key-value heads of 128 under 5 query heads each, a state of
    8,704 x 128 a head) over ``rows`` slots, in place."""
    from pytorch_distributed_nn_tpu.ops.pallas import retention as rt

    def build(arg):
        f32, D = jnp.float32, rt.state_rows(128)
        return (jax.jit(rt.step.__wrapped__, donate_argnums=(0,)),
                [arg((rows, 8, D, 128), f32), arg((rows, 8), f32),
                 arg((rows, 8, 128), f32), arg((rows, 8, 128), f32),
                 arg((rows, 8, 5, 128), f32), arg((rows,), jnp.bool_)], 1)
    return build


def _retention_chunk(T):
    """A prefill's sequential part at the same widths: ``T`` positions
    of one row in chunks of 128, bf16 as served."""
    from pytorch_distributed_nn_tpu.nn import retention
    from pytorch_distributed_nn_tpu.ops.pallas import retention as rt

    def build(arg):
        f32, bf16, D = jnp.float32, jnp.bfloat16, rt.state_rows(128)
        n, C = T // retention.CHUNK, retention.CHUNK
        return (jax.jit(rt.chunk.__wrapped__, donate_argnums=(0,)),
                [arg((1, 8, D, 128), f32), arg((1, 8, n, 5, C, 128), bf16),
                 arg((1, 8, n, C, 128), bf16), arg((1, 8, n, C, 128), bf16),
                 arg((1, 8, n), f32)], 1)
    return build


CASES = {
    # Llama-3-8B's head layout (32 q / 8 kv heads of 128), long context
    "flash_fwd_bwd_d128_gqa_T8192": _flash(32, 8, 8192, 128, True),
    # BERT-base's head layout (12 heads of 64), bidirectional
    "flash_fwd_bwd_d64_T1024": _flash(12, 12, 1024, 64, False),
    # every weight shape of the int8 8B — q/o, k/v, q|k|v fused, gate/up,
    # gate|up fused, down, LM head — for one decode row, a decode round
    # of 8 slots, a prefill bucket of 32 and a prefill chunk of 256
    **{f"int8_matmul_M{m}_K{k}_N{n}": _int8(m, k, n)
       for k, n in ((4096, 4096), (4096, 1024), (4096, 6144),
                    (4096, 14336), (4096, 28672), (14336, 4096),
                    (4096, 128256))
       for m in (1, 8, 32, 256)},
    # ResNet-50's gradient in one bucket
    "quantize_25M": _quantize(25_557_032),
    # ResNet-50 stage 1 at batch 128: C = 64 folds into the 128 lanes
    "bn_stats_sumsq_c64": _bn_stats((128, 56, 56, 64), False),
    "bn_stats_dot_c256": _bn_stats((128, 56, 56, 256), True),
    # one ring step of a 32k sequence over four devices
    "ring_block_Tl8192_d128": _ring_block(8, 8192, 128),
    # A.X-K1's whole prompt and its suffix behind restored rows against
    # a row of 8,192; LongCat's smallest and largest buckets
    "prefix_attention_mla_T8192_S8192": _prefix_attention(8192, 8192),
    "prefix_attention_mla_T512_S8192": _prefix_attention(512, 8192),
    "prefix_attention_mla_T256_S256": _prefix_attention(256, 256),
    "prefix_attention_mla_T4096_S4096": _prefix_attention(4096, 4096),
    # grouped queries of 128 / 128. Mistral's 32 to 8: the documents'
    # largest bucket, chat's largest (the smallest that runs in tiles),
    # a suffix; K-EXAONE's 64 to 8, a step's four heads half a group
    "prefix_attention_gqa4_T4096_S4096": _prefix_attention(
        4096, 4096, (32, 8), (128, 128)),
    "prefix_attention_gqa4_T1024_S1024": _prefix_attention(
        1024, 1024, (32, 8), (128, 128)),
    "prefix_attention_gqa4_T512_S4096": _prefix_attention(
        512, 4096, (32, 8), (128, 128)),
    "prefix_attention_gqa8_T2048_S2048": _prefix_attention(
        2048, 2048, (64, 8), (128, 128)),
    # Jamba's 20 to 1: the chat cell's largest bucket and the smallest
    # that runs in tiles; its mixers' scan as the kernel at the smallest
    # bucket (one chunk) and the largest, and as the loop that stays
    "prefix_attention_gqa20_T4096_S4096": _prefix_attention(
        4096, 4096, (20, 1), (128, 128)),
    "prefix_attention_gqa20_T1024_S1024": _prefix_attention(
        1024, 1024, (20, 1), (128, 128)),
    # a decode round over the cells' caches of rows by position, one
    # fed position a row: Mistral's chat and documents (4 query heads a
    # K/V head), K-EXAONE's full layers (8), LFM2's (heads of 64 two a
    # lane tile: 2 x 4 rows a wide head), Jamba's (20 to one); SDAR's
    # block round (8 positions x 8)
    "round_attention_32x1024_8heads_4rows": _round_attention(32, 1024, 8, 4),
    "round_attention_8x4096_8heads_4rows": _round_attention(8, 4096, 8, 4),
    "round_attention_32x4096_8heads_8rows": _round_attention(32, 4096, 8, 8),
    "round_attention_64x4096_4heads_8rows": _round_attention(64, 4096, 4, 8),
    "round_attention_64x4096_1head_20rows": _round_attention(64, 4096, 1, 20),
    "round_attention_64x2048_4heads_64rows": _round_attention(
        64, 2048, 4, 64),
    "selective_scan_T128": _selective_scan(128),
    "selective_scan_T4096": _selective_scan(4096),
    "mamba_scan_T4096": _mamba_scan(4096),
    # Brumby's retention: the round over the cell's 16 slots, a prefill's
    # chunks at its smallest and largest bucket
    "retention_step_16rows": _retention_step(16),
    "retention_chunk_T1024": _retention_chunk(1024),
    "retention_chunk_T4096": _retention_chunk(4096),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(topo, name):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, min_kernels = CASES[name](arg)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count(KERNEL) >= min_kernels, (
        f"{name}: {text.count(KERNEL)} Pallas calls in the compiled "
        f"program, want >= {min_kernels}")


def test_ring_attention_fwd_bwd_compiles_for_four_chips(topo, monkeypatch):
    """The fused ring forward and its Pallas backward as one program
    across the four described chips: kernels AND the KV ring's
    collective-permutes. The backward asks the backend which branch to
    take; this test answers for the chip."""
    from pytorch_distributed_nn_tpu.parallel.sequence import ring_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(topo.devices, ("seq",))
    spec = P(None, "seq")

    def grads(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, axis="seq", causal=True,
                               impl="pallas")
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    fn = jax.jit(jax.shard_map(grads, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=(spec,) * 3, check_vma=False))
    sh = NamedSharding(mesh, spec)
    q = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16, sharding=sh)
    text = fn.lower(q, kv, kv).compile().as_text()
    assert text.count(KERNEL) >= 3
    assert "collective-permute" in text


def test_dp_explicit_step_reduces_leaves_in_place_on_four_chips(topo):
    """The bucketed dp_explicit step of a one-layer BERT-base (published
    widths, so the two embedding matrices are 94 MB leaves) as the
    chip's compiler leaves it: a few all-reduces whose operands are the
    leaves in their own shapes, nothing packed into a flat buffer. The
    step compiles under its own compiler options (a name this libtpu
    does not know is refused), whether or not the combiner leaves an
    all-reduce of one operand for them to make asynchronous."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig, OptimConfig
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.ops.buckets import make_bucket_reduce
    from pytorch_distributed_nn_tpu.parallel.dp import (
        make_dp_train_step_explicit,
    )
    from pytorch_distributed_nn_tpu.runtime.mesh import (
        MeshSpec, batch_pspec, make_mesh,
    )
    from pytorch_distributed_nn_tpu.train.losses import get_loss_fn
    from pytorch_distributed_nn_tpu.train.optim import make_optimizer
    from pytorch_distributed_nn_tpu.train.state import TrainState

    mesh = make_mesh(MeshSpec(data=4), devices=list(topo.devices))
    model = get_model(ModelConfig(name="bert_base",
                                  extra=dict(num_layers=1)))

    def init():
        variables = model.init(jax.random.key(0),
                               jnp.zeros((1, 128), jnp.int32), train=False)
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"],
            tx=make_optimizer(OptimConfig(name="adamw"), total_steps=10),
            model_state={}, rng=jax.random.key(1))

    def on(spec):
        sharding = NamedSharding(mesh, spec)
        return lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=sharding)

    state = jax.tree.map(on(P()), jax.eval_shape(init))
    tokens = on(batch_pspec())(jax.ShapeDtypeStruct((32, 128), jnp.int32))
    step = make_dp_train_step_explicit(
        mesh, get_loss_fn("mlm_synthetic"),
        bucket_reduce=make_bucket_reduce(bucket_mb=100.0))
    text = step.lower(state, tokens, tokens).compile().as_text()

    reduced = [line.split("=", 1)[1] for line in text.splitlines()
               if re.search(r" all-reduce\(|^\s+%async-collective-start"
                            r"[.\d]* = ", line)]
    assert 1 <= len(reduced) < 10
    flat = [int(n) for result in reduced
            for n in re.findall(r"f32\[(\d+)\]", result.split(" all-")[0])]
    assert max(flat, default=0) <= 30522  # the largest rank-1 leaf
    # and no gradient-sized rank-1 buffer anywhere in the program
    assert max(map(int, re.findall(r"= f32\[(\d+)\]", text))) < 1 << 20


# served families at small depth but the published cache-leaf widths
# (heads of 128; MLA's latent 512 and rotated key 64; a ring of 128)
_SERVED = {
    "llama": ("llama3_8b", dict(
        vocab_size=1024, num_layers=2, d_model=512, num_heads=4,
        num_kv_heads=2, head_dim=128, mlp_dim=1024, rope_theta=1e6)),
    "longcat": ("longcat_flash", dict(
        vocab_size=1024, num_layers=1, d_model=512, num_heads=4,
        num_kv_heads=4, mlp_dim=1024, q_lora_rank=256, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        expert_mlp_dim=256, num_experts=8, num_zero_experts=4, moe_topk=2,
        routed_scaling=6.0, ep_size=2, ep_rank=0)),
    "k_exaone": ("k_exaone", dict(
        vocab_size=1024, num_layers=4, d_model=512, num_heads=4,
        num_kv_heads=2, head_dim=128, mlp_dim=1024, window=128,
        expert_mlp_dim=256, num_experts=8, moe_topk=2, ep_size=2,
        ep_rank=0)),
    "ax_k1": ("ax_k1", dict(
        vocab_size=1024, num_layers=2, d_model=512, num_heads=4,
        mlp_dim=1024, q_lora_rank=256, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        expert_mlp_dim=256, num_experts=8, moe_topk=2, n_group=4,
        topk_group=2, ep_size=2, ep_rank=0)),
}


def _loop_over_rows(buf, new, starts):
    """``_row_update`` as it was for any number of tokens a row."""
    return jax.vmap(
        lambda b, n, s: jax.lax.dynamic_update_slice(
            b, n, (s,) + (0,) * (b.ndim - 1))
    )(buf, new, starts)


@pytest.mark.parametrize("family", list(_SERVED))
def test_serve_step_writes_cache_rows_without_a_loop(topo, monkeypatch,
                                                     family):
    """A decode round's cache write on the chip: the compiled
    ``_serve_step`` (8 slots x 256) holds no ``while`` that came from a
    scatter — for a Llama no ``while`` at all; the expert loops of the
    other two stay — and donates its cache as before: aliased bytes and
    temporaries no larger than with the write as a ``vmap`` of
    ``dynamic_update_slice``, which this TPU runs as a serial loop over
    the slots, two a layer."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.nn import attention, mla
    from pytorch_distributed_nn_tpu.serve import engine

    one_chip = SingleDeviceSharding(topo.devices[0])
    name, extra = _SERVED[family]
    model = get_model(ModelConfig(name=name, dtype="float32",
                                  compute_dtype="bfloat16",
                                  extra=dict(extra)))
    slots = 8

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    cache = on(jax.eval_shape(lambda: init_cache(model, slots, 256)))
    state = on([jax.ShapeDtypeStruct((slots,), dt) for dt in
                (jnp.int32, jnp.int32, jnp.bool_, jnp.int32)]
               + [jax.ShapeDtypeStruct((), jnp.int32)])

    def compiled():
        # a function of its own each time: the trace of the last one
        # is not reused, so the patched write below is read
        step = jax.jit(lambda *a: engine._serve_step.__wrapped__(*a),
                       static_argnums=(0,), donate_argnums=(2,))
        return step.lower(model, params, cache, *state).compile()

    def loops(text):
        return re.findall(r"^\s*%?while[.\d]* = .*?op_name=\"([^\"]*)\"",
                          text, re.M)

    now = compiled()
    from_scatter = [op for op in loops(now.as_text())
                    if op.endswith("/scatter")]
    assert not from_scatter, from_scatter
    if family == "llama":
        assert " while(" not in now.as_text()

    monkeypatch.setattr(attention, "_row_update", _loop_over_rows)
    monkeypatch.setattr(mla, "_row_update", _loop_over_rows)
    before = compiled()
    leaves = sum(a.ndim >= 3 for a in jax.tree.leaves(cache))
    assert sum(op.endswith("/scatter")
               for op in loops(before.as_text())) == leaves  # the witness
    was, is_ = before.memory_analysis(), now.memory_analysis()
    assert is_.alias_size_in_bytes == was.alias_size_in_bytes
    assert is_.temp_size_in_bytes <= was.temp_size_in_bytes


@pytest.mark.parametrize("family,slots,S,tails", [
    ("mistral", 8, 4096, [(1024,), (1024,)]),
    ("by_heads", 8, 4096, [(8, 128), (8, 128)]),
    # A.X-K1's latent and rotated-key leaves: the second is half a lane
    # tile wide and lies position-minor on the chip
    ("latent", 16, 8192, [(512,), (64,)]),
])
def test_block_copies_hold_no_loop_on_the_chip(topo, family, slots, S,
                                               tails):
    """``_save_blocks`` and ``_restore_blocks`` as the chip's compiler
    leaves them at the cells' sizes: no ``while`` in either (a loop over
    the blocks cost ~3 us a block) and the save's store written in
    place."""
    from pytorch_distributed_nn_tpu.serve import engine

    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def tree(lead):
        return {**{f"leaf{i}": arg(lead + t) for i, t in enumerate(tails)},
                "index": arg((), jnp.int32)}

    cache, store, row = (tree((slots, S)), tree((slots * S // 16, 16)),
                         tree((1, S)))
    table, i32 = arg((S // 16,), jnp.int32), arg((), jnp.int32)
    save = engine._save_blocks.lower(
        cache, store, 16, i32, table, i32).compile()
    restore = engine._restore_blocks.lower(
        row, store, 16, table, i32).compile()
    assert " while(" not in save.as_text()
    assert " while(" not in restore.as_text()
    held = sum(math.prod(leaf.shape) * 2 for leaf in store.values()
               if leaf.shape)
    assert save.memory_analysis().alias_size_in_bytes >= held


def _leaf_sized(text, elements):
    """The instructions of a compiled program that make a new array of
    at least ``elements`` bf16 elements, a cache leaf's size: a copy, a
    transpose, or a fusion that is one (its name says so)."""
    import re

    made = re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]*(?:copy|transpose)[\w.\-]*) = "
        r"bf16\[([\d,]+)\]", text, re.M)
    return [(name, dims) for name, dims in made
            if math.prod(map(int, dims.split(","))) >= elements]


@pytest.mark.parametrize("family,slots,rows,layers", [
    ("llama", 32, 1024, 2),       # the chat cell's cache, every layer
    ("llama", 8, 4096, 2),        # the documents'
    ("k_exaone", 32, 4096, 1),    # LLLG: the one full layer
])
def test_a_decode_round_reads_rows_by_position_where_they_lie(
        topo, monkeypatch, family, slots, rows, layers):
    """The compiled ``_serve_step`` of a model whose cache is rows by
    position (two K/V heads of 128 a position, the cells' slots and
    rows) holds one ``round_attention`` call a layer over them, fed the
    leaves the round's scatter wrote, and makes no array of a leaf's
    size on the way: no copy, no transpose, in the entry or in a fusion
    (the dispatcher asks for the backend, and this test answers for the
    chip). A ring is not this routine's."""
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    name, extra = _SERVED[family]
    model = get_model(ModelConfig(name=name, dtype="float32",
                                  compute_dtype="bfloat16",
                                  extra=dict(extra)))

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    cache = on(jax.eval_shape(lambda: init_cache(model, slots, rows)))
    state = on([jax.ShapeDtypeStruct((slots,), dt) for dt in
                (jnp.int32, jnp.int32, jnp.bool_, jnp.int32)]
               + [jax.ShapeDtypeStruct((), jnp.int32)])
    step = jax.jit(lambda *a: engine._serve_step.__wrapped__(*a),
                   static_argnums=(0,), donate_argnums=(2,))
    compiled = step.lower(model, params, cache, *state).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == layers
    assert all(f"bf16[{slots},{rows},256]" in line for line in calls)
    assert not _leaf_sized(text, slots * rows * 256)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * layers * slots * rows * 256 * 2   # the cache, in place


def _dense_prefill(q, k, v, positions, lengths=None):
    """``nn/attention._prefill_attention`` as the dense routine it
    replaced: every score of the row at once."""
    from pytorch_distributed_nn_tpu.nn import attention

    seen = jnp.arange(k.shape[1])[None, None, :] <= positions[:, :, None]
    return attention._cache_attention(q, k, v, seen, q.dtype)


@pytest.mark.parametrize("family,T,S,kernels", [
    ("longcat", 2048, 2048, 2),     # two attentions a layer, T = S
    ("ax_k1", 512, 8192, 2),        # a suffix against the cell's row
    ("llama", 2048, 2048, 2),       # rows by position, two layers
    ("llama", 512, 4096, 2),        # a suffix behind restored rows
    ("k_exaone", 2048, 2048, 1),    # LLLG: one full layer; the rings'
                                    # band is not this routine
    ("llama_dense", 2048, 2048, 0),  # the witness: the dense routine
])
def test_serve_prefill_keeps_scores_in_the_core(topo, monkeypatch,
                                                family, T, S, kernels):
    """The compiled ``_serve_prefill`` of the latent-attention families
    and of the rows-by-position ones holds one Pallas call an attention
    and no float32 tensor of scores ``heads x queries x row`` (with the
    dense routine put back it holds them: the pattern finds them); the
    dispatcher asks for the backend, and this test answers for the
    chip."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.nn import attention
    from pytorch_distributed_nn_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if family == "llama_dense":
        family = "llama"
        monkeypatch.setattr(attention, "_prefill_attention", _dense_prefill)
    one_chip = SingleDeviceSharding(topo.devices[0])
    name, extra = _SERVED[family]
    model = get_model(ModelConfig(name=name, dtype="float32",
                                  compute_dtype="bfloat16",
                                  extra=dict(extra)))

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    cache = on(jax.eval_shape(lambda: init_cache(model, 1, S)))
    one = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    prefill = jax.jit(lambda *a: engine._serve_prefill.__wrapped__(*a),
                      static_argnums=(0,), donate_argnums=(2,))
    text = prefill.lower(
        model, params, cache,
        jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip),
        one, one).compile().as_text()
    assert text.count(KERNEL) == kernels
    scores = set(re.findall(rf"f32\[[\d,]*,(?:{T}|512),{S}\]", text))
    assert bool(scores) == (kernels == 0), scores


@pytest.mark.parametrize("program", ["step", 64, 1024])
def test_sdar_serve_programs_fit_the_chip_at_the_cells_size(
        topo, monkeypatch, program):
    """``sdar_30b_a3b_seq2`` as its cell runs it (7 layers, every one of the
    128 experts of a layer, the whole vocabulary, bf16; 64 slots x 2,048
    positions): the block round (two blocks of positions a row: the
    open one and the one it may owe) and the smallest and largest prefill
    buckets compile for the described chip and fit its 16 GB beside
    what they are given, the round's cache donated. A layer's held
    experts are one ``grouped_experts`` call (``ops/pallas``: the
    dispatcher asks for the backend, and this test answers for the
    chip) and no loop: a loop an expert unrolled into the text, 128 a
    layer, this compile takes 110 s where it takes 6."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    model = get_model(ModelConfig(name="sdar_30b_a3b_seq2", dtype="bfloat16",
                                  extra=dict(num_layers=7)))
    slots, rows, B = 64, 2048, model.block_decoding()["block_length"]

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    if program == "step":
        cache = on(jax.eval_shape(lambda: init_cache(model, slots, rows)))
        compiled = jax.jit(
            lambda *a: engine._serve_step.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((slots, B + 1)),
            dict(depth=arg((slots,)), masked=arg((slots, B), jnp.bool_),
                 step=arg((slots,)), skip=arg((slots,)),
                 owes=arg((slots,), jnp.bool_), owed=arg((slots, B))),
            arg((slots,), jnp.bool_), arg((slots,)), arg(())).compile()
        # one kernel a layer for its experts and one for its attention
        # over the flat cache rows, no loop around either; the cache
        # written by scatters, no loop over the slots; no float32 scores
        # of a row's whole padded length
        text = compiled.as_text()
        assert text.count(KERNEL) == 14
        assert text.count(" while(") == 0
        assert not re.findall(rf"f32\[{slots},\d+,\d+,\d+,{rows}\]", text)
    else:
        cache = on(jax.eval_shape(lambda: init_cache(model, 1, program)))
        compiled = jax.jit(
            lambda *a: engine._serve_prefill.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((1, program)), arg((1,)),
            arg((1,))).compile()
        # a block decoder's prefill fills rows and yields no token: the
        # last layer's experts feed nothing and are not in the program;
        # a blockwise attention a layer where the bucket's scores would
        # be large
        assert compiled.as_text().count(KERNEL) == (13 if program == 1024
                                                    else 6)
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    assert held < 16.0e9, held
    if program == "step":
        assert m.alias_size_in_bytes > 1.8e9   # the cache, in place


@pytest.mark.parametrize("program", ["step", 128, 1024, 4096])
def test_lfm2_serve_programs_fit_the_chip_at_the_cells_size(
        topo, monkeypatch, program):
    """``lfm2_8b_a1b`` as its cell runs it (14 of 24 layers: the two
    leading dense convolution layers and three periods of attention and
    three convolutions before experts; all 32 experts of a layer, every
    head of 64, the whole vocabulary, bf16; 64 slots x 4,096 positions):
    the decode round and the smallest, a middle and the largest prefill
    bucket compile for the described chip and fit its 16 GB beside what
    they are given, the round's cache donated. A layer's held experts
    are one ``grouped_experts`` call (``ops/pallas``: the dispatcher
    asks for the backend, and this test answers for the chip) and no
    loop; its cache of 64-wide K/V heads lies flat, a position's eight
    heads in one row of 512 lanes, and a round reads it in place, one
    ``round_attention`` call an attention layer that takes two heads as
    one of 128 (a head a row, the compiler transposed each leaf whole,
    twice a layer a round: 0.56 GB of temporaries where there are
    0.03); a prefill whose scores would be large attends blockwise, one
    ``prefix_attention`` call an attention layer at heads of 64, and
    holds no float32 scores of the bucket against the row.
    ``benchmark/configs/lfm2_8b_a1b.json``'s ``deployment`` quotes what
    this prints for 14 layers and for the 18 that do not fit."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    model = get_model(ModelConfig(name="lfm2_8b_a1b", dtype="bfloat16",
                                  extra=dict(num_layers=14)))
    slots, rows = 64, 4096
    moe_layers, attn_layers = 12, 3

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    if program == "step":
        cache = on(jax.eval_shape(lambda: init_cache(model, slots, rows)))
        compiled = jax.jit(
            lambda *a: engine._serve_step.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((slots,)), arg((slots,)),
            arg((slots,), jnp.bool_), arg((slots,)), arg(())).compile()
        text = compiled.as_text()
        assert text.count(KERNEL) == moe_layers + attn_layers
        assert text.count(" while(") == 0
        # no copy of a cache leaf anywhere in the program: the scatter
        # and the round's kernel read it where it lies
        assert not _leaf_sized(text, slots * rows * 512)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    else:
        cache = on(jax.eval_shape(lambda: init_cache(model, 1, program)))
        compiled = jax.jit(
            lambda *a: engine._serve_prefill.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((1, program)), arg((1,)),
            arg((1,))).compile()
        text = compiled.as_text()
        blockwise = program * program > 512 * 512
        assert text.count(KERNEL) == moe_layers \
            + (attn_layers if blockwise else 0)
        scores = re.findall(rf"f32\[[\d,]*,(?:{program}|512),{program}\]",
                            text)
        assert bool(scores) == (not blockwise), scores[:3]
        # the tied head scores the row the engine takes (ISSUE 51): no
        # logits of the bucket (1.07 GB at 4,096), and the temporaries
        # what this printed then (0.047, 0.232, 1.012 GB; with the
        # bucket's logits among them 0.298 at 1,024 and 1.108 at 4,096)
        assert not re.findall(rf"f32\[(?:1,)?{program},65536\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes \
            < {128: 0.06e9, 1024: 0.26e9, 4096: 1.06e9}[program]
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    print(f"lfm2 {program}: arguments {m.argument_size_in_bytes / 1e9:.3f} "
          f"GB, temporaries {m.temp_size_in_bytes / 1e9:.3f}, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f}, aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f}, held {held / 1e9:.3f}")
    assert held < 16.0e9, held
    if program == "step":
        assert m.alias_size_in_bytes > 1.6e9   # the cache, in place


@pytest.mark.parametrize("program", ["step", 1024, 4096])
def test_brumby_serve_programs_fit_the_chip_at_the_cells_size(
        topo, monkeypatch, program):
    """``brumby_14b`` as its cell runs it (8 of 40 layers, every width,
    the whole vocabulary, bf16; 16 slots x 8,192 positions, which bound
    no leaf: the whole cache is state): the decode round and the
    smallest and largest prefill bucket compile for the described chip
    and fit its 16 GB beside what they are given. A layer's state is
    moved by one ``retention_step`` call a round, in place (the cache
    donated, the state aliased through the kernel: no copy of a leaf of
    4.6 GB), and advanced by one ``retention_chunk`` call a prefill; no
    program holds the logits of every fed position (the head is applied
    to the row the engine takes).
    ``benchmark/configs/brumby_14b.json``'s ``deployment`` stands on
    what this prints."""
    import re

    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    layers, slots, rows = 8, 16, 8192
    model = get_model(ModelConfig(name="brumby", dtype="bfloat16",
                                  extra=dict(num_layers=layers)))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)

    params = on(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])
    if program == "step":
        cache = on(jax.eval_shape(lambda: init_cache(model, slots, rows)))
        compiled = jax.jit(
            lambda *a: engine._serve_step.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((slots,)), arg((slots,)),
            arg((slots,), jnp.bool_), arg((slots,)), arg(())).compile()
    else:
        cache = on(jax.eval_shape(lambda: init_cache(model, 1, program)))
        compiled = jax.jit(
            lambda *a: engine._serve_prefill.__wrapped__(*a),
            static_argnums=(0,), donate_argnums=(2,)).lower(
            model, params, cache, arg((1, program)), arg((1,)),
            arg((1,))).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == layers
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    print(f"brumby {program}: arguments {m.argument_size_in_bytes / 1e9:.3f} "
          f"GB, temporaries {m.temp_size_in_bytes / 1e9:.3f}, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f}, aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f}, held {held / 1e9:.3f}")
    assert held < 16.0e9, held
    state = rf"f32\[{slots if program == 'step' else 1},8,8704,128\]"
    assert not re.findall(rf"%copy[.\d]* = {state}",
                          text[text.index("\nENTRY "):])
    if program == "step":
        assert m.alias_size_in_bytes > 4.5e9   # the cache, in place
        assert m.temp_size_in_bytes < 0.3e9
    else:
        assert not re.findall(rf"f32\[(?:1,)?{program},151936\]", text)
        assert m.temp_size_in_bytes < 1.0e9
