import numpy as np
import pytest

from pytorch_distributed_nn_tpu.ops.buckets import (
    group_leaves,
    make_bucket_reduce,
    partition_buckets,
)
from pytorch_distributed_nn_tpu.ops.fake_collectives import FakeWorld


def test_partition_respects_budget():
    sizes = [10, 20, 30, 40, 5]
    buckets = partition_buckets(sizes, 50)
    assert buckets == [[0, 1], [2], [3, 4]]
    # every index exactly once
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == list(range(5))


def test_partition_oversized_leaf_own_bucket():
    assert partition_buckets([100, 5], 50) == [[0], [1]]
    assert partition_buckets([5, 100, 5], 50) == [[0], [1], [2]]


def test_partition_bad_budget():
    with pytest.raises(ValueError):
        partition_buckets([1], 0)


def test_bucket_reduce_matches_per_tensor_mean(mesh8):
    """Bucketed pmean == plain per-tensor pmean (the DDP-vs-hand-rolled
    contrast of SURVEY.md §3.2, checked for equality of results)."""
    import jax
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(0)
    grads = {
        "w1": rng.randn(8, 16, 4).astype(np.float32),
        "b1": rng.randn(8, 4).astype(np.float32),
        "w2": rng.randn(8, 4, 2).astype(np.float32),
    }
    reduce_fn = make_bucket_reduce(bucket_mb=0.0001)  # force several buckets

    mapped = jax.shard_map(
        reduce_fn, mesh=mesh8,
        in_specs=P("data"), out_specs=P("data"), check_vma=False,
    )
    got = jax.jit(mapped)(grads)
    for key, g in grads.items():
        want = np.broadcast_to(g.mean(0, keepdims=True), g.shape)
        np.testing.assert_allclose(np.asarray(got[key]), want, rtol=1e-6)


def test_quantized_bucket_reduce_close(mesh8):
    import jax
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(1)
    grads = {"w": rng.randn(8, 32).astype(np.float32)}
    reduce_fn = make_bucket_reduce(bucket_mb=1.0, quantized=True)
    mapped = jax.shard_map(reduce_fn, mesh=mesh8,
                           in_specs=P("data"), out_specs=P("data"),
                           check_vma=False)
    got = np.asarray(jax.jit(mapped)(grads)["w"])
    want = np.broadcast_to(grads["w"].mean(0, keepdims=True), (8, 32))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_quantized_allreduce_trains_end_to_end():
    """config knob -> dp_explicit bucket controller -> quantized wire:
    a short bf16-wire training run must track the exact-wire run
    closely (same data, same init), and int8 must stay stable."""
    import jax

    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    def run(quant):
        cfg = get_config("mlp_mnist",
                         **{"steps": "8", "log_every": "1",
                            "data.prefetch": "0"})
        cfg.parallel.strategy = "dp_explicit"
        cfg.parallel.quantized_allreduce = quant
        cfg.mesh = MeshSpec(data=8)
        trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
        trainer.train()
        return np.array(trainer.losses())

    exact = run("")
    bf16 = run("bf16")
    int8 = run("int8")
    assert exact[-1] < exact[0]
    # bf16 wire: ~3 decimal digits of gradient mantissa — curves track
    np.testing.assert_allclose(bf16, exact, rtol=0.05)
    # int8 stochastic wire is noisier but must still optimize
    assert np.isfinite(int8).all() and int8[-1] < int8[0]


def _bert_layers(n=2):
    """Shapes of ``n`` BERT-base encoder layers' gradients (float32)."""
    import jax
    import jax.numpy as jnp

    def f(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def layer():
        att = {name: {"kernel": f(768, 12, 64), "bias": f(12, 64)}
               for name in ("query", "key", "value")}
        att["out"] = {"kernel": f(12, 64, 768), "bias": f(768)}
        return {"attention": att,
                "ln_att": {"scale": f(768), "bias": f(768)},
                "mlp_in": {"kernel": f(768, 3072), "bias": f(3072)},
                "mlp_out": {"kernel": f(3072, 768), "bias": f(768)},
                "ln_mlp": {"scale": f(768), "bias": f(768)}}

    return {f"layer_{i}": layer() for i in range(n)}


def _all_reduce_operands(compiled_text):
    """One list of operand shapes (``"f32[768,3072]"``) per all-reduce
    instruction of a compiled module."""
    import re

    found = []
    for line in compiled_text.splitlines():
        if re.search(r" all-reduce(-start)?\(", line):
            result = line.split("=", 1)[1].split(" all-reduce")[0]
            found.append(re.findall(r"\b[a-z]+\d+\[[\d,]*\]", result))
    return found


def test_exact_reduce_compiles_to_leaves_in_place(mesh8):
    """A bucket is a group of leaves, not a buffer: the compiled
    reduction holds no packing copy, every all-reduce operand has its
    leaf's own shape, and the combiner leaves at most one collective a
    bucket (and one for what it regroups)."""
    import jax
    from jax.sharding import PartitionSpec as P

    tree = _bert_layers(2)
    leaves = jax.tree.leaves(tree)
    bucket_mb = 25.0
    buckets = group_leaves(leaves, int(bucket_mb * 2 ** 20))
    assert len(buckets) == 3
    reduce_fn = make_bucket_reduce(bucket_mb=bucket_mb, axis="data")
    mapped = jax.shard_map(reduce_fn, mesh=mesh8, in_specs=P(),
                           out_specs=P(), check_vma=False)
    text = jax.jit(mapped).lower(tree).compile().as_text()
    assert "concatenate(" not in text
    assert "dynamic-update-slice(" not in text
    collectives = _all_reduce_operands(text)
    assert 1 <= len(collectives) <= len(buckets) + 1
    operands = [shape for c in collectives for shape in c]
    assert len(operands) == len(leaves)
    want = sorted(f"f32[{','.join(map(str, leaf.shape))}]"
                  for leaf in leaves)
    assert sorted(operands) == want  # none flattened, none packed


def test_bucket_reduce_mixed_dtypes_leaf_for_leaf(mesh8):
    """float32 and bfloat16 leaves: each comes back as the per-tensor
    mean in its own dtype and shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(2)
    grads = {
        "w": rng.randn(8, 16, 4).astype(np.float32),
        "h": jnp.asarray(rng.randn(8, 4, 6), jnp.bfloat16),
        "b": rng.randn(8, 4).astype(np.float32),
        "g": jnp.asarray(rng.randn(8, 6), jnp.bfloat16),
    }
    reduce_fn = make_bucket_reduce(bucket_mb=0.0001, axis="data")
    mapped = jax.shard_map(reduce_fn, mesh=mesh8, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False)
    got = jax.jit(mapped)(grads)
    per_tensor = jax.jit(jax.shard_map(
        lambda t: jax.tree.map(lambda x: jax.lax.pmean(x, "data"), t),
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(grads)
    for key, g in grads.items():
        assert got[key].dtype == g.dtype and got[key].shape == g.shape
        np.testing.assert_array_equal(
            np.asarray(got[key].astype(jnp.float32)),
            np.asarray(per_tensor[key].astype(jnp.float32)))


@pytest.mark.parametrize("bucket_bytes", [64, 1000, 4096, 10 ** 9])
def test_group_leaves_reverse_order_within_budget(bucket_bytes):
    """Leaves are visited last to first, one dtype a bucket, and no
    bucket passes the budget but a single leaf larger than it."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    leaves = [jax.ShapeDtypeStruct(
        (int(rng.randint(1, 40)), int(rng.randint(1, 12))),
        (jnp.float32, jnp.bfloat16)[int(rng.randint(2))])
        for _ in range(40)]
    nbytes = [x.size * x.dtype.itemsize for x in leaves]
    groups = group_leaves(leaves, bucket_bytes)
    assert sorted(i for g in groups for i in g) == list(range(40))
    assert groups[0][0] == 39  # the last leaf's gradient is ready first
    for dtype in (jnp.float32, jnp.bfloat16):
        visited = [i for g in groups for i in g
                   if leaves[i].dtype == dtype]
        assert visited == sorted(visited, reverse=True)
    for g in groups:
        assert len({leaves[i].dtype for i in g}) == 1
        assert len(g) == 1 or sum(nbytes[i] for i in g) <= bucket_bytes


def test_bf16_wire_leaves_in_place_close(mesh8):
    """The bf16 wire through groups of leaves: the tolerance of
    test_quantized_bucket_reduce_close, float32 back, no packing."""
    import re

    import jax
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(4)
    grads = {"w": rng.randn(8, 32, 6).astype(np.float32),
             "b": rng.randn(8, 6).astype(np.float32),
             "v": rng.randn(8, 5, 3, 2).astype(np.float32)}
    reduce_fn = make_bucket_reduce(bucket_mb=0.0005, axis="data",
                                   quantized="bf16")
    mapped = jax.jit(jax.shard_map(
        reduce_fn, mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    lowered = mapped.lower(grads)
    assert "concatenate(" not in lowered.compile().as_text()
    # the wire as the program states it (the CPU's compiler widens it)
    wires = re.findall(r"stablehlo\.all_reduce.*?\}\) : \(tensor<([^>]*)>",
                       lowered.as_text(), re.S)
    assert len(wires) == 3 and all(w.endswith("xbf16") for w in wires)
    got = mapped(grads)
    for key, g in grads.items():
        assert got[key].dtype == np.float32
        want = np.broadcast_to(g.mean(0, keepdims=True), g.shape)
        np.testing.assert_allclose(np.asarray(got[key]), want,
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("quantized,packed_leaves",
                         [(False, 0), ("bf16", 0), ("int8", 3)])
def test_bucket_reduce_logs_what_it_packs(mesh8, caplog, quantized,
                                          packed_leaves):
    """Traced once, the reduction says how many buckets it made and how
    many leaves went through a packed operand: none but on the int8
    wire, whose kernel takes a flat one."""
    import logging

    import jax
    from jax.sharding import PartitionSpec as P

    grads = {"w": np.ones((8, 16, 4), np.float32),
             "b": np.ones((8, 4), np.float32),
             "u": np.ones((8, 4, 2), np.float32)}
    reduce_fn = make_bucket_reduce(bucket_mb=1.0, axis="data",
                                   quantized=quantized)
    with caplog.at_level(logging.INFO,
                         logger="pytorch_distributed_nn_tpu.ops.buckets"):
        jax.jit(jax.shard_map(
            reduce_fn, mesh=mesh8, in_specs=P("data"),
            out_specs=P("data"), check_vma=False)).lower(grads)
    lines = [r.getMessage() for r in caplog.records
             if "bucket reduce" in r.getMessage()]
    assert len(lines) == 1
    assert "3 leaves in 1 buckets" in lines[0]
    assert f"packed operand: {packed_leaves} leaves" in lines[0]
