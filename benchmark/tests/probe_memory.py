"""Probe (chip only, by hand): does ``memory_stats()['peak_bytes_in_use']``
count a compiled program's temporaries? Prints the allocator's numbers
around a program with a known 2 GiB intermediate, and the BERT step's
``memory_analysis()`` beside the peak after one step."""
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
import jax, jax.numpy as jnp
from benchmark.lib import common
common.place_compile_cache()
d = jax.devices()[0]
print("start", d.memory_stats())

@jax.jit
def f(x):
    y = jnp.tanh(x[:, None, :] * x[None, :, :])   # (n, n, 64) f32 intermediate
    return y.sum(axis=(0, 1))
n = 2896  # n*n*64*4 = 2.1 GB
x = jnp.ones((n, 64), jnp.float32)
c = f.lower(x).compile()
print("analysis temp", c.memory_analysis().temp_size_in_bytes)
jax.block_until_ready(f(x))
print("after f", d.memory_stats())

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.train.trainer import Trainer
for b in (128, 256):
    pc = get_config("bert_base_buckets", **{"data.batch_size": b, "steps": 100000})
    t = Trainer(pc)
    xb, yb = t.loader.batch_at(0)
    m = t.step_fn.lower(t.state, xb, yb).compile().memory_analysis()
    print("batch", b, "step temp", m.temp_size_in_bytes, "args", m.argument_size_in_bytes, "out", m.output_size_in_bytes, "alias", m.alias_size_in_bytes, "code", m.generated_code_size_in_bytes)
    t.train(steps=3)
    print("after 3 steps", d.memory_stats())
    t.close(); del t, xb, yb
    import gc; gc.collect()
