"""Digests of the lowered serve programs of the served families that a
new family must leave alone.

``tests/test_k_exaone.py`` compares :func:`digests` with
``tests/data/serve_program_digests.json``, which was written from the
commit before K-EXAONE came (ISSUE 33): a Mistral-shaped and a
LongCat-shaped model's ``_serve_prefill`` and ``_serve_step`` lower to
the same text, byte for byte, with the window, the ring, the q/k norm
and the router's new fields in the shared modules as without them.
The two step programs were written anew by ISSUE 34, which meant to
change them (a decode round's cache write is one scatter a leaf); the
two prefill programs are still those of that commit. A PR that means
to change those programs writes the file anew and says so:

    JAX_PLATFORMS=cpu python tests/serve_program_digests.py > tests/data/serve_program_digests.json

(The text holds no source locations; it does depend on the installed
JAX, so a new JAX is such a PR too.)
"""

import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_SHAPES = {
    # grouped-query, rotary, SwiGLU, through models/llama.py as Mistral
    "mistral": ("llama3_8b", dict(
        vocab_size=101, num_layers=2, d_model=32, num_heads=4,
        num_kv_heads=2, mlp_dim=64, rope_theta=1e6)),
    "longcat": ("longcat_flash", dict(
        vocab_size=256, num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, mlp_dim=128, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        expert_mlp_dim=32, num_experts=16, num_zero_experts=8, moe_topk=4,
        routed_scaling=6.0, ep_size=2, ep_rank=0)),
}


def lowered(family: str, program: str) -> str:
    """The text of one of the engine's two model programs for a small
    model of ``family``, greedy and without a bank, 4 slots x 64
    positions, a prefill bucket of 16; at JAX's own matmul precision,
    whatever the caller has set."""
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.models import get_model
    from pytorch_distributed_nn_tpu.serve import engine

    name, extra = _SHAPES[family]
    mc = ModelConfig(name=name, dtype="float32", compute_dtype="float32")
    mc.extra = dict(extra)
    model = get_model(mc)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]

    def vec(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype)

    with jax.default_matmul_precision(None):
        if program == "step":
            cache = jax.eval_shape(lambda: init_cache(model, 4, 64))
            return engine._serve_step.lower(
                model, params, cache, vec(4), vec(4), vec(4, jnp.bool_),
                vec(4), jax.ShapeDtypeStruct((), jnp.int32)).as_text()
        cache = jax.eval_shape(lambda: init_cache(model, 1, 16))
        return engine._serve_prefill.lower(
            model, params, cache, jax.ShapeDtypeStruct((1, 16), jnp.int32),
            vec(1), vec(1)).as_text()


def digests() -> dict:
    return {f"{family}.{program}": hashlib.sha256(
        lowered(family, program).encode()).hexdigest()
        for family in _SHAPES for program in ("prefill", "step")}


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
