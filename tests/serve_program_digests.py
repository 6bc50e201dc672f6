"""Digests of the lowered serve programs of the served families that a
new family must leave alone.

``tests/test_k_exaone.py`` compares :func:`digests` with
``tests/data/serve_program_digests.json``, which was written from the
commit before K-EXAONE came (ISSUE 33): a Mistral-shaped and a
LongCat-shaped model's ``_serve_prefill`` and ``_serve_step`` lower to
the same text, byte for byte, with the window, the ring, the q/k norm
and the router's new fields in the shared modules as without them.
The two step programs were written anew by ISSUE 34, which meant to
change them (a decode round's cache write is one scatter a leaf).
``longcat.prefill`` was written anew by ISSUE 36, which meant to change
it (MLA's expanded path runs blockwise, ``ops/pallas/prefix_attention``;
that one line: the ``trees`` entries are still those of ISSUE 35's
commit, and the uncached forward's logits moved by 1e-6). ISSUE 39 put
the blockwise routine behind ``nn/attention.MultiHeadAttention``'s
cached prefill too, grouped-query heads and all, and the file stayed as
it was, every line: a bucket whose dense scores are small
(``nn/attention.prefill_in_tiles``: up to 512 x 512 a head, this file's
16 x 16 among them) keeps the dense routine, which was not slower there
on the chip, so the Mistral and K-EXAONE prefill programs are still
those of the first commit; at ``G = 1`` the routine lowers to ISSUE
36's text, so ``longcat.prefill`` is still ISSUE 36's. ISSUE 51 wrote
the three ``*.prefill`` lines anew and meant to: every served model
takes ``head_rows`` and ``_apply_prefill_at`` passes it, so a prefill's
final norm and head see the one row the engine reads; the ``*.step``
lines and the ``trees`` entries stayed byte-equal, and the Brumby-shaped
and SDAR-shaped programs (``_LEFT_ALONE``: the two families that had
``head_rows`` before; ``sdar.step`` is ``_block_round``) were added
from the commit before it, whose text they still are
(``tests/test_head_rows.py``). ISSUE 53 wrote ``mistral.prefill``,
``mistral.step``, ``kexaone.prefill``, ``kexaone.step`` and the
``trees`` entry ``kexaone.cache`` anew and meant to: rows by position
in the compute dtype lie flat in every family, ``(slots, S, Hkv * D)``
(K-EXAONE's full layers; its rings keep a head a row), so the four
programs reshape the fed positions before the write and the leaf by
head before the dense routine, which is still the routine off a TPU
(a decode round's kernel is chosen by backend and dtype, and this file
lowers for the CPU in float32), and ``models/llama.py`` takes the
engine's ``token_mask`` and hands its attention ``lengths``. The
LongCat lines (latent rows), Brumby's, and ``sdar.prefill`` and
``sdar.step`` (flat since ISSUE 44, the same routine as before) stayed
byte-equal. What the served
sizes compile to is ``tests/test_chip_compile.py``'s to hold. A PR that
means to change those programs writes the file anew and says so:

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
    # post-norm blocks, rings beside full rows, the sigmoid router with a
    # shared expert and a leading dense layer (ISSUE 35 added this shape)
    "kexaone": ("k_exaone", dict(
        vocab_size=256, num_layers=5, d_model=64, num_heads=8,
        num_kv_heads=2, head_dim=16, mlp_dim=192, window=8,
        expert_mlp_dim=32, num_experts=16, moe_topk=4, ep_size=2,
        ep_rank=0)),
    # the two families that had ``head_rows`` before ISSUE 51: a state a
    # sequence in place of rows, and a block decoder
    "brumby": ("brumby", dict(
        vocab_size=101, num_layers=2, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, mlp_dim=64, rope_theta=1e4)),
    "sdar": ("sdar_moe", dict(
        vocab_size=97, num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, expert_mlp_dim=32, num_experts=8,
        moe_topk=2, block_length=4, denoising_steps=2,
        remasking="sequential", mask_token_id=96)),
}
# what ISSUE 51 had to leave alone, beside every family's ``step``
_LEFT_ALONE = ("brumby.prefill", "brumby.step", "sdar.prefill",
               "sdar.step")


def _model(family: str):
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model

    name, extra = _SHAPES[family]
    mc = ModelConfig(name=name, dtype="float32", compute_dtype="float32")
    mc.extra = dict(extra)
    return get_model(mc)


def lowered(family: str, program: str) -> str:
    """The text of one of the engine's two model programs for a small
    model of ``family``, greedy and without a bank, 4 slots x 64
    positions, a prefill bucket of 16; at JAX's own matmul precision,
    whatever the caller has set."""
    from pytorch_distributed_nn_tpu.inference.generate import init_cache
    from pytorch_distributed_nn_tpu.serve import engine

    model = _model(family)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]

    def vec(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype)

    with jax.default_matmul_precision(None):
        if program == "step":
            cache = jax.eval_shape(lambda: init_cache(model, 4, 64))
            state = (vec(4), vec(4))
            if engine._block_of(model) is not None:
                # a block decoder's round, under the step's name
                state = jax.eval_shape(
                    lambda: engine._idle_block_state(
                        4, model.block_decoding()["block_length"]))
            return engine._serve_step.lower(
                model, params, cache, *state, vec(4, jnp.bool_),
                vec(4), jax.ShapeDtypeStruct((), jnp.int32)).as_text()
        cache = jax.eval_shape(lambda: init_cache(model, 1, 16))
        return engine._serve_prefill.lower(
            model, params, cache, jax.ShapeDtypeStruct((1, 16), jnp.int32),
            vec(1), vec(1)).as_text()


def _tree_digest(tree) -> str:
    """Names, shapes and types of a tree's leaves, as one digest."""
    rows = sorted(
        ("/".join(str(getattr(k, "key", k)) for k in path),
         tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])
    return hashlib.sha256(repr(rows).encode()).hexdigest()


def trees(family: str) -> dict:
    """What a caller of the shared modules can see of them besides the
    lowered programs: the parameter tree and the cache tree (names,
    shapes, types) and, with ``model.init``'s own weights under key 0,
    the uncached forward's logits of ten fixed tokens (the last
    position's first eight, and the mean magnitude of all)."""
    from pytorch_distributed_nn_tpu.inference.generate import init_cache

    model = _model(family)
    tokens = (jnp.arange(10, dtype=jnp.int32) * 37 + 5)[None] % 101
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.key(0), tokens, train=False)["params"]
        logits = model.apply({"params": params}, tokens)[0]
    return {
        "params": _tree_digest(params),
        "cache": _tree_digest(jax.eval_shape(
            lambda: init_cache(model, 4, 64))),
        "logits": [round(float(v), 6) for v in logits[-1, :8]],
        "logits_mean_abs": round(float(jnp.abs(logits).mean()), 6),
    }


def digest(name: str) -> str:
    """sha256 of the lowered text of ``<family>.<program>``."""
    return hashlib.sha256(lowered(*name.split(".")).encode()).hexdigest()


def digests() -> dict:
    out = {f"{family}.{program}": digest(f"{family}.{program}")
           for family in ("mistral", "longcat", "kexaone")
           for program in ("prefill", "step")}
    out["trees"] = {family: trees(family)
                    for family in ("longcat", "kexaone")}
    out.update({name: digest(name) for name in _LEFT_ALONE})
    return out


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))
