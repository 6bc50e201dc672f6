"""Autoregressive generation with a KV cache.

Beyond the reference's scope (it is a training harness), but a framework
a reference user switches to needs an inference path. Design:

- the cache is a flax "cache" collection sized once by ``init_cache``
  (one ``cached_key``/``cached_value``/``cache_index`` per attention
  layer — :class:`nn.attention.MultiHeadAttention` with ``decode=True``);
- the prompt is consumed in ONE prefill ``apply`` (full (B, P) chunk —
  batched matmuls on the MXU, not P sequential steps);
- the token loop is ONE jitted device program (``lax.scan`` over
  sample→feed steps, cache donated): decoding is O(T) in cache reads
  instead of the O(T^2) full-context recompute, and the host dispatches
  once per generate() call, not once per token;
- sampling: greedy (``temperature=0``), temperature, and top-k — all on
  device via ``jax.random.categorical``.

Supported models: the Llama family (rotary positions are absolute via
the cache index) and TransformerLM (learned positional table offset by
a model-level cache counter). Token-identical to full-context argmax
decoding — the oracle in tests/test_generate.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_nn_tpu import obs


def shard_params_for_inference(params, mesh):
    """Place params on ``mesh`` per the TP/EP layout rules
    (parallel/sharding_rules) with no fsdp sharding — inference has no
    optimizer state to spread, and row/column-parallel weights are what
    make a model wider than one chip's HBM decodable. XLA inserts the
    Megatron all-reduces in the decode step from these layouts alone."""
    from pytorch_distributed_nn_tpu.parallel.sharding_rules import (
        path_str,
        spec_for,
    )
    from pytorch_distributed_nn_tpu.runtime.mesh import (
        AXIS_EXPERT,
        AXIS_TENSOR,
        global_device_put,
    )

    tensor = mesh.shape.get(AXIS_TENSOR, 1)
    expert = mesh.shape.get(AXIS_EXPERT, 1)
    shardings = jax.tree_util.tree_map_with_path(
        lambda kp, x: NamedSharding(
            mesh,
            spec_for(path_str(kp), tuple(x.shape), tensor=tensor,
                     expert=expert),
        ),
        params,
    )
    return global_device_put(params, shardings)


def _shard_cache(cache, mesh):
    """KV caches shard their heads dim over ``tensor`` (matching the
    q/k/v projection layout, so cache writes stay local); scalars and
    indivisible leaves replicate."""
    from pytorch_distributed_nn_tpu.runtime.mesh import (
        AXIS_TENSOR,
        global_device_put,
    )

    tensor = mesh.shape.get(AXIS_TENSOR, 1)

    def spec(x):
        # flat (B, T, Hkv * D) rows, (B, T, Hkv, D) payloads and
        # (B, T, Hkv) int8-cache scales all carry heads at axis 2, a
        # head's lanes together
        if x.ndim in (3, 4) and tensor > 1 and x.shape[2] % tensor == 0:
            return P(None, None, AXIS_TENSOR)
        return P()

    shardings = jax.tree.map(lambda x: NamedSharding(mesh, spec(x)),
                             cache)
    return global_device_put(cache, shardings)


def init_cache(model, batch_size: int, max_len: int):
    """Size the per-layer KV caches for a (batch_size, max_len) stream.

    Returns the "cache" pytree (zeros); params come from training /
    checkpoints. Shape inference only — ``jax.eval_shape`` over
    ``model.init``, so no parameters are materialized and no forward
    runs (an 8B model would otherwise allocate and discard the full
    param set here on every generate() call).
    """
    try:
        shapes = jax.eval_shape(
            lambda: model.init(
                jax.random.key(0),
                jnp.zeros((batch_size, max_len), jnp.int32),
                train=False, decode=True,
            )
        )
    except TypeError as e:  # no `decode` kwarg on this model family
        raise ValueError(
            f"{type(model).__name__} has no decode cache support"
        ) from e
    if "cache" not in shapes:
        raise ValueError(
            f"{type(model).__name__} has no decode cache support"
        )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def _apply_decode(model, params, cache, tokens):
    """One (B, T) decode chunk: returns ((B, V) next-token logits,
    updated cache). last_only skips the vocab projection for all but
    the final position (the only row generation consumes)."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens,
        train=False, decode=True, last_only=True, mutable=["cache"],
    )
    return logits[:, -1, :], mutated["cache"]


_decode_step = functools.partial(jax.jit, static_argnums=(0,),
                                 donate_argnums=(2,))(_apply_decode)


def _apply_prefill_ragged(model, params, cache, tokens, lengths):
    """Ragged prefill: ``tokens`` (B, P) LEFT-ALIGNED rows (row i's real
    prompt in columns [0, lengths[i]); columns beyond are don't-care).
    Every row writes its KV from cache slot 0 (``cache_positions`` = 0),
    and the per-position causal mask keeps slots >= lengths[i] out of
    every consumed attention row, so each row computes exactly its
    sequential prefill. Returns ((B, V) logits at each row's LAST real
    position, cache). Full logits are materialized (not ``last_only``)
    because "last" differs per row — fine at serving batch sizes; the
    (P-1) extra head rows are the price of one fused prefill. A model
    that takes ``token_mask`` is told which columns are real: padding
    written by position is masked until overwritten, but in a ring
    cache (a sliding window's) it would displace real rows."""
    zeros = jnp.zeros((tokens.shape[0],), jnp.int32)
    extra = {"token_mask": jnp.arange(tokens.shape[1])[None, :]
             < lengths[:, None]} \
        if getattr(model, "takes_token_mask", False) else {}
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens,
        train=False, decode=True, mutable=["cache"],
        cache_positions=zeros, **extra,
    )
    last = (lengths.astype(jnp.int32) - 1)[:, None, None]
    next_logits = jnp.take_along_axis(logits, last, axis=1)[:, 0, :]
    return next_logits, mutated["cache"]


prefill_ragged = functools.partial(
    jax.jit, static_argnums=(0,), donate_argnums=(2,)
)(_apply_prefill_ragged)


def _apply_decode_ragged(model, params, cache, tokens, positions,
                         **extra):
    """One per-row decode step: ``tokens`` (B,) int32 next tokens,
    ``positions`` (B,) int32 per-row cache depths (row i's token lands
    in cache slot positions[i] and attends slots [0, positions[i]]).
    The shared scalar cache_index is untouched — rows at different
    depths share one batch, which is what continuous batching needs.
    ``extra`` goes to the model as keywords (the serving engine's
    ``token_mask``). Returns ((B, V) next-token logits, cache)."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens[:, None],
        train=False, decode=True, last_only=True, mutable=["cache"],
        cache_positions=positions.astype(jnp.int32), **extra,
    )
    return logits[:, -1, :], mutated["cache"]


decode_step_ragged = functools.partial(
    jax.jit, static_argnums=(0,), donate_argnums=(2,)
)(_apply_decode_ragged)


@functools.partial(jax.jit, static_argnums=(0, 5, 7, 8, 9),
                   donate_argnums=(2,))
def _decode_loop(model, params, cache, next_logits, rng, n_steps,
                 temperature, top_k, eos_token, top_p):
    """The whole autoregressive loop as ONE device program: ``lax.scan``
    over decode steps (sample → feed → next logits). One dispatch for
    all ``n_steps`` tokens — per-token host round-trips would otherwise
    cost ~dispatch-latency × n_steps. ``temperature`` is
    a traced operand (per-request values don't recompile); only
    n_steps/top_k/eos_token key the compile cache. Returns (n_steps, B)
    sampled tokens."""

    def step(carry, _):
        next_logits, cache, rng, done = carry
        rng, step_rng = jax.random.split(rng)
        tok = _sample(next_logits, temperature=temperature, top_k=top_k,
                      rng=step_rng, top_p=top_p)
        if eos_token is not None:
            tok = jnp.where(done, eos_token, tok)
            done = done | (tok == eos_token)
        tok = tok.astype(jnp.int32)
        # the final iteration's decode is one step of dead compute
        # (its logits are never sampled) but keeps the scan uniform;
        # the cache is sized for it (index ends at P + n_steps)
        next_logits, cache = _apply_decode(model, params, cache,
                                           tok[:, None])
        return (next_logits, cache, rng, done), tok

    done0 = jnp.zeros((next_logits.shape[0],), bool)
    (_, final_cache, _, _), toks = jax.lax.scan(
        step, (next_logits, cache, rng, done0), None, length=n_steps
    )
    # the caller discards final_cache, but RETURNING it is what lets
    # the donated input cache alias an output buffer — without it XLA
    # warns "donated buffers were not usable" and the loop transiently
    # holds TWO cache copies (268 MB at the 8B's b=8/T=256, real HBM)
    return toks, final_cache


@functools.partial(jax.jit, static_argnums=(0, 5, 7, 8, 9),
                   donate_argnums=(2,))
def _decode_loop_ragged(model, params, cache, next_logits, rng, n_steps,
                        temperature, top_k, eos_token, top_p, lengths):
    """Ragged twin of :func:`_decode_loop`: the scan carry additionally
    holds per-row cache depths (starting at the prompt lengths), and
    each step feeds through the per-row decode apply. Same fused
    one-dispatch property; ``lengths`` is traced so different ragged
    batches share one compile."""

    def step(carry, _):
        next_logits, cache, rng, done, pos = carry
        rng, step_rng = jax.random.split(rng)
        tok = _sample(next_logits, temperature=temperature, top_k=top_k,
                      rng=step_rng, top_p=top_p)
        if eos_token is not None:
            tok = jnp.where(done, eos_token, tok)
            done = done | (tok == eos_token)
        tok = tok.astype(jnp.int32)
        next_logits, cache = _apply_decode_ragged(model, params, cache,
                                                  tok, pos)
        return (next_logits, cache, rng, done, pos + 1), tok

    done0 = jnp.zeros((next_logits.shape[0],), bool)
    (_, final_cache, _, _, _), toks = jax.lax.scan(
        step, (next_logits, cache, rng, done0,
               lengths.astype(jnp.int32)), None, length=n_steps
    )
    return toks, final_cache


def _sample(logits, *, temperature, top_k: int, rng, top_p: float = 0.0):
    """logits (B, V) -> tokens (B,). ``temperature`` may be a traced
    scalar OR a traced (B,) per-row vector (0 selects greedy via
    jnp.where — top-k/top-p membership is temperature-invariant, so
    filtering before scaling is equivalent), which keeps per-request
    temperatures from recompiling the decode scan. A (B,) temperature
    scales row-wise and picks greedy row-wise, so mixed greedy+sampled
    batches compose with the top_p mask at batch granularity.
    ``top_k``/``top_p`` stay static (top_k needs a static k; p changes
    the masking structure)."""
    greedy = jnp.argmax(logits, axis=-1)
    if rng is None:
        return greedy
    if top_k > 0:
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p > 0.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # with cumulative mass >= top_p (the first token always stays)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p  # mass BEFORE this token < p
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
            keepdims=True,
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    temperature = jnp.asarray(temperature)
    # a (B,) vector must scale along the batch axis, not broadcast
    # against (B, V)'s vocab axis — the scalar shape is unchanged
    scale_t = (temperature[:, None] if temperature.ndim == 1
               else temperature)
    scaled = logits / jnp.maximum(scale_t, 1e-6)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature == 0.0, greedy, sampled)


def generate(model, params, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0, rng=None,
             eos_token: int | None = None, mesh=None,
             prefill_chunk: int = 0, prompt_lengths=None):
    """Generate continuations for ``prompt`` (B, P) int32.

    Returns (B, P + max_new_tokens) tokens (prompt included). With
    ``eos_token`` set, sequences that emit it keep it and then pad with
    it (the batch still runs max_new_tokens steps).

    ``prompt_lengths``: ragged batches. (B,) ints — row i's real prompt
    is the LAST prompt_lengths[i] columns (left-padding convention, pad
    values are don't-care). Rows are realigned internally and decoded
    via per-row cache positions; greedy output for each row is
    bit-identical to running that row alone through generate()
    (tests/test_generate.py golden test). The returned array keeps the
    padded prompt prefix as passed: generated tokens for every row live
    in columns [P, P + max_new_tokens).

    ``mesh``: distributed decoding — params are laid out tensor/expert-
    parallel (:func:`shard_params_for_inference`), the KV cache shards
    its heads dim to match, and the jitted decode program runs SPMD over
    the mesh with XLA-inserted collectives. Token-identical to the
    single-device path.

    ``prefill_chunk``: consume the prompt in chunks of this many tokens
    instead of one apply. One-shot prefill scores (P, P); chunked
    prefill bounds live attention scores at (chunk, P) — the difference
    between a 32k-token prompt fitting or not. Token-identical either
    way (the decode cache makes chunked prefill exact).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(f"prompt must be (B, P>=1), got {prompt.shape}")
    if max_new_tokens < 0:
        raise ValueError(
            f"max_new_tokens must be >= 0, got {max_new_tokens}"
        )
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if prefill_chunk < 0:
        raise ValueError(
            f"prefill_chunk must be >= 0, got {prefill_chunk}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs an rng key")
    B, P_len = prompt.shape
    if prompt_lengths is not None:
        lens_host = np.asarray(prompt_lengths, dtype=np.int64)
        if lens_host.shape != (B,):
            raise ValueError(
                f"prompt_lengths must be ({B},), got {lens_host.shape}"
            )
        if lens_host.min() < 1 or lens_host.max() > P_len:
            raise ValueError(
                f"prompt_lengths must be in [1, {P_len}], got "
                f"[{lens_host.min()}, {lens_host.max()}]"
            )
        if mesh is not None:
            raise ValueError(
                "ragged prompts (prompt_lengths) are not supported with "
                "mesh sharding yet — shard the params and run the "
                "uniform path, or batch equal-length rows"
            )
        if prefill_chunk:
            raise ValueError(
                "prompt_lengths and prefill_chunk are mutually "
                "exclusive (ragged prefill is one fused apply)"
            )
    if max_new_tokens == 0:
        return prompt
    if prompt_lengths is not None:
        return _generate_ragged(
            model, params, prompt, max_new_tokens, lens_host,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=rng,
            eos_token=eos_token,
        )
    total = P_len + max_new_tokens
    cache = init_cache(model, B, total)
    if mesh is not None:
        params = shard_params_for_inference(params, mesh)
        cache = _shard_cache(cache, mesh)
        from pytorch_distributed_nn_tpu.runtime.mesh import (
            global_device_put,
        )

        prompt = global_device_put(prompt, NamedSharding(mesh, P()))

    # prefill: the whole prompt in one chunk, or bounded chunks for
    # long prompts (each chunk attends to the cache prefix, so live
    # scores are (chunk, filled) instead of (P, P))
    with obs.span("inference/prefill", batch=B, prompt_len=P_len):
        if prefill_chunk and prefill_chunk < P_len:
            pos = 0
            while pos < P_len:
                chunk = prompt[:, pos:pos + prefill_chunk]
                next_logits, cache = _decode_step(model, params, cache,
                                                  chunk)
                pos += chunk.shape[1]
        else:
            next_logits, cache = _decode_step(model, params, cache,
                                              prompt)

    # greedy ignores the key; pass a constant so the trace is uniform
    rng0 = rng if rng is not None else jax.random.key(0)
    # span covers dispatch of the fused scan, not device completion —
    # callers that fence (bench) see the true decode window in-trace
    with obs.span("inference/decode_loop", batch=B,
                  new_tokens=max_new_tokens):
        toks, _ = _decode_loop(model, params, cache, next_logits, rng0,
                               max_new_tokens, jnp.float32(temperature),
                               int(top_k), eos_token, float(top_p))
    obs.get_registry().counter(
        "inference_tokens_total", "tokens generated (dispatched)").inc(
        B * max_new_tokens)
    return jnp.concatenate([prompt, toks.T.astype(jnp.int32)], axis=1)


def _generate_ragged(model, params, prompt, max_new_tokens, lens_host,
                     *, temperature, top_k, top_p, rng, eos_token):
    """The ragged-batch body of :func:`generate` (validated inputs).

    Left-padded rows are realigned to left-ALIGNED internally (row i's
    prompt occupies cache slots [0, L_i)), prefilled in one per-row
    apply, then decoded by the ragged scan with per-row cache depths.
    The causal-by-slot mask zeroes every don't-care slot exactly
    (softmax weight exp(-1e30 - max) underflows to 0.0), so each row's
    float math is the sequential row's float math — bit-identical
    greedy decoding, not just approximately equal."""
    B, P_len = prompt.shape
    lengths = jnp.asarray(lens_host, jnp.int32)
    # realign: aligned[i, j] = prompt[i, (j + P - L_i) % P] puts row
    # i's first real token at column 0 and wraps its padding to the
    # tail (which the mask then excludes from all consumed rows)
    shift = (jnp.arange(P_len)[None, :]
             + (P_len - lengths)[:, None]) % P_len
    aligned = jnp.take_along_axis(prompt, shift, axis=1)
    cache = init_cache(model, B, P_len + max_new_tokens)
    with obs.span("inference/prefill", batch=B, prompt_len=P_len,
                  ragged=True):
        next_logits, cache = prefill_ragged(model, params, cache,
                                            aligned, lengths)
    rng0 = rng if rng is not None else jax.random.key(0)
    with obs.span("inference/decode_loop", batch=B,
                  new_tokens=max_new_tokens):
        toks, _ = _decode_loop_ragged(
            model, params, cache, next_logits, rng0, max_new_tokens,
            jnp.float32(temperature), int(top_k), eos_token,
            float(top_p), lengths)
    obs.get_registry().counter(
        "inference_tokens_total", "tokens generated (dispatched)").inc(
        B * max_new_tokens)
    return jnp.concatenate([prompt, toks.T.astype(jnp.int32)], axis=1)
