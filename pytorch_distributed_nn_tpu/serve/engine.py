"""Continuous-batching decode engine.

The data plane of the serving stack: a dense batched KV cache of
``max_slots`` rows, stepped one token per round for every active row
(a block decoder's: a block of positions, see below),
with finished rows retired *mid-batch* and newly admitted requests
prefilled into the freed rows — the batch never drains to admit work.
Policy (who gets in, who waits) is the scheduler's
(:mod:`serve.scheduler`); this module only executes its decisions.

Correctness contract: greedy decode through the engine is
**bit-identical** to sequential ``inference.generate.generate`` for the
same prompt (tests/test_serve.py golden test). Both paths run the same
per-row math — prefill via :func:`inference.generate.prefill_ragged`
(batch of one) and per-round steps via the same per-row decode apply,
where every row's attention is masked to exactly its own filled cache
prefix; masked slots contribute exact 0.0 after softmax, so sharing a
batch with strangers cannot perturb a row's floats. That holds on the
CPU backend, where the golden test runs. On the TPU in bf16 it does
not (chip_smoke.py, PR 23: at Llama-3-8B widths 9 of 12 requests left
the sequential path): a row goes through programs of other shapes here
(8 slots over a 256-row cache) than there (one row, a cache of its own
length), and the compiler orders the same sums differently. Every
divergence seen was a choice between logits less than half a bf16 step
apart; chip_smoke.py gates on that margin.

What the cache holds is the model's. Most leaves are *rows by absolute
position*, ``(slots, max_seq_len, ...)``: keys and values, or a latent
attention's compressed rows; position p of a sequence is row p, so a
prefix of a sequence is a prefix of its rows, which is what the prefix
cache and the block store stand on. A model may declare leaves that are
not (``leaves_not_by_position()``): a *ring*, ``(slots, window, ...)``,
a sliding window's rows with position p in row p mod window
(K-EXAONE's window layers), and a *state*, ``(slots, ...)`` with no
position axis at all: the running value of a recurrence, one a sequence
whatever its length (Jamba's Mamba layers: the SSM state and the
convolution's last inputs). Both are written by the model's own
programs and copied into a slot whole by :func:`_insert_row`, like any
leaf; neither can be cut into blocks of positions, so an engine whose
model declares any keeps no prefix cache and no block store and refuses
block export and ingest (``__init__`` says why). A state differs from
a ring in one thing the model must see to: a ring row written by a
padded position is masked until overwritten, a state advanced by one is
wrong for good, so such a model takes the ``token_mask``
(:func:`_mask_kw`) and holds its state through every position that is
not real: a prefill bucket's padding, a decode round's retired row.

There are two model programs, :func:`_serve_prefill` and
:func:`_serve_step`, and what the engine holds and the batch contains
decides their form: each takes ``lora`` (the bank and the rows' adapter
ids) if the engine was built with a bank, and ``sampling`` (the rows'
specs, RNG lanes and running logprobs) while a row of a sampled request
is active. Neither is an option. An absent argument is ``None``, an
empty pytree to ``jax.jit``: the program lowers to the text of a
function that never had the argument (tests/test_quality.py reads it),
so a greedy batch of an engine without a bank pays for neither.

The loop keeps one decode round in flight. In steady state a call of
:meth:`ServingEngine._decode_round` dispatches round n+1 and *then*
fetches round n's tokens, so all the host does for a round (the fetch,
the hooks, streaming, retirement, a retire's ``_save_blocks``, the next
``next_admissions``) runs while the chip computes the next one; JAX's
asynchronous dispatch does the rest, each step's outputs being the next
step's inputs. Three rules make that a reordering of host work and not
another result:

- *a row stops on the device.* ``_serve_step`` carries each row's
  remaining budget and the engine's stop token beside ``active`` and
  returns the next round's mask: a row is frozen in the very step that
  produces its last token, so the round already dispatched computes
  nothing for it. The host applies the same rule (:meth:`_done`) one
  fetch later and retires the row then.
- *only an admission writes device slot state.* The host's mirrors
  (``_Slot``) lag the device by the round in flight, so the host never
  pushes them wholesale: an admission sets the rows it filled (last
  token, depth, active, budget; adapter id and sampling rows where the
  engine has them) in one program of one shape, ``_write_rows``, queued
  behind the round in flight and the rows' ``_insert_row``; a retire
  writes nothing. (The chaos flip drill writes its one corrupted row
  the same way, after dropping the round that was fed the true token.)
- *a round knows its rows.* Rounds are numbered as they are dispatched
  and a ``_Slot`` records the first round that holds it: the token a
  round carries for a slot filled after its dispatch is the last
  occupant's and reaches nobody.

``has_work`` is true while a round is unfetched, so every driver that
steps while it is (``run_until_idle``, ``drain``, the server's loop
before it parks, the fleets) leaves nothing in flight; an admission's
prefill fetch drains the device, and the next call refills the
pipeline.

Hot-loop discipline (lint-enforced): :meth:`ServingEngine._decode_round`
contains the per-round device work and performs NO host->device
transfers and no jnp/jax array construction — slot state (last token,
per-row cache depth, active mask, remaining budget) lives on device
across rounds, and the one device->host fetch per call (the tokens the
host must see to stream them and to retire rows) is a single
``np.asarray`` of a (slots,) array.

A *block decoder* (a model that declares ``block_decoding()``:
SDAR's block diffusion, ``models/sdar_moe.py``) changes what a round
is and nothing of the above. Its round (:func:`_block_round`, under
``_serve_step``'s name) forwards every row's open block of ``B``
positions and unmasks some of them (a denoising step). The step that
leaves none masked hands the block out, up to ``B`` tokens for the
request at once, and the row then *owes* the block's rows: what the
cache holds of it was computed while some of its positions were still
fed the mask token. The next round's forward of that row feeds the owed
block's final tokens and the fresh block, all masked, behind it, ``2B``
positions under the mask by blocks, so the owed rows are written in the
forward that takes the fresh block's first step: a block costs its
steps and no forward of its own to commit it, and a request's last
block is never committed at all. Rows at different steps share one
program, so every row feeds ``2B`` positions; a row that owes nothing
feeds its open block and ``B`` positions that are not real. So a round
gives a row none or up to a block of tokens, the one fetch is a
(slots, B + 1) array (the tokens and how many), a row's rounds to go
are its blocks left times a block's steps, a prefill fills the prompt's
whole blocks and yields no first token (the prompt's tail opens the
first block, written with the slot's state), and time to first token is
the first block's last step. A row that retires owes its last block:
the rows written for good end at that block's start, and a retire saves
the pages wholly under it (:meth:`ServingEngine._donate_blocks`). The
three rules hold as they stand: the device stops a row in the round
that hands out its last token; only an admission writes slot state
(``_write_block_rows``); a round knows its rows. ``docs/sdar.md`` has
the pieces and what is refused.

Observability: TTFT + per-token latency histograms, batch-occupancy /
queue-depth / KV-utilization gauges, one flight-ring ``serve`` event
per decode round (a wedged loop is visible to the doctor as a stalled
round counter), per-request retroactive spans when tracing is on, and
per-request ``serve_request`` JSONL records through MetricsLogger.
The loop accounts for its own thread (``ServingEngine.loop``, an
:class:`obs.GoodputMeter` over the serve loop's phases): every
instant from its first round on belongs to one phase, each phase's
boundary also writes the span, and every round leaves a record
(:func:`obs.serve_loop_records`) that names what the thread traced,
lowered or compiled in it and what the collector took.
"""

from __future__ import annotations

import collections
import functools
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.inference.generate import (
    _apply_decode_ragged,
    init_cache,
)
from pytorch_distributed_nn_tpu.nn.lora import num_adapters
from pytorch_distributed_nn_tpu.obs import (
    audit,
    flight,
    goodput,
    jitwatch,
    meter,
    trace,
    watchtower,
    xray,
)
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import autoscale, decoding
from pytorch_distributed_nn_tpu.serve.kv_pool import KVPool
from pytorch_distributed_nn_tpu.serve.prefix_cache import PrefixCache
from pytorch_distributed_nn_tpu.serve.scheduler import (
    Request,
    Scheduler,
    branch_seq_ids,
)

log = logging.getLogger(__name__)

# TTFT spans queueing (ms..s under load); per-token latency is ms-scale
_TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                 2.5, 5.0, 10.0, 30.0)
_TOKEN_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                  0.25, 0.5, 1.0)
# decode rounds between two readings of a model's device-side counters:
# ~100 us of fetch every second or two of serving
_COUNTER_ROUNDS = 64
# one row of the programs' ``sampling`` argument: a DecodeSpec's
# numbers, the row's RNG lane and step, its running logprob
_SAMPLING_ROW = dict(temp=np.float32, top_k=np.int32, top_p=np.float32,
                     seed=np.int32, branch=np.int32, step=np.int32,
                     logprob=np.float32)


def _mask_kw(model, mask) -> dict:
    """``token_mask`` for a model that takes it (what it computes or
    counts follows the real tokens: a dropless MoE); nothing otherwise,
    and the other models' programs are as they were."""
    return {"token_mask": mask} \
        if getattr(model, "takes_token_mask", False) else {}


def _block_of(model):
    """What a block decoder declares (``block_decoding()``: positions a
    block, denoising steps, the rule that unmasks, its threshold, the
    mask token), or None for a model that emits a token a round."""
    declared = getattr(model, "block_decoding", None)
    return None if declared is None else declared()


def _block_steps(masked: int, block: int, steps: int) -> int:
    """Denoising forwards that unmask ``masked`` positions of a block
    of ``block`` planned in ``steps``: step t takes ``block // steps``,
    one more in the first ``block % steps`` (the family's
    ``get_num_transfer_tokens``), fewer if fewer are masked. Exact for
    the rules that take just that many, an upper bound for
    ``low_confidence_dynamic``."""
    t = 0
    while masked > 0:
        masked -= block // steps + (t < block % steps)
        t += 1
    return t


def _block_rounds(prompt_len: int, new_tokens: int, block: int,
                  steps: int) -> int:
    """Rounds a block decoder's row needs at most: its first block's
    steps (the prompt's tail known from the start), then those of every
    further block that holds one of its tokens. No block has a round of
    its own to commit it."""
    tail = prompt_len % block
    return _block_steps(block - tail, block, steps) \
        + (-(-(tail + new_tokens) // block) - 1) \
        * _block_steps(block, block, steps)


def _idle_block_state(slots: int, block: int) -> tuple:
    """A block decoder's slot state with no row live: ``(out, place)``
    as :func:`_block_round` takes them."""
    idle = jnp.zeros((slots,), jnp.int32)
    return (jnp.zeros((slots, block + 1), jnp.int32),
            dict(depth=idle, masked=jnp.zeros((slots, block), bool),
                 step=idle, skip=idle, owes=jnp.zeros((slots,), bool),
                 owed=jnp.zeros((slots, block), jnp.int32)))


def _apply_prefill_at(model, params, cache, tokens, lengths, starts,
                      **extra):
    """Ragged prefill with a per-row cache-write offset: row i's KV
    lands in cache rows [starts[i], starts[i] + lengths[i]) and its
    queries attend absolute positions [0, starts[i] + t] — which is
    what prefix-cache suffix prefill needs: the restored rows
    [0, starts[i]) are already in ``cache`` and the suffix computes
    exactly the floats a full from-zero prefill would have. Returns
    ((B, V) logits at each row's LAST real suffix position, cache):
    the model is told that row (``head_rows``) and its head scores no
    other, so no program holds ``(bucket, vocab)`` logits. ``extra``
    goes to the model as keywords (an engine's ``lora``), so a model
    never sees a keyword its engine has no use for."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens,
        train=False, decode=True, mutable=["cache"],
        cache_positions=starts.astype(jnp.int32), **extra,
        head_rows=(lengths.astype(jnp.int32) - 1)[:, None],
        **_mask_kw(model, jnp.arange(tokens.shape[1])[None, :]
                   < lengths[:, None]),
    )
    return logits[:, 0, :], mutated["cache"]


def _draw(logits, sampling, active):
    """Each row's token under its own spec, and the rows' sampling
    state one step on. Row i draws with the key
    ``fold_in(fold_in(key(seed), branch), step)``, the same in a
    prefill and in a decode round, so a branch's whole stream is one
    unbroken (seed, branch, step) sequence; a temperature-0 row takes
    the argmax. ``logprob`` sums the chosen tokens' log-probabilities
    under the model distribution: best-of-n ranks by it at retirement
    and needs no fetch before."""
    keys = decoding.row_keys(sampling["seed"], sampling["branch"],
                             sampling["step"])
    toks = decoding.sample_rows(logits, sampling["temp"], sampling["top_k"],
                                sampling["top_p"], keys).astype(jnp.int32)
    logprob = sampling["logprob"] + decoding.token_logprobs(logits, toks)
    return toks, dict(
        sampling,
        step=jnp.where(active, sampling["step"] + 1, sampling["step"]),
        logprob=jnp.where(active, logprob, sampling["logprob"]))


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_prefill(model, params, cache, tokens, lengths, starts,
                   lora=None, sampling=None):
    """Batch-of-one (suffix) prefill and the first token, chosen on the
    device so that the only host transfer is the token itself:
    ``(tokens, filled (1, P_pad, ...) row cache, sampling one step
    on)``. ``starts`` (1,) int32 is the number of rows already restored
    from the prefix cache (0 for a miss). ``lora`` is the model's
    keywords for a bank (``lora_bank``, and ``adapter_ids`` (1,)).
    Without ``sampling`` the token is the (1,) argmax; with it (the
    step's layout, one row for each of the request's ``n`` branches)
    the one prompt's logits fan into ``n`` first tokens. An absent
    argument is an empty pytree: the program lowers as if the argument
    had never been written, and its output is absent too."""
    if _block_of(model) is not None:
        # a block decoder's prefill fills rows and yields no token: its
        # first block, the prompt's tail among it, is the rounds'
        # (nor a logit: the hidden states come back, the head is skipped)
        _, cache = _apply_prefill_at(
            model, params, cache, tokens, lengths, starts,
            return_hidden=True, **(lora or {}))
        return None, cache, sampling
    next_logits, cache = _apply_prefill_at(
        model, params, cache, tokens, lengths, starts, **(lora or {}))
    with jax.named_scope("head"):   # the choice of a token counts there
        if sampling is None:
            return (jnp.argmax(next_logits, axis=-1).astype(jnp.int32),
                    cache, None)
        n = sampling["step"].shape[0]
        toks, sampling = _draw(
            jnp.broadcast_to(next_logits[0], (n, next_logits.shape[-1])),
            sampling, True)
    return toks, cache, sampling


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _serve_step(model, params, cache, last_tok, lengths, active, remaining,
                eos, lora=None, sampling=None):
    """One decode round over all slots: feed every row its last token
    at its own cache depth and choose its next, by argmax or, with
    ``sampling`` (per-slot ``temp``, ``top_k``, ``top_p``, ``seed``,
    ``branch``, ``step``, ``logprob``), under each row's own spec: a
    greedy row in a mixed batch sees the same logits and keeps its
    argmax. Inactive rows still flow through the batched apply (a
    dynamic batch size would recompile); their tokens/depths are frozen
    by the ``active`` mask and their cache writes land in retired rows
    that the next occupant's prefill overwrites (and masks until it
    grows there). A model that takes the ``token_mask`` is told which
    rows are live, and its attention over rows by position reads no
    key of the others (``nn/attention._round_attention``). A row stops
    HERE, in the step that produces its last token: ``remaining``
    (slots,) is the tokens a row may still emit and ``eos`` the
    engine's stop token (a scalar, -1 for none: no token is negative),
    and the mask that comes back is the next round's, so a round
    dispatched before the host has seen this one's tokens computes
    nothing for a finished row. ``lora`` and absent
    arguments as in :func:`_serve_prefill`, with ``adapter_ids``
    (slots,). Returns ``(tokens, lengths, active, remaining, cache,
    sampling)``.

    A block decoder (:func:`_block_of`) gets :func:`_block_round` under
    this program's name, with a block's state where a row's last token
    and depth stand; the branch is taken while tracing, on the static
    ``model``, so no other model's program holds a line of it."""
    block = _block_of(model)
    if block is not None:
        return _block_round(model, block, params, cache, last_tok, lengths,
                            active, remaining, eos, lora, sampling)
    logits, cache = _apply_decode_ragged(
        model, params, cache, last_tok, lengths, **(lora or {}),
        **_mask_kw(model, active[:, None]))
    with jax.named_scope("head"):
        if sampling is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt, sampling = _draw(logits, sampling, active)
    nxt = jnp.where(active, nxt, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    alive = active & (remaining > 1) & (nxt != eos)
    remaining = jnp.where(active, remaining - 1, remaining)
    return nxt, lengths, alive, remaining, cache, sampling


def _block_round(model, block, params, cache, out, place, active, remaining,
                 eos, lora, sampling):
    """One round of a block decoder over all slots: a forward of every
    row's open block of ``B`` positions, with the finished block before
    it if the row still *owes* that block's keys and values, and then,
    row by row, a *denoising step*.

    ``out`` (slots, B + 1) int32 is both state and what the host
    fetches: the open block's tokens and, last, how many tokens the
    round handed to the request (a row whose block became whole holds
    those tokens, left-aligned, where its block stood: the next block
    starts all masked and its tokens mean nothing). ``place`` is the
    rest of a row's state: ``depth`` (slots,) where the open block
    starts, a multiple of B; ``masked`` (slots, B) which positions of
    the block are unknown (they are fed ``mask_token_id``; a boolean,
    not a comparison of ids: the mask token in a prompt is a token);
    ``step`` the denoising steps the block has had; ``skip`` the
    leading positions that are the prompt's tail and are not handed
    out; ``owes`` (slots,) whether the block before the open one is
    whole but not written, and ``owed`` (slots, B) its final tokens.

    Every row feeds ``2B`` positions, one static shape. A row that owes
    feeds the owed block and the open one behind it from ``depth - B``:
    under the mask by blocks the owed block's queries see nothing of
    the open one and the open one sees the owed one whole, so the
    forward writes the owed block's rows from its final tokens (the
    *commit*) and takes the open block's step at once. A row that owes
    nothing (the step after, or a first block behind a prefill) feeds
    its open block from ``depth`` and ``B`` positions that are not real
    (``token_mask``, a left-aligned prefix as ever: they reach no
    expert and no counter, and what they write lies past the block,
    where nothing reads before a later forward overwrites it, or past
    ``max_seq_len``, where it is dropped). Only the open block's ``B``
    rows reach the head.

    Position p's own row of logits scores position p. ``x0`` is the
    best token but the mask token (or the row's draw), ``conf`` its
    probability, and ``n_t`` of the masked positions take their ``x0``
    (:func:`_block_steps`'s schedule): the leftmost (``sequential``),
    the most confident (``low_confidence_static``), or every one over
    ``confidence_threshold`` if those are at least ``n_t``
    (``low_confidence_dynamic``). A block that a step leaves whole is
    handed out in that round: the positions past ``skip``, up to the
    row's budget or its first ``eos``; the row's depth moves on by B, a
    fresh block begins, and the row owes the whole one unless it
    stopped (a stopped row's last block is never written: the retire
    saves no page that holds it). Every forward writes the open block's
    rows and the next overwrites them, the owed write last. Rows at
    different steps, a row that owes beside one that does not, a
    stopped row flowing through: one program. Returns as
    :func:`_serve_step`."""
    B, S = block["block_length"], block["denoising_steps"]
    mask_id, rule = block["mask_token_id"], block["remasking"]
    tok, masked, step = out[:, :B], place["masked"], place["step"]
    depth, skip, owes = place["depth"], place["skip"], place["owes"]
    both = jnp.concatenate(
        [place["owed"], jnp.where(masked, mask_id, tok)], axis=1)
    owing = owes[:, None]
    logits, mutated = model.apply(
        {"params": params, "cache": cache},
        jnp.where(owing, both, jnp.roll(both, B, axis=1)), train=False,
        decode=True, mutable=["cache"],
        cache_positions=jnp.where(owes, depth - B, depth), block_round=True,
        token_mask=active[:, None] & (owing | (jnp.arange(2 * B) < B)),
        head_rows=jnp.where(owing, B, 0) + jnp.arange(B),
        **(lora or {}))
    with jax.named_scope("head"):
        V = logits.shape[-1]
        allowed = jnp.where(jnp.arange(V) == mask_id, -jnp.inf, logits)
        if sampling is None:
            x0 = jnp.argmax(allowed, axis=-1).astype(jnp.int32)
        else:
            # a row's spec for each of its positions; the key is the row's
            # at this round, the position's place in its block folded in
            per = lambda v: jnp.repeat(v, B)  # noqa: E731
            keys = jax.vmap(lambda k: jax.vmap(
                lambda i: jax.random.fold_in(k, i))(jnp.arange(B)))(
                decoding.row_keys(sampling["seed"], sampling["branch"],
                                  sampling["step"]))
            x0 = decoding.sample_rows(
                allowed.reshape(-1, V), per(sampling["temp"]),
                per(sampling["top_k"]), per(sampling["top_p"]),
                keys.reshape((-1,) + keys.shape[2:])
            ).astype(jnp.int32).reshape(tok.shape)
            sampling = dict(sampling, step=jnp.where(
                active, sampling["step"] + 1, sampling["step"]))
    n_t = B // S + (step < B % S)
    if rule == "sequential":
        rank = jnp.cumsum(masked, axis=-1) - 1
    else:   # by falling confidence, the leftmost of equals first
        with jax.named_scope("head"):   # a pass over the vocabulary
            conf = jnp.exp(
                jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
                - jax.nn.logsumexp(logits, axis=-1))
        order = jnp.argsort(-jnp.where(masked, conf, -1.0), axis=-1,
                            stable=True)
        rank = jnp.argsort(order, axis=-1)
    take = masked & (rank < n_t[:, None])
    if rule == "low_confidence_dynamic":
        sure = masked & (conf > block["confidence_threshold"])
        take = jnp.where((sure.sum(axis=-1) >= n_t)[:, None], sure, take)
    take &= active[:, None]
    tok = jnp.where(take, x0, tok)
    whole = active & ~(masked & ~take).any(axis=-1)
    place_i = jnp.arange(B)[None]
    n_out = jnp.minimum(B - skip, remaining)
    stops = (tok == eos) & (place_i >= skip[:, None]) \
        & (place_i < (skip + n_out)[:, None])
    stopped = stops.any(axis=-1)
    n_out = jnp.where(stopped, jnp.argmax(stops, axis=-1) - skip + 1, n_out)
    n_out = jnp.where(whole, n_out, 0)
    handed = jnp.take_along_axis(
        tok, jnp.minimum(skip[:, None] + place_i, B - 1), axis=1)
    out = jnp.concatenate([
        jnp.where(whole[:, None], handed, tok), n_out[:, None]], axis=1)
    remaining = remaining - n_out
    alive = jnp.where(whole, (remaining > 0) & ~stopped, active)
    cache = model.add_block_counts(mutated["cache"], jnp.stack([
        active.sum(), (active & ~masked.any(axis=-1)).sum(), take.sum(),
        n_out.sum(), (active & owes).sum()]))
    place = dict(
        depth=jnp.where(whole, depth + B, depth),
        masked=jnp.where(whole[:, None], True, masked & ~take),
        step=jnp.where(whole, 0, jnp.where(active, step + 1, step)),
        skip=jnp.where(whole, 0, skip),
        owes=whole & alive,
        owed=jnp.where(whole[:, None], tok, place["owed"]))
    return out, place, alive, remaining, cache, sampling


@jax.jit
def _write_block_rows(out, place, active, remaining, rows, tails,
                      adapter_ids=None, sampling=None, mirror=None):
    """:func:`_write_rows` for a block decoder: a written row gets its
    first block. ``rows`` (5, slots) int32 is the host's ``(written,
    prompt positions in the block, depth, remaining, adapter)`` and
    ``tails`` (slots, B) the block's tokens, the prompt's tail first;
    the positions after it are masked. The row owes nothing: the blocks
    before its first are the prefill's."""
    written = rows[0] > 0
    B = tails.shape[1]
    out = jnp.where(written[:, None],
                    jnp.pad(tails, ((0, 0), (0, 1))), out)
    place = dict(
        depth=jnp.where(written, rows[2], place["depth"]),
        masked=jnp.where(written[:, None],
                         jnp.arange(B)[None] >= rows[1][:, None],
                         place["masked"]),
        step=jnp.where(written, 0, place["step"]),
        skip=jnp.where(written, rows[1], place["skip"]),
        owes=place["owes"] & ~written, owed=place["owed"])
    state = (out, place, jnp.where(written, rows[3] > 0, active),
             jnp.where(written, rows[3], remaining))
    if adapter_ids is not None:
        adapter_ids = jnp.where(written, rows[4], adapter_ids)
    if sampling is not None:
        sampling = {k: jnp.where(written, v, sampling[k])
                    if k in ("step", "logprob") else v
                    for k, v in mirror.items()}
    return state, adapter_ids, sampling


@jax.jit
def _write_rows(last_tok, lengths, active, remaining, rows,
                adapter_ids=None, sampling=None, mirror=None):
    """Set the slot state of the rows an admission filled, and of no
    other: the rest are the device's, one round ahead of what the host
    has seen. ``rows`` (5, slots) int32 is the host's ``(written, last
    token, depth, remaining, adapter)``; a written row is active if it
    has tokens left to emit. With ``sampling`` (the device's) and
    ``mirror`` (the host's, every slot) a row's spec and RNG lane
    are the host's for every slot (the device never changes them) and
    its step and running logprob only where written. One program of one
    shape for any number of rows; absent arguments as in
    :func:`_serve_prefill`."""
    written = rows[0] > 0
    out = (jnp.where(written, rows[1], last_tok),
           jnp.where(written, rows[2], lengths),
           jnp.where(written, rows[3] > 0, active),
           jnp.where(written, rows[3], remaining))
    if adapter_ids is not None:
        adapter_ids = jnp.where(written, rows[4], adapter_ids)
    if sampling is not None:
        sampling = {k: jnp.where(written, v, sampling[k])
                    if k in ("step", "logprob") else v
                    for k, v in mirror.items()}
    return out, adapter_ids, sampling


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
def _save_blocks(cache, store, block_size, slot, table, n):
    """Copy the first ``n`` full blocks of batch row ``slot`` into the
    physical blocks ``table[:n]`` of the block store (retire-side
    donation). ``table`` is shape-padded to the per-sequence block
    ceiling so slot/table/n are all traced — ONE program and ONE
    dispatch per retire, however many blocks the sequence spans (the
    per-block version made the cache-ON bench dispatch-bound).

    No loop over the blocks (one cost ~3 us a block of 32 KB): a leaf
    is one indexed scatter over the whole table, in place in the
    donated store. The table's tail past ``n`` is zeros and 0 is a
    physical block, so those entries are sent past the store's end and
    dropped."""
    def sv(c, s):
        if c.ndim < 2:
            return s
        k = min(c.shape[1] // block_size, table.shape[0])
        rows = jax.lax.dynamic_index_in_dim(c, slot, keepdims=False)
        blocks = rows[:k * block_size].reshape(
            (k, block_size) + c.shape[2:])
        j = jnp.arange(k)
        past = s.shape[0] + j  # distinct, so the indices stay unique
        return s.at[jnp.where(j < n, table[:k], past)].set(
            blocks.astype(s.dtype), mode="drop", unique_indices=True)
    return jax.tree.map(sv, cache, store)


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _restore_blocks(row_cache, store, block_size, table, n):
    """Copy physical blocks ``table[:n]`` of the store into rows
    [0, n * block_size) of a batch-of-one prefill cache
    (admission-side prefix restore; one dispatch per admission). One
    gather a leaf of the blocks the row cache's length holds (a count
    from its shape, so no block lands past its end; the caller
    guarantees n * block_size <= that length, PrefixCache ``max_rows``
    caps matches), selected against the row cache's own rows from
    ``n * block_size`` on."""
    def rs(r, s):
        if r.ndim < 2:
            return r
        k = min(r.shape[1] // block_size, table.shape[0])
        rows = k * block_size
        got = s[table[:k]].reshape((1, rows) + s.shape[2:])
        keep = (jnp.arange(rows) < n * block_size).reshape(
            (1, rows) + (1,) * (r.ndim - 2))
        head = jnp.where(keep, got.astype(r.dtype), r[:, :rows])
        return head if rows == r.shape[1] else \
            jax.lax.dynamic_update_slice(r, head, (0,) * r.ndim)
    return jax.tree.map(rs, row_cache, store)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("totals", "count"))
def _insert_row(batch_cache, row_cache, slot, totals=None, count=True):
    """Copy a prefilled batch-of-one cache into batch row ``slot``.
    Scalar leaves (the shared cache_index / pos_index counters) are
    untouched — per-row mode never reads them. ``totals`` is the path
    of the one leaf a model declares as running totals shared by every
    slot (its ``device_counter_leaf``): what the prefill counted joins
    what the batch has, once (``count`` is False for the further
    branch rows of one prefill)."""
    def ins(path, b, r):
        if b.ndim == 0:
            return b
        if tuple(getattr(k, "key", k) for k in path) == totals:
            return b + r if count else b
        return jax.lax.dynamic_update_slice(
            b, r.astype(b.dtype), (slot,) + (0,) * (b.ndim - 1))
    return jax.tree_util.tree_map_with_path(ins, batch_cache, row_cache)


# init_cache retraces model.init (pure Python, ~100ms even for tiny
# models) and mints each leaf with an eager jnp.zeros: ~85 host
# dispatches for a 17-layer model, per admission, while every slot
# waits. Under jit the retrace runs once per (model, batch, max_len),
# the zeros are broadcasts inside ONE program, and each call is one
# dispatch that returns fresh buffers (they are donated to the prefill
# jit, so a cached array must never be handed out twice). The jit cache
# pins the model the same way the prefill jits do.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _zero_cache(model, batch, max_len):
    return init_cache(model, batch, max_len)


def _fresh_cache(model, batch: int, max_len: int):
    return _zero_cache(model, batch, max_len)


def _bucket_len(n: int, floor: int = 16) -> int:
    """Round a prompt length up to a power of two (>= ``floor``): the
    prefill/insert jit cache then holds O(log max_seq_len) programs
    instead of one per distinct prompt length."""
    b = floor
    while b < n:
        b *= 2
    return b


class _Slot:
    """Host-side mirror of one batch row (= one decode branch)."""

    __slots__ = ("req", "emitted", "tokens", "depth", "cached",
                 "seq_id", "branch", "step0", "streamed", "first_round",
                 "rounds")

    def __init__(self, req: Request, first_token: Optional[int], depth: int,
                 first_round: int, cached: int = 0, seq_id: str = "",
                 branch: int = 0, rounds: int = 0):
        self.req = req
        # the first decode round (by the engine's count of dispatches)
        # that holds this row: an earlier round, still unfetched when
        # the row was admitted, has the slot's last occupant's token
        self.first_round = first_round
        # a block decoder's row starts with no token (its prefill yields
        # none) and knows the rounds it needs at most: ``rounds``
        self.tokens = [] if first_token is None else [int(first_token)]
        self.emitted = len(self.tokens)
        self.rounds = rounds
        self.depth = depth  # cache rows filled (prompt + emitted - 1)
        self.cached = cached  # prompt tokens restored from prefix cache
        # Prism: which pool sequence this row extends (== request_id
        # for branch 0 / unbranched requests), the branch's RNG lane,
        # and the sampling step this leg started at
        self.seq_id = seq_id or req.request_id
        self.branch = branch
        self.step0 = req.decode_step0
        self.streamed = 0  # tokens already pushed to req.stream


class ServingEngine:
    """Continuous-batching engine over one model + params."""

    def __init__(self, model, params, *, max_slots: int = 4,
                 max_seq_len: int = 256, block_size: int = 16,
                 max_queue: int = 64, max_prefills_per_round: int = 2,
                 eos_token: Optional[int] = None, metrics=None,
                 tag: str = "", prefix_cache: bool = True,
                 lora_bank=None, tenant_quotas=None,
                 stream_chunk_tokens: int = 1) -> None:
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.model = model
        self.params = params
        # owner label (fleet replica name): rides every serve_request
        # record so per-replica occupancy survives into the JSONL
        self.tag = tag
        self.max_slots = max_slots
        self.max_seq_len = int(max_seq_len)
        self.eos_token = eos_token
        self.metrics = metrics  # MetricsLogger or None
        # Causeway: give an armed tracer the JSONL sink (no-op when
        # TPUNN_TRACE is unset — zero writes, lint contract)
        trace.attach_metrics(metrics)
        # Abacus: same contract for an armed meter (TPUNN_METER)
        meter.attach_metrics(metrics)
        # Lighthouse: same contract for an armed audit (TPUNN_AUDIT)
        audit.attach_metrics(metrics)
        # fleet replica index (stamped by the fleet supervisor): the
        # chaos flip@replica=K drill keys on it; standalone engines
        # keep 0
        self.replica_index = 0
        # analytic FLOPs per token (utils/flops.py XLA count at batch
        # 1, seq 1): computed lazily on first metered billing, never
        # when the meter is unarmed; 0 = no cost model reachable
        self._flops_per_token: Optional[int] = None
        # per-request LoRA: stacked (n, L, ...) factor bank
        # (nn/lora.py); requests pick an adapter at submit and each
        # batch row applies its own deltas in the shared forward
        self.lora_bank = lora_bank
        # a block decoder (``block_decoding()``): a round rewrites a
        # block of positions a row and hands out none or up to a block
        # of tokens (:func:`_block_round`). Under its mask by blocks a
        # prefix-cache page's rows depend on nothing after the page
        # only if pages are whole blocks
        self._block = _block_of(model)
        # what a block decoder's serve/decode span says beside the rest
        self._span_block = {}
        if self._block is not None:
            B = self._block["block_length"]
            self._span_block = {"block": B}
            if block_size % B or self.max_seq_len % B:
                raise ValueError(
                    f"{type(model).__name__} decodes blocks of {B} "
                    f"positions under a mask by blocks: block_size "
                    f"{block_size} and max_seq_len {self.max_seq_len} "
                    f"must be multiples of it (a prefix-cache page has "
                    f"to hold whole blocks, a row's last block to fit)")
        pool = KVPool(
            num_blocks=max_slots * (-(-self.max_seq_len // block_size)),
            block_size=block_size,
        )
        self._cache = _fresh_cache(model, max_slots, self.max_seq_len)
        # a model may declare cache leaves that are not rows by absolute
        # position (``leaves_not_by_position()``: what they are, and
        # their paths): a sliding window's *ring* ``(slots, window,
        # ...)``, row = position mod window, or a recurrence's *state*
        # ``(slots, ...)``, one value a sequence whatever its length.
        # Such a model gets no prefix cache and no block store. A hit
        # of n rows needs a window layer's rows [n - window, n), which a
        # retiring sequence no longer holds except at its very end, and
        # a recurrence's state as it stood after exactly n tokens, which
        # a sequence holds only while it is there; a store page for
        # every block of every layer would cost what the ring or the
        # state saved. ``_save_blocks`` / ``_restore_blocks`` slice
        # every leaf at j * block_size and must never see either.
        # ``_insert_row`` copies such a leaf like any other: the insert
        # overwrites all of the slot's.
        kinds = getattr(model, "leaves_not_by_position", dict)()
        self._not_by_position = "; ".join(
            f"{len(paths)} {kind}" for kind, paths in kinds.items() if paths)
        if prefix_cache and self._not_by_position:
            log.info("%s declares cache leaves that are not rows by "
                     "position (%s): no prefix cache and no block store; "
                     "every admission prefills from position 0",
                     type(model).__name__, self._not_by_position)
            prefix_cache = False
        # what the batch cache holds, by how its leaves are addressed
        # (the slots' counters and indices, of one axis or none, aside)
        g_cache = obs.get_registry().gauge(
            "serve_cache_bytes", "bytes of the batch cache in leaves that "
            "are rows by absolute position, and in leaves that are not "
            "(rings, recurrent state)", labels=("leaves",))
        declared = {p for paths in kinds.values() for p in paths}
        held = {"by_position": 0, "not_by_position": 0}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._cache)[0]:
            if leaf.ndim >= 2:
                name = tuple(getattr(k, "key", k) for k in path)
                held["not_by_position" if name in declared
                     else "by_position"] += leaf.nbytes
        for leaves, n in held.items():
            g_cache.set(n, leaves=leaves)
        if prefix_cache:
            self.prefix_cache: Optional[PrefixCache] = PrefixCache(
                pool, max_rows=self.max_seq_len, tag=tag)
            # device block store: retired sequences donate their KV
            # blocks here; admissions with a radix match restore from
            # here. Scalar leaves are fresh zeros (NEVER aliased into
            # self._cache — the decode jit donates the cache every
            # round, and an aliased leaf would be invalidated with it).
            self._store = jax.tree.map(
                lambda x: (jnp.zeros_like(x) if x.ndim < 2 else
                           jnp.zeros((pool.num_blocks, block_size)
                                     + x.shape[2:], x.dtype)),
                self._cache)
            # fixed save/restore table width: one compiled program
            # serves every sequence, whatever its block count
            self._blocks_per_seq = -(-self.max_seq_len // block_size)
        else:
            self.prefix_cache = None
            self._store = None
        self.scheduler = Scheduler(
            pool, max_queue=max_queue, max_seq_len=self.max_seq_len,
            max_prefills_per_round=max_prefills_per_round,
            tenant_quotas=tenant_quotas,
            prefix_cache=self.prefix_cache,
        )
        self.scheduler.metrics = metrics
        self._slots: list[Optional[_Slot]] = [None] * max_slots
        # slot state (last token, cache depth, active, tokens left to
        # emit): the device's. The step advances it and stops a row;
        # the host writes a row only when it fills one (_write_slots)
        # and otherwise follows one round behind, in the _Slot mirrors
        self._d_slots = (jnp.zeros((max_slots,), jnp.int32),
                         jnp.zeros((max_slots,), jnp.int32),
                         jnp.zeros((max_slots,), bool),
                         jnp.zeros((max_slots,), jnp.int32))
        if self._block is not None:
            # a block's state where a row's last token and depth stand
            # (_block_round says what each holds)
            self._d_slots = (
                *_idle_block_state(max_slots, self._block["block_length"]),
                *self._d_slots[2:])
        self._d_eos = jnp.asarray(
            -1 if eos_token is None else eos_token, jnp.int32)
        # decode rounds dispatched and not yet fetched, oldest first:
        # (tokens, time of dispatch, the slot and sampling state the
        # round was given). One in steady state, two for a moment
        # inside _decode_round, none when the batch is empty.
        self._flight: collections.deque = collections.deque()
        self._round_no = 0  # the newest dispatched round's number
        self._t_fetched = 0.0  # when the last fetch returned
        # when _decode_round began to wait for the chip: what came
        # before is the round's dispatch, what follows its fetch
        self._t_fetch = 0.0
        # of the admissions since the last round: the wait for a
        # prefill's first token, and the thread's seconds on a core
        # (the round's record carries both: an admission that is long
        # and is neither waited for the chip, for a lock or for a core)
        self._first_token_wait = 0.0
        self._admit_cpu = 0.0
        # the loop's own account of its thread, from its first round
        # (or InferenceServer.start) on, on the clock the engine's
        # other stamps use
        jitwatch.install()
        self.loop = goodput.GoodputMeter(
            goodput.SERVE_PHASES, goodput.SERVE_SPANS, cat="app",
            clock=time.monotonic, rounds=True, idle="parked",
            counter=obs.get_registry().counter(
                "serve_loop_seconds_total",
                "seconds of the serve loop's thread by phase "
                "(exclusive; they sum to its wall time)",
                labels=("phase",)))
        # the decode program, asked for once a variant: when the tally's
        # dispatch phase traced (obs/scopes.py)
        self.loop.programs["dispatch"] = self._step_program
        self._overlapped = 0  # rounds dispatched over an unfetched one
        # the programs' ``lora`` argument: the bank and each slot's
        # adapter id (written with the slot state above), or None, and
        # then no program takes it and no adapter ids are kept or pushed
        self._lora = None if lora_bank is None else dict(
            lora_bank=lora_bank,
            adapter_ids=jnp.zeros((max_slots,), jnp.int32))
        # the step's ``sampling`` argument (serve/decoding.py), one row
        # a slot: the host's mirror, and the device's copy, written at
        # an admission while a sampled row is live and handed to the
        # step only then. Traced arrays, so every mix of greedy and
        # sampled rows runs one program; steps and running logprobs
        # advance on the device inside it.
        self._h_sampling = {
            name: np.zeros((max_slots,), dtype)
            for name, dtype in _SAMPLING_ROW.items()}
        self._d_sampling = jax.tree.map(jnp.asarray, self._h_sampling)
        # first-token logprobs of the rows this admission pass
        # prefilled (slot -> value): they are on the host, the older
        # rows' sums on the device
        self._pending_logprob: dict[int, float] = {}
        self._n_sampled = 0  # active rows of sampled requests
        # best-of-n bookkeeping: request_id -> {branch: (tokens, logprob)}
        self._branch_done: dict[str, dict[int, tuple]] = {}
        # incremental streaming: tokens per chunk (1 = every token is
        # a chunk). Chunking never changes the retired fingerprint —
        # the Lighthouse fold runs over the full token list at retire.
        self.stream_chunk_tokens = max(int(stream_chunk_tokens), 1)
        # bench/report feed: per-round wall seconds + finished requests
        self.round_seconds: list[float] = []
        self.completed: list[dict] = []
        self._occ_sum = 0  # sum of per-round active-slot counts
        self._tokens_emitted = 0  # prefills' first tokens and rounds' tokens
        reg = obs.get_registry()
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", "submit -> first token",
            buckets=_TTFT_BUCKETS)
        # per-tenant twin of serve_ttft_seconds — the base histogram
        # stays UNLABELED (its series is the global SLO feed; labeling
        # it would break every existing snapshot() caller)
        self._h_ttft_tenant = reg.histogram(
            "serve_tenant_ttft_seconds",
            "submit -> first token, per tenant",
            labels=("tenant",), buckets=_TTFT_BUCKETS)
        self._h_tok = reg.histogram(
            "serve_token_latency_seconds", "decode round wall time "
            "(= per-token latency of every active stream)",
            buckets=_TOKEN_BUCKETS)
        self._g_occ = reg.gauge(
            "serve_batch_occupancy", "active decode slots")
        self._c_tokens = reg.counter(
            "serve_tokens_total", "tokens emitted by the engine")
        self._c_stream_chunks = reg.counter(
            "serve_stream_chunks_total",
            "token chunks pushed to streaming clients")
        # over serve_token_latency_seconds' count (every decode round)
        # this is the share of rounds the chip did not wait for
        self._c_overlapped = reg.counter(
            "serve_rounds_overlapped_total",
            "decode rounds dispatched before the previous round's "
            "tokens were fetched")
        # over the device time of jit__save_blocks / jit__restore_blocks
        # in a trace this is microseconds a block
        self._c_blocks_copied = reg.counter(
            "serve_store_blocks_copied_total",
            "blocks copied between a batch row and the block store: "
            "save at a retire, restore at an admission with a prefix hit",
            labels=("direction",))
        # a model's device-side counters (obs/device_counters.py): the
        # model names the cache leaf that holds them and its entries;
        # read every _COUNTER_ROUNDS rounds from the batch cache
        self._counter_leaf = getattr(model, "device_counter_leaf", None)
        self._device_counters = obs.DeviceCounters(
            model.device_counter_names()) \
            if self._counter_leaf is not None else None

    # -- client surface ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw) -> Request:
        adapter = int(kw.get("adapter", 0))
        if self.lora_bank is not None:
            n = num_adapters(self.lora_bank)
            if not 0 <= adapter < n:
                raise ValueError(
                    f"adapter {adapter} out of range for a LoRA bank "
                    f"of {n} adapters")
        elif adapter != 0:
            raise ValueError(
                f"adapter {adapter} requested but the engine has no "
                f"LoRA bank (pass lora_bank= to ServingEngine)")
        spec = kw.get("decode")
        if self._block is not None and getattr(spec, "branches", 1) > 1:
            raise ValueError(
                f"n > 1 branches: {type(self.model).__name__} decodes by "
                f"blocks, and a prefill that yields no logits has none "
                f"to fan into branches (each branch would need its own "
                f"first block)")
        if spec is not None \
                and getattr(spec, "branches", 1) > self.max_slots:
            raise ValueError(
                f"best_of={getattr(spec, 'branches', 1)} branches can "
                f"never fit a {self.max_slots}-slot engine")
        return self.scheduler.submit(prompt, max_new_tokens, **kw)

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_work(self) -> bool:
        """Rows to decode, requests queued, or a dispatched round whose
        tokens nobody has fetched yet: a driver that parks only when
        this is false leaves nothing in flight."""
        return self.active_slots > 0 or self.scheduler.queue_depth > 0 \
            or bool(self._flight)

    # -- engine loop pieces (one driving thread) ---------------------------

    def step(self) -> bool:
        """One scheduler round: admit + prefill into free slots,
        dispatch the next decode round and fetch the one before it,
        retire the rows that one finished. Returns False when there
        was nothing to do (caller may sleep/park)."""
        sched = self.scheduler
        sched.round += 1
        loop = self.loop
        if not loop.running:
            loop.start()
        with obs.span("serve/round", round=sched.round) as rnd:
            # chaos tenant_flood: synthetic burst traffic lands through the
            # REAL submit path (quota checks, DRR queues, reject counters)
            for tenant, owed in chaos.on_tenant_flood():
                for _ in range(owed):
                    self.submit(np.asarray([3, 5, 7], np.int32), 2,
                                tenant=tenant)
            changed = self._admit()
            if self.active_slots == 0 and not self._flight:
                self._g_occ.set(0)
                rnd.set(occ=0)
                return changed
            with loop.phase("dispatch") as dec:
                host_tok, dt = self._decode_round()
                dec.set(dispatch_us=dec.split("fetch", self._t_fetch),
                        **self._span_block)
            with loop.phase("round_host") as host_span:
                self.round_seconds.append(dt)
                self._h_tok.observe(dt)
                if self._flight:
                    # the call left a round in flight: it dispatched it
                    # while the round it fetched was still unfetched
                    self._overlapped += 1
                    self._c_overlapped.inc()
                # the rows of the fetched round: a row admitted since
                # its dispatch is in the round in flight, not in this
                fetched = self._round_no - len(self._flight)
                live = [(i, s) for i, s in enumerate(self._slots)
                        if s is not None and s.first_round <= fetched]
                occ = len(live)
                self._g_occ.set(occ)
                self._occ_sum += occ
                flight.record("serve", "decode_round", step=sched.round,
                              note=f"occ={occ}/{self.max_slots}")
                # watchtower feed (token-latency SLO + queue/KV pressure):
                # here, NOT in _decode_round — its hot-loop lint bans extras
                watchtower.on_serve_round(
                    sched.round, dt, queue_depth=sched.queue_depth,
                    queue_max=sched.max_queue,
                    kv_free=sched.pool.free_blocks,
                    kv_total=sched.pool.num_blocks)
                # helm feed (instantaneous queue/KV between control ticks);
                # inert one-comparison no-op unless TPUNN_AUTOSCALE armed it
                autoscale.on_serve_round(
                    sched.round, dt, queue_depth=sched.queue_depth,
                    queue_max=sched.max_queue,
                    kv_free=sched.pool.free_blocks,
                    kv_total=sched.pool.num_blocks)
                # xray capture clock (serving-side): rounds advance an active
                # capture window / interval trigger, same placement rule
                xray.on_serve_round(sched.round)
                # Abacus decode billing: one token per active slot this round,
                # split by tenant — here, NOT in _decode_round (hot-loop lint).
                # enabled() gate so the slot scan + FLOPs lookup never run on
                # an unarmed process (the armed-vs-unset A/B contract)
                if meter.enabled():
                    # Lighthouse shadow/probe legs are audit duplicates, not
                    # customer traffic — their decode rounds are never billed
                    meter.on_decode_round(
                        [s.req.tenant for _, s in live
                         if s.req.tenant != audit.SHADOW_TENANT],
                        self.flops_per_token())
                retired, emitted = self._collect(host_tok, live)
                self._count_tokens(emitted)
                if sched.round % _COUNTER_ROUNDS == 0:
                    self.publish_device_counters()
                    loop.publish()
                host_span.set(retired=retired)
            wait, self._first_token_wait = self._first_token_wait, 0.0
            if wait:
                cpu, self._admit_cpu = self._admit_cpu, 0.0
                loop.lap(sched.round, occ=occ, emitted=emitted,
                         first_token_wait_s=wait, admit_cpu_s=cpu)
            else:
                loop.lap(sched.round, occ=occ, emitted=emitted)
            rnd.set(occ=self.active_slots)
            return True

    def publish_device_counters(self) -> None:
        """Fetch the model's running totals from the batch cache and
        add what is new to the registry. The newest cache is the round
        in flight's: the fetch waits for it, once in
        ``_COUNTER_ROUNDS`` rounds."""
        if self._device_counters is not None:
            leaf = self._cache
            for key in self._counter_leaf:
                leaf = leaf[key]
            self._device_counters.publish(np.asarray(leaf))

    def run_until_idle(self) -> None:
        """Drive rounds until queue and batch are both empty."""
        while self.has_work:
            self.step()

    def drain(self) -> int:
        """Graceful shutdown: reject everything queued, finish every
        in-flight sequence, leave the batch empty. Returns the number
        of requests that were still queued (now rejected)."""
        rejected = self.scheduler.drain()
        while self.active_slots > 0 or self._flight:
            self.step()
        flight.record("serve", "drained",
                      note=f"rejected_queued={rejected}")
        return rejected

    # -- internals ---------------------------------------------------------

    def _admit(self) -> bool:
        """Pull scheduler admissions into free slots and prefill them."""
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return False
        sched = self.scheduler
        with self.loop.phase("next_admissions") as nxt:
            admitted = sched.next_admissions(len(free))
            nxt.set(queued=sched.last_queued, admitted=len(admitted),
                    lock_wait_us=sched.last_lock_wait_us)
        if not admitted:
            return False
        with self.loop.phase("admit", n=len(admitted)):
            # once a pass, not a round: this clock costs 5 us on the
            # chip's host, where it ticks every 10 ms
            cpu0 = time.thread_time()
            filled = []
            for req in admitted:
                # a branched request claims one row per branch (the
                # scheduler already counted them against free_slots)
                slots = [free.pop(0) for _ in range(req.branches)]
                self._prefill_into(slots, req)
                filled += slots
            # a budget-1 (or instant-eos) request retires in the same
            # pass
            self._retire_finished()
            self._write_slots(filled)
            self._pending_logprob.clear()
            self._admit_cpu += time.thread_time() - cpu0
        return True

    def _row_lora(self, adapter: int):
        """The prefill's ``lora`` argument: the bank and the one row's
        adapter id."""
        return None if self._lora is None else dict(
            self._lora, adapter_ids=jnp.asarray([adapter], jnp.int32))

    def warmup(self, prompt_lens=(8,)) -> None:
        """Compile what this engine runs for greedy requests (with its
        bank, if it has one) before a driving thread does: the zeroed
        row cache and the prefill of each prompt bucket, the row insert,
        an admission's write of its rows' slot state, the decode step.
        One throwaway forward a bucket into a throwaway batch cache: the
        engine's own state is not touched, so a replica may be warmed
        while its driver loop idles."""
        # (a call that traces here is noted for obs/scopes.py as the
        # loop's own would be: the loop will find the executable)
        noting = jitwatch.noting
        cache = _fresh_cache(self.model, self.max_slots, self.max_seq_len)
        if self._block is not None:
            # what a block decoder prefills of a prompt: its whole blocks
            B = self._block["block_length"]
            prompt_lens = [int(p) // B * B for p in prompt_lens
                           if int(p) >= B]
        for plen in prompt_lens:
            pad = min(_bucket_len(int(plen)), self.max_seq_len)
            args = (self.model, self.params,
                    _fresh_cache(self.model, 1, pad),
                    jnp.zeros((1, pad), jnp.int32),
                    jnp.asarray([int(plen)], jnp.int32),
                    jnp.zeros((1,), jnp.int32), self._row_lora(0), None)
            with noting((_serve_prefill, args)):
                _, row, _ = _serve_prefill(*args)
            # static arguments as _prefill_into passes them: a default left
            # out is another program to jax.jit
            kw = dict(totals=self._counter_leaf, count=True)
            with noting((_insert_row, (cache, row, 0), kw)):
                cache = _insert_row(cache, row, 0, **kw)
        idle = jnp.zeros((self.max_slots,), jnp.int32)
        ids = None if self._lora is None else self._lora["adapter_ids"]
        rows = np.zeros((5, self.max_slots), np.int32)
        # every argument, as _write_slots passes them
        if self._block is not None:
            B = self._block["block_length"]
            write, args = _write_block_rows, (
                *_idle_block_state(self.max_slots, B),
                jnp.zeros((self.max_slots,), bool), idle, rows,
                np.zeros((self.max_slots, B), np.int32), ids, None, None)
        else:
            write, args = _write_rows, (
                idle, idle, jnp.zeros((self.max_slots,), bool), idle,
                rows, ids, None, None)
        with noting((write, args)):
            state, _, _ = write(*args)
        args = (self.model, self.params, cache, *state, self._d_eos,
                self._lora, None)
        with noting((_serve_step, args)):
            nxt, *_ = _serve_step(*args)
        np.asarray(nxt)  # block until compiled + executed

    def _step_program(self):
        """``_serve_step`` with this engine's arguments as the round
        just dispatched left them (the program's outputs have its
        inputs' shapes), for :func:`obs.scopes.note`."""
        return _serve_step, (
            self.model, self.params, self._cache, *self._d_slots,
            self._d_eos, self._lora,
            self._d_sampling if self._n_sampled else None)

    def _prefill_into(self, slots: list, req: Request) -> None:
        """Prefill ONE request into ``len(slots)`` batch rows. The
        prompt forward runs once; branched requests fan the resulting
        row cache into every branch row (``_insert_row`` donates only
        the batch cache, so one prefilled row inserts n times) and
        draw each branch's first token from the same prompt logits
        under its own RNG lane."""
        L = len(req.prompt)
        spec = req.decode
        sampled = spec is not None and spec.sampled
        match = req.prefix_match
        m = match.tokens if match is not None else 0
        bs = self.scheduler.pool.block_size
        blk = self._block
        end = L  # the prompt positions the prefill fills
        if blk is not None:
            # a block decoder prefills the prompt's whole blocks (its
            # tail opens the first block of the rounds) and resumes a
            # restored prefix at a whole block: the rows of a block cut
            # short were computed with what followed it there
            B = blk["block_length"]
            end = L // B * B
            m = min(m // B * B, end)
        suffix = np.asarray(req.prompt[m:end], np.int32)
        # >= 1 (PrefixCache caps matches at L - 1) but for a block
        # decoder whose whole blocks are all restored, or that has none
        T = len(suffix)
        t_pad = min(_bucket_len(T), self.max_seq_len - m) if T else 0
        # row-cache length must hold BOTH the restored blocks and the
        # suffix writes: a dynamic_update_slice whose start exceeds the
        # buffer silently clamps (corrupting neighbor rows), so pad is
        # sized to max(restored top, m + suffix pad), never less
        restore_top = len(match.restore_blocks) * bs \
            if match is not None else 0
        pad = min(_bucket_len(max(m + t_pad, restore_top)),
                  self.max_seq_len)
        with obs.span("serve/prefill_into", request=req.request_id,
                      tokens=T, padded=t_pad, cached=m, row_len=pad):
            tokens = np.zeros((1, t_pad), np.int32)
            tokens[0, :T] = suffix  # left-ALIGNED (pad tail is masked)
            row_cache = None  # nothing to fill and nothing restored
            if T or m:
                with jitwatch.dispatch_span(
                        "serve/fresh_cache",
                        program=(_zero_cache, (self.model, 1, pad))):
                    row_cache = _fresh_cache(self.model, 1, pad)
            if m > 0:
                nb = len(match.restore_blocks)
                table = np.zeros((self._blocks_per_seq,), np.int32)
                table[:nb] = match.restore_blocks
                t_restore = time.monotonic()
                args = (row_cache, self._store, bs, table, np.int32(nb))
                with jitwatch.dispatch_span(
                        "serve/restore", program=(_restore_blocks, args),
                        blocks=nb):
                    row_cache = _restore_blocks(*args)
                self._c_blocks_copied.inc(nb, direction="restore")
                trace.on_segment(req.trace, "restore", t_restore,
                                 time.monotonic(), blocks=nb, cached=m)
            # the request's rows of the sampling mirror: its spec, one
            # RNG lane a branch, all at the leg's first step. Slots are
            # reused: a greedy row landing on a retired sampled row must
            # read temperature 0 (the step's per-row greedy branch)
            h = self._h_sampling
            h["temp"][slots] = spec.temperature if sampled else 0.0
            h["top_k"][slots] = spec.top_k if sampled else 0
            h["top_p"][slots] = spec.top_p if sampled else 0.0
            h["seed"][slots] = spec.seed if sampled else 0
            h["branch"][slots] = np.arange(len(slots))
            h["step"][slots] = req.decode_step0
            h["logprob"][slots] = 0.0
            rows = {k: v[slots] for k, v in h.items()} if sampled else None
            firsts = [None] * len(slots)
            args = (self.model, self.params, row_cache,
                    jnp.asarray(tokens), jnp.asarray([T], jnp.int32),
                    jnp.asarray([m], jnp.int32),
                    self._row_lora(req.adapter), rows) if T else None
            with jitwatch.dispatch_span(
                    "serve/prefill",
                    program=(_serve_prefill, args) if T else None,
                    request=req.request_id, prompt_len=L, cached=m):
                if T:
                    tok0, row_cache, drawn = _serve_prefill(*args)
                if blk is None:
                    t_wait = time.monotonic()
                    firsts = [int(t) for t in np.asarray(tok0)]
                    self._first_token_wait += time.monotonic() - t_wait
                    if sampled:
                        h["logprob"][slots] = np.asarray(drawn["logprob"])
            if match is not None:
                # restored rows are copied out; the COW tail pin can drop
                self.prefix_cache.finish_restore(match)
                req.prefix_match = None
            req.t_prefilled = time.monotonic()
            if blk is None:
                self._first_token(req, req.t_prefilled)
            else:
                rounds = _block_rounds(L, req.max_new_tokens, B,
                                       blk["denoising_steps"])
            sids = branch_seq_ids(req)
            totals = self._counter_leaf
            with jitwatch.dispatch_span(
                    "serve/insert_row",
                    program=None if row_cache is None else (
                        _insert_row, (self._cache, row_cache, slots[0]),
                        dict(totals=totals, count=True)),
                    rows=len(slots)):
                for k, slot in enumerate(slots):
                    # one program for every row of a model without totals
                    if row_cache is not None:
                        self._cache = _insert_row(
                            self._cache, row_cache, slot, totals=totals,
                            count=k == 0 or totals is None)
                    # (a block decoder's row: no token yet, ``depth``
                    # the same count of the host's, prompt + emitted - 1)
                    s = _Slot(req, firsts[k], depth=L if blk is None
                              else L - 1, first_round=self._round_no + 1,
                              cached=m, seq_id=sids[k], branch=k,
                              rounds=0 if blk is None else rounds)
                    self._slots[slot] = s
                    self._n_sampled += sampled
                    self._pending_logprob[slot] = float(h["logprob"][slot])
                    # the prefill-produced first token
                    self._count_tokens(s.emitted)
                    flight.record("serve", "admit", step=self.scheduler.round,
                                  note=f"{sids[k]} slot={slot} L={L} "
                                       f"cached={m}")
                    if k == 0:
                        # first chunk = the client-visible TTFT event (no-op
                        # for non-streaming requests)
                        self._emit_chunk(s)
            # Abacus prefill billing: the suffix actually computed, plus
            # the cached-prefix FLOPs the restore SKIPPED as a credit
            # (audit shadow/probe legs are never billed)
            if meter.enabled() and req.tenant != audit.SHADOW_TENANT:
                meter.on_prefill(req.request_id, req.tenant,
                                 new_tokens=T, cached_tokens=m,
                                 flops_per_token=self.flops_per_token())

    def _count_tokens(self, n: int) -> None:
        """``n`` tokens given: a prefill's first, a round's."""
        self._c_tokens.inc(n)
        self._tokens_emitted += n

    def _first_token(self, req: Request, now: float) -> None:
        """Stamp a request's first token (a block decoder's: its first
        block's last step) and observe its TTFT. TTFT is charged from the
        logical request's ORIGINAL arrival (t_origin: set by the fleet
        on resubmitted legs), and only when THIS leg delivers the first
        token — a disagg decode leg or a post-first-token failover
        re-admission arrives with t_first_origin already set and must
        not observe again (the capacity sim's accounting, now pinned
        for the live fleet too)."""
        req.t_first_token = now
        if req.t_first_origin == 0.0:
            ttft = now - (req.t_origin or req.t_submit)
            self._h_ttft.observe(ttft)
            self._h_ttft_tenant.observe(ttft, tenant=req.tenant)

    def _decode_round(self):
        """THE hot loop body (see module docstring for the lint
        contract: no host->device transfers, no jnp/jax array
        construction — device state stays resident; one (slots,)
        device->host fetch). Dispatches until one round is in flight
        beyond the oldest, then fetches the oldest: ``(its tokens, its
        seconds)``, the seconds from the later of its dispatch and the
        previous fetch's return to this fetch's return. ``_t_fetch`` is
        when the waiting began: ``step()`` splits the call there into
        the host's dispatch and the wait for the chip."""
        t0 = time.monotonic()
        # chaos slow@/crash@/preempt@ key on the decode round the way
        # they key on the training step; inside the timed window so an
        # injected slow round shows up in the latency histograms
        # exactly like a real one
        chaos.on_step(self.scheduler.round)
        unfetched = self._flight  # (``flight`` is the obs module here)
        while not unfetched or (len(unfetched) == 1
                                and self._rows_outlast_flight()):
            given = (self._d_slots, self._d_sampling)
            nxt, depth, active, remaining, self._cache, drawn = _serve_step(
                self.model, self.params, self._cache, *self._d_slots,
                self._d_eos, self._lora,
                self._d_sampling if self._n_sampled else None)
            if drawn is not None:
                self._d_sampling = drawn
            self._d_slots = (nxt, depth, active, remaining)
            self._round_no += 1
            unfetched.append((nxt, t0, given))
        nxt, dispatched, _ = unfetched.popleft()
        self._t_fetch = time.monotonic()
        host_tok = np.asarray(nxt)
        now = time.monotonic()
        dt = now - max(dispatched, self._t_fetched)
        self._t_fetched = now
        return host_tok, dt

    def _rows_outlast_flight(self) -> bool:
        """Whether some row's budget reaches past the rounds in flight
        (an eos the host has not seen yet may still stop it: the round
        dispatched for it then computes nothing and is fetched like any
        other). A row admitted after a round's dispatch is not in it."""
        if self._block is not None:
            # a block decoder's row counts rounds, not tokens: blocks
            # left times the steps a block takes (at most:
            # ``low_confidence_dynamic`` may take fewer)
            return any(s is not None
                       and self._round_no - (s.first_round - 1) < s.rounds
                       for s in self._slots)
        fetched = self._round_no - len(self._flight)
        return any(
            s is not None and s.emitted + self._round_no
            - max(fetched, s.first_round - 1) < s.req.max_new_tokens
            for s in self._slots)

    def _collect_blocks(self, host_out: np.ndarray, live: list) -> tuple:
        """:meth:`_collect` for a block decoder: ``host_out`` (slots,
        B + 1) holds, for a row whose block the round's step left
        whole, the tokens handed out and, last, how many (none for a
        row whose block still has a position masked)."""
        if chaos.on_flip_token(self.replica_index, self.scheduler.round):
            raise RuntimeError(
                f"chaos flip@: {type(self.model).__name__} decodes by "
                f"blocks; the drill rewrites one fetched token on the "
                f"device, and a block's tokens there are not the fetched "
                f"ones (a flip would have to rewrite a committed row's "
                f"keys and values)")
        emitted = 0
        for i, s in live:
            n = int(host_out[i, -1])
            if not n:
                continue
            if not s.emitted:
                self._first_token(s.req, time.monotonic())
            s.tokens.extend(int(t) for t in host_out[i, :n])
            s.emitted += n
            s.depth += n
            emitted += n
            self.scheduler.pool.extend(s.seq_id, s.depth)
            if s.req.stream is not None and \
                    len(s.tokens) - s.streamed >= self.stream_chunk_tokens:
                self._emit_chunk(s)
        return self._retire_finished(), emitted

    def _collect(self, host_tok: np.ndarray, live: list) -> tuple:
        """Fold one round's tokens into the host mirrors of its rows
        (``live``: slot and mirror of every row the round held) and
        retire rows that hit eos or budget, which the device stopped in
        that round. Returns the retired count and the tokens the round
        gave."""
        if self._block is not None:
            return self._collect_blocks(host_tok, live)
        # chaos flip@replica=K: perturb ONE fetched token (first active
        # slot) this round — a silent corruption: the wrong id flows
        # into the slot mirror, the JSONL record, and the fingerprint
        # chain exactly as flaky HBM would ship it. Host-side, outside
        # _decode_round (its hot-loop lint bans extras).
        flip = chaos.on_flip_token(self.replica_index,
                                   self.scheduler.round)
        flipped = None
        for i, s in live:
            tok = int(host_tok[i])
            if flip:
                flip = False
                flipped = i
                tok = tok - 1 if tok > 0 else tok + 1
            s.tokens.append(tok)
            s.emitted += 1
            s.depth += 1
            self.scheduler.pool.extend(s.seq_id, s.depth)
            if s.req.stream is not None and \
                    len(s.tokens) - s.streamed >= self.stream_chunk_tokens:
                self._emit_chunk(s)
        retired = self._retire_finished()
        if flipped is not None:
            # write the corrupted token to the device so the flip
            # PROPAGATES: subsequent tokens condition on the wrong id,
            # exactly like real rot. The round in flight was fed the
            # true one: it is dropped (what it wrote to the cache the
            # round dispatched in its place writes again), and the
            # device's state is what that round was given.
            while self._flight:
                _, _, (self._d_slots, self._d_sampling) = self._flight.pop()
                self._round_no -= 1
            if self._n_sampled:
                # the row's running logprob is the device's
                self._h_sampling["logprob"][flipped] = np.asarray(
                    self._d_sampling["logprob"])[flipped]
            self._write_slots([flipped])
        return retired, len(live)

    def _done(self, s: _Slot) -> bool:
        if s.emitted >= s.req.max_new_tokens:
            return True
        return self.eos_token is not None and bool(s.tokens) and \
            s.tokens[-1] == self.eos_token

    def _retire_finished(self) -> int:
        retired = 0
        with obs.span("serve/retire") as sp:
            for i, s in enumerate(self._slots):
                if s is None or not self._done(s):
                    continue
                self._slots[i] = None
                retired += 1
                req = s.req
                self._n_sampled -= req.decode is not None \
                    and req.decode.sampled
                if req.branches > 1:
                    self._retire_branch(i, s)
                    continue
                if self.prefix_cache is not None:
                    # donate BEFORE retire: release() indexes the physical
                    # blocks into the radix, so their bytes must already be
                    # in the store when another admission can match them
                    self._donate_blocks(i, s)
                # final flush BEFORE retire: the closing chunk must be in
                # the stream when done.set() wakes the client
                self._emit_chunk(s, final=True)
                with obs.span("serve/release", blocks=s.depth
                              // self.scheduler.pool.block_size):
                    self.scheduler.retire(
                        req, np.asarray(s.tokens, np.int32))
                flight.record("serve", "retire", step=self.scheduler.round,
                              note=f"{req.request_id} tokens={s.emitted}")
                self._finish_record(req, s)
            sp.set(n=retired)
        return retired

    def _retire_branch(self, slot: int, s: _Slot) -> None:
        """Retire ONE branch of a best-of-n request: bank its tokens +
        device-accumulated logprob, free its KV tail (each branch
        retires at its OWN eos/budget — a short branch's blocks return
        to the pool while its siblings decode on). The request itself
        retires when its last branch lands: rank by cumulative
        logprob, hand the client the top ``n``."""
        req = s.req
        # outside the hot loop (retirement path), so the fetch is
        # legal; it waits for the round in flight, which left the
        # stopped row's sum as it was. A budget-1 branch retires in the
        # same _admit pass that prefilled it — before _write_slots put
        # its first token's logprob on the device — so the pending
        # value wins.
        lp = self._pending_logprob.pop(slot, None)
        if lp is None:
            lp = float(np.asarray(self._d_sampling["logprob"])[slot])
        self.scheduler.release_branch(req, s.seq_id)
        done = self._branch_done.setdefault(req.request_id, {})
        done[s.branch] = (list(s.tokens), lp)
        flight.record("serve", "retire_branch",
                      step=self.scheduler.round,
                      note=f"{s.seq_id} tokens={s.emitted} "
                           f"logprob={lp:.4f}")
        if len(done) < req.branches:
            return
        del self._branch_done[req.request_id]
        # highest cumulative logprob wins; branch index breaks ties
        # deterministically
        order = sorted(done.items(), key=lambda kv: (-kv[1][1], kv[0]))
        n_best = [dict(branch=k, tokens=list(t), logprob=lp)
                  for k, (t, lp) in order][:req.decode.n]
        win_tokens, win_lp = done[order[0][0]]
        # the winning branch's view rides the JSONL record: reuse the
        # last slot mirror as the record carrier
        s.tokens = win_tokens
        s.emitted = len(win_tokens)
        self.scheduler.finish_branches(
            req, np.asarray(win_tokens, np.int32), n_best, win_lp)
        flight.record("serve", "retire", step=self.scheduler.round,
                      note=f"{req.request_id} tokens={s.emitted} "
                           f"branches={req.branches}")
        self._finish_record(req, s)

    def _emit_chunk(self, s: _Slot, final: bool = False) -> None:
        """THE streaming funnel: every token chunk a client sees flows
        through this one ``TokenStream._feed`` call site (lint-pinned),
        so chunk accounting (counter, flight, JSONL) can never drift
        from what was actually delivered. No-op for non-streaming
        requests."""
        stream = s.req.stream
        if stream is None:
            return
        chunk = s.tokens[s.streamed:]
        if chunk:
            first = s.streamed == 0
            s.streamed = len(s.tokens)
            stream._feed(chunk)
            self._c_stream_chunks.inc()
            flight.record("serve", "stream_chunk",
                          step=self.scheduler.round,
                          note=f"{s.req.request_id} n={len(chunk)}"
                               f"{' first' if first else ''}"
                               f"{' final' if final else ''}")
            if self.metrics is not None:
                self.metrics.emit(
                    "serve_stream_chunk", request_id=s.req.request_id,
                    tokens=len(chunk), first=first, final=final)
        if final:
            stream.close()

    def _donate_blocks(self, slot: int, s: _Slot) -> None:
        """Copy the retiring slot's full KV blocks into the device
        store. Count matches what ``PrefixCache.release`` will index:
        ``depth // block_size`` full blocks (depth = prompt + emitted
        - 1 = exactly the rows whose tokens the scheduler hands to
        release). Re-saving a block the radix already owns writes
        bit-identical bytes — harmless.

        A block decoder's row retires owing its last block, the one
        that holds row ``depth``: what the cache has of it a denoising
        step wrote while some of its positions were still fed the mask
        token, and no forward of its final tokens followed. The rows
        written for good end at that block's start, ``depth // B * B``,
        and the pages wholly under it are the same ``depth //
        block_size``, a page being whole blocks (the constructor
        refuses any other): the count is release's, and no saved page
        holds a row of the owed block."""
        pool = self.scheduler.pool
        bs = pool.block_size
        table = pool.block_table(s.req.request_id)
        nb = min(s.depth // bs, len(table))
        if nb == 0:
            return
        padded = np.zeros((self._blocks_per_seq,), np.int32)
        padded[:nb] = table[:nb]
        args = (self._cache, self._store, bs, np.int32(slot), padded,
                np.int32(nb))
        with jitwatch.dispatch_span(
                "serve/save_blocks", program=(_save_blocks, args), blocks=nb):
            self._store = _save_blocks(*args)
        self._c_blocks_copied.inc(nb, direction="save")

    def _refuse_blocks(self, what: str) -> None:
        if self._not_by_position:
            raise ValueError(
                f"{what}: {type(self.model).__name__} keeps cache leaves "
                f"that are not rows by position "
                f"({self._not_by_position}); its engine has no block "
                f"store, and blocks sliced by absolute position cannot "
                f"carry them")

    def export_blocks(self, table):
        """Host-side copy of physical store blocks ``table`` (leading
        axis = position in the streamed chain) — the transfer SOURCE of
        KV block streaming (:mod:`serve.disagg`). Reads the device
        block store the retire path's ``_save_blocks`` maintains; the
        caller pins the blocks in the pool across the export window so
        eviction cannot recycle them before the peer's write lands.
        Non-block leaves (ndim < 2 scalars) ship as empty placeholders
        so the pytree structure round-trips."""
        self._refuse_blocks("export_blocks")
        idx = jnp.asarray(np.asarray(table, np.int32))
        return jax.tree.map(
            lambda s: np.asarray(s[idx]) if s.ndim >= 2
            else np.zeros((), s.dtype), self._store)

    def ingest_blocks(self, tokens, host_blocks, adapter: int = 0) -> int:
        """Transfer SINK of KV block streaming: index ``tokens``'s full
        blocks in this engine's prefix cache (:meth:`PrefixCache.
        ingest` adopts cached-ring blocks from the free list) and
        scatter the streamed ``host_blocks`` rows into the device store
        at the adopted ids. Already-resident blocks dedup by digest and
        are not rewritten. Returns blocks written; 0 when this engine
        has no prefix cache or the pool had no headroom to adopt."""
        self._refuse_blocks("ingest_blocks")
        if self._block is not None \
                and len(tokens) % self._block["block_length"]:
            raise ValueError(
                f"ingest_blocks: {len(tokens)} tokens end inside a block "
                f"of {self._block['block_length']}; the rows of a block "
                f"under way were written by a denoising step, not by its "
                f"commit, and cannot be taken for the sequence's")
        if self.prefix_cache is None or self._store is None:
            return 0
        plan = self.prefix_cache.ingest(tokens, adapter)
        if not plan:
            return 0
        src = jnp.asarray(np.asarray([j for j, _ in plan], np.int32))
        dst = jnp.asarray(np.asarray([p for _, p in plan], np.int32))
        self._store = jax.tree.map(
            lambda d, b: d.at[dst].set(jnp.asarray(b)[src])
            if d.ndim >= 2 else d, self._store, host_blocks)
        return len(plan)

    def _finish_record(self, req: Request, s: _Slot) -> None:
        # TTFT from the logical request's original arrival: for a
        # resubmitted leg, t_origin is the FIRST submit and
        # t_first_origin (if set) the first token an earlier leg
        # already delivered — the JSONL must agree with the fleet
        # ticket and the capacity sim, not restart the clock per leg
        origin = req.t_origin or req.t_submit
        t_first = req.t_first_origin or req.t_first_token
        ttft = t_first - origin
        total = req.t_done - req.t_submit
        decode = req.t_done - req.t_first_token
        per_tok = decode / max(s.emitted - 1, 1)
        # per-request waterfall: the request_id's timeline through
        # admission -> queue -> prefill -> decode -> retire, from the
        # scheduler's lifecycle timestamps + round bookkeeping. Rides
        # the serve_request JSONL record, the retroactive trace span's
        # phase children, and any watchtower alert that names this
        # request.
        waterfall = dict(
            queued_s=round(max(req.t_admit - req.t_submit, 0.0), 6),
            # (a block decoder's first token is its first block's last
            # step, some rounds after its prefill)
            prefill_s=round(max((req.t_prefilled or req.t_first_token)
                                - req.t_admit, 0.0), 6),
            decode_s=round(max(decode, 0.0), 6),
            round_submitted=req.round_submitted,
            round_admitted=req.round_admitted,
            round_done=req.round_done,
        )
        rec = dict(
            request_id=req.request_id, prompt_len=len(req.prompt),
            new_tokens=s.emitted, ttft_s=ttft, total_s=total,
            per_token_s=per_tok,
            rounds_waited=req.round_admitted - req.round_submitted,
            kv_util=self.scheduler.pool.utilization(),
            waterfall=waterfall,
            tenant=req.tenant, adapter=req.adapter,
            cached_tokens=s.cached,
        )
        if self.tag:
            rec["replica"] = self.tag
        # Prism keys: absent for default requests (key-absent wire
        # discipline: a greedy, non-streaming run's JSONL holds
        # nothing of the decode policy)
        if req.decode is not None:
            rec["decode"] = req.decode.to_wire()
        if req.n_best is not None:
            rec["branches"] = req.branches
            rec["logprob"] = round(req.logprob, 6)
        if req.stream is not None:
            rec["stream_chunks"] = req.stream.chunks
        if req.trace is not None:
            # the record names its trace (watchtower pages attach it;
            # key absent when untraced, so replayed streams from an
            # unarmed run stay byte-identical)
            rec["trace"] = req.trace.trace_id
        # Lighthouse fingerprint: THE one engine call site that folds a
        # request's emitted tokens onto its chain seed (lint-pinned).
        # None unarmed — the fp key stays absent and the record stream
        # is byte-identical to a pre-audit run.
        fp = audit.on_retire(req.request_id, s.tokens,
                             seed=req.fp_seed, replica=self.tag)
        if fp is not None:
            rec["fp"] = fp
        self.completed.append(rec)
        if self.metrics is not None:
            self.metrics.emit("serve_request", **rec)
        watchtower.on_serve_request(rec)
        # Abacus lifecycle charges (queue/decode wall time, tokens,
        # the per-request JSONL record, the cost-anomaly feed). Audit
        # shadow/probe legs are duplicates, never billed.
        if meter.enabled() and req.tenant != audit.SHADOW_TENANT:
            meter.on_request_done(rec, self.flops_per_token())
        # Causeway segments, retroactive from the scheduler's
        # lifecycle timestamps — the decode hot loop stays untouched
        # (its lint bans extras); resubmit legs ride the ctx the fleet
        # minted/linked
        trace.on_segment(req.trace, "queued", req.t_submit,
                         req.t_admit, request_id=req.request_id,
                         replica=self.tag)
        trace.on_segment(req.trace, "prefill", req.t_admit,
                         req.t_first_token, request_id=req.request_id,
                         replica=self.tag, cached=s.cached,
                         prompt_len=len(req.prompt))
        seg_kw = dict(request_id=req.request_id, replica=self.tag,
                      tokens=s.emitted)
        if fp is not None:
            # the decode span carries the leg fingerprint so a trace
            # waterfall can show WHERE a chain diverged across legs
            seg_kw["fp"] = fp
        trace.on_segment(req.trace, "decode", req.t_first_token,
                         req.t_done, **seg_kw)
        tracer = obs.current_recorder()
        if tracer is not None:
            # retroactive per-request span: duration is only known now
            end_us = tracer._now_us()
            t0_us = end_us - total * 1e6
            tracer.add_event(f"serve/{req.request_id}",
                             t0_us, total * 1e6,
                             cat="serve", args=dict(
                                 prompt_len=len(req.prompt),
                                 new_tokens=s.emitted,
                                 ttft_ms=ttft * 1e3))
            off_us = 0.0
            for phase in ("queued", "prefill", "decode"):
                dur_us = waterfall[f"{phase}_s"] * 1e6
                if dur_us > 0:
                    tracer.add_event(
                        f"serve/{req.request_id}/{phase}",
                        t0_us + off_us, dur_us, cat="serve")
                off_us += dur_us

    def _write_slots(self, slots: list) -> None:
        """Write the device's slot state of the rows ``slots`` from
        their host mirrors, in one dispatch behind the round in flight
        and the rows' ``_insert_row`` (admission path; the flip drill).
        Only a row the host has just filled is the host's to write:
        every other live row is one round further on the device than
        its mirror here, and a finished row the device has stopped
        itself. A slot whose row retired in the pass that filled it is
        written inactive. The adapter ids go with an engine's bank, the
        sampling rows while a sampled row is live."""
        rows = np.zeros((5, self.max_slots), np.int32)
        rows[0, slots] = 1
        blk = self._block
        if blk is not None:
            B = blk["block_length"]
            tails = np.zeros((self.max_slots, B), np.int32)
        for i in slots:
            s = self._slots[i]
            if s is None:
                continue
            if blk is not None:
                # the row's first block: the prompt's tail, then masks;
                # its RNG step counts the row's rounds
                L = len(s.req.prompt)
                tails[i, :L % B] = s.req.prompt[L // B * B:]
                rows[1:, i] = (L % B, L // B * B, s.req.max_new_tokens,
                               s.req.adapter)
                self._h_sampling["step"][i] = s.step0
                continue
            rows[1:, i] = (s.tokens[-1], s.depth,
                           s.req.max_new_tokens - s.emitted,
                           s.req.adapter)
            # a row's RNG step is step0 + emitted: recomputable
            # here by design, so a flip drill's write mid-stream
            # cannot skew the device's counter
            self._h_sampling["step"][i] = s.step0 + s.emitted
        sampled = self._n_sampled > 0
        # (positional, as warmup() passes them: a keyword is another
        # program to jax.jit)
        write = _write_rows if blk is None else _write_block_rows
        args = (*self._d_slots, rows, *(() if blk is None else (tails,)),
                None if self._lora is None else self._lora["adapter_ids"],
                self._d_sampling if sampled else None,
                self._h_sampling if sampled else None)
        with jitwatch.noting((write, args)):
            self._d_slots, ids, drawn = write(*args)
        if ids is not None:
            self._lora = dict(self._lora, adapter_ids=ids)
        if drawn is not None:
            self._d_sampling = drawn

    def flops_per_token(self) -> int:
        """Analytic forward FLOPs of ONE token through this model
        (:func:`utils.flops.fwd_flops` at batch 1, seq 1) — the unit
        every Abacus billing multiplies. Integer (exact per-tenant
        sums), computed once per engine. On a CPU backend a failed
        count bills 0 FLOPs (tokens/residency/wire still meter); on the
        chip it raises. Only metered paths call this, so an unarmed
        process never pays the lowering."""
        if self._flops_per_token is None:
            from pytorch_distributed_nn_tpu.utils.flops import fwd_flops

            try:
                self._flops_per_token = int(round(
                    fwd_flops(self.model, (1, 1), jnp.int32)))
            except RuntimeError:
                if jax.default_backend() == "tpu":
                    raise
                self._flops_per_token = 0
        return self._flops_per_token

    def summary(self) -> dict:
        """Engine-lifetime aggregates (bench + serve_summary JSONL)."""
        # flush per-tenant meter_ledger JSONL records (inert no-op
        # unless TPUNN_METER armed): a finished run's stream carries
        # the final ledgers for obs_cost/obs_report
        meter.on_serve_summary()
        self.publish_device_counters()
        rounds = len(self.round_seconds)
        occ = self._occ_sum / max(rounds * self.max_slots, 1)
        out = dict(
            rounds=rounds,
            # rounds dispatched before the one before them was fetched:
            # over ``rounds``, the share the chip did not wait for
            rounds_overlapped=self._overlapped,
            requests_done=len(self.completed),
            tokens_out=int(sum(r["new_tokens"] for r in self.completed)),
            # every token the engine gave (a prefill's first, a round's
            # one a row or a block decoder's up to a block a row), the
            # unfinished requests' too
            tokens_emitted=self._tokens_emitted,
            occupancy=occ,
            kv_util=self.scheduler.pool.utilization(),
            queue_depth=self.scheduler.queue_depth,
        )
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        # the loop thread's own account: seconds by phase since its
        # first round, and its three longest rounds with what filled them
        loop = self.loop
        loop.publish()
        out["loop"] = loop.summary()
        out["longest_rounds"] = [
            goodput.describe_round(r, loop.t_start or 0.0)
            for r in list(loop.longest)]
        return out
