"""SDAR's block-diffusion decoder at a small size, float32, on the CPU.

The model (``models/sdar_moe.py``: the Qwen3-MoE block under a mask that
is causal by blocks) and the engine's block round (``serve/engine.py``
``_block_round``) against a reference written here in plain
``jax.numpy``: a full forward with no cache, and the family's
generation loop a forward at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.inference.generate import init_cache
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.runtime import chaos
from pytorch_distributed_nn_tpu.serve import ServingEngine, engine
from pytorch_distributed_nn_tpu.serve.decoding import DecodeSpec

B = 4
V = 97
MASK = 96          # the tiny vocabulary's mask token
HEADS, KV, HD, EXPERTS, TOPK, LAYERS, D = 4, 2, 16, 8, 2, 3, 64
EPS, THETA = 1e-6, 1e6


def _model(**over):
    extra = dict(vocab_size=V, num_layers=LAYERS, d_model=D, num_heads=HEADS,
                 num_kv_heads=KV, head_dim=HD, expert_mlp_dim=32,
                 num_experts=EXPERTS, moe_topk=TOPK, block_length=B,
                 denoising_steps=2, remasking="sequential",
                 mask_token_id=MASK, norm_eps=EPS, rope_theta=THETA)
    extra.update(over)
    return get_model(ModelConfig(name="sdar_moe", dtype="float32",
                                 compute_dtype="float32", extra=extra))


@pytest.fixture(scope="module")
def params():
    model = _model()
    p = model.init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32),
                   train=False)["params"]
    # gains off one, so that a norm left out shows
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.key(len(str(path))), x.shape)
        if str(path[-1]).endswith("scale']") else x, p)


# -- the reference: a full forward, no cache --------------------------------

def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, pos):
    half = HD // 2
    freqs = THETA ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / HD)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def ref_forward(p, ids, block=B):
    """(N, V) logits of ``ids`` at positions 0..N-1: row t scores
    position t and sees every position up to the end of t's block."""
    ids = jnp.asarray(ids)
    n = ids.shape[0]
    pos = jnp.arange(n)
    see = pos[None, :] <= (pos[:, None] // block * block + block - 1)
    x = p["tok_embed"]["table"][ids]
    for i in range(LAYERS):
        w = p[f"layer{i}"]
        a = _rms(x, w["input_norm"]["scale"])
        q = _rms(jnp.einsum("td,dhk->thk", a, w["attn"]["query"]["kernel"]),
                 w["attn"]["q_norm"]["scale"])
        k = _rms(jnp.einsum("td,dhk->thk", a, w["attn"]["key"]["kernel"]),
                 w["attn"]["k_norm"]["scale"])
        v = jnp.einsum("td,dhk->thk", a, w["attn"]["value"]["kernel"])
        q, k = _rope(q, pos), _rope(k, pos)
        heads = []
        for h in range(HEADS):
            g = h // (HEADS // KV)
            s = q[:, h] @ k[:, g].T / np.sqrt(HD)
            heads.append(jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
                         @ v[:, g])
        x = x + jnp.einsum("thk,hkd->td", jnp.stack(heads, 1),
                           w["attn"]["out"]["kernel"])
        m = _rms(x, w["post_attn_norm"]["scale"])
        r = jax.nn.softmax(m @ w["moe"]["router"]["kernel"], -1)
        top, idx = jax.lax.top_k(r, TOPK)
        top = top / top.sum(-1, keepdims=True)
        ff = w["moe"]["experts_gate"].shape[1] // EXPERTS
        y = jnp.zeros_like(m)
        for e in range(EXPERTS):
            we = jnp.where(idx == e, top, 0.0).sum(-1, keepdims=True)
            g_, u_ = (w["moe"][nm][:, e * ff:(e + 1) * ff]
                      for nm in ("experts_gate", "experts_up"))
            d_ = w["moe"]["experts_down"][:, e * D:(e + 1) * D]
            y = y + we * ((jax.nn.silu(m @ g_) * (m @ u_)) @ d_)
        x = x + y
    return _rms(x, p["final_norm"]["scale"]) @ p["lm_head"]["kernel"]


def ref_generate(p, prompt, G, *, steps, rule, threshold=0.9, eos=None):
    """The family's ``block_diffusion_generate`` a forward at a time, on
    the engine's schedule: a block is handed out by the step that leaves
    it whole, and its keys and values are written by the next forward of
    the row, the one that takes the next block's first step (a forward
    with no cache writes nothing, so here that forward is only counted:
    ``fused``). No forward unmasks nothing and only commits (``commits``
    stays 0), and the last block of a request is never committed.
    ``(tokens, forwards, commits, unmasked, fused)``."""
    prompt = [int(t) for t in prompt]
    P = len(prompt)
    c = P // B * B
    done = prompt[:c]
    block = prompt[c:] + [None] * (B - (P - c))
    out, forwards, commits, unmasked, fused = [], 0, 0, 0, 0
    owes = False
    while True:
        t = 0
        while any(x is None for x in block):
            ids = done + [MASK if x is None else x for x in block]
            logits = np.asarray(ref_forward(p, ids))[c:c + B]
            forwards += 1
            fused += owes
            owes = False
            open_ = logits.copy()
            open_[:, MASK] = -np.inf
            x0 = open_.argmax(-1)
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            conf = (probs / probs.sum(-1, keepdims=True))[np.arange(B), x0]
            masked = [i for i, x in enumerate(block) if x is None]
            n_t = min(B // steps + (t < B % steps), len(masked))
            if rule == "sequential":
                take = masked[:n_t]
            else:
                take = sorted(masked, key=lambda i: (-conf[i], i))[:n_t]
                sure = [i for i in masked if conf[i] > threshold]
                if rule == "low_confidence_dynamic" and len(sure) >= n_t:
                    take = sure
            for i in take:
                block[i] = int(x0[i])
            unmasked += len(take)
            t += 1
        for i, tok in enumerate(block):
            if c + i >= P and len(out) < G:
                out.append(tok)
                if tok == eos:
                    return out, forwards, commits, unmasked, fused
        if len(out) >= G:
            return out, forwards, commits, unmasked, fused
        done, block, c, owes = done + block, [None] * B, c + B, True


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 64)
    return ServingEngine(model, params, block_size=16, **kw)


def _serve(eng, jobs):
    reqs = [eng.submit(np.asarray(p, np.int32), g) for p, g in jobs]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return [[int(t) for t in r.tokens] for r in reqs]


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, MASK, size=(n,))


def _block_counts(eng):
    """The round's own counters as the registry has them: ``[forwards,
    commits, positions unmasked, tokens emitted, fused commits]`` (a
    counter that never moved is not in a snapshot)."""
    eng.publish_device_counters()
    reg = obs.get_registry().snapshot()
    return [int(reg.get(f"block_{n}_total", 0)) for n in (
        "forwards", "commits", "positions_unmasked", "tokens_emitted",
        "commits_fused")]


# -- the model --------------------------------------------------------------

def test_forward_under_the_mask_by_blocks(params):
    ids = _prompt(14, 1)
    got = _model().apply({"params": params}, jnp.asarray(ids)[None],
                         train=False)[0]
    np.testing.assert_allclose(got, ref_forward(params, ids), atol=2e-5)
    # the mask is the difference from a causal model, and it shows
    causal = ref_forward(params, ids, block=1)
    assert float(jnp.abs(got - causal).max()) > 1e-2


def test_prefill_and_rounds_through_the_cache_equal_the_full_forward(params):
    """Rows [0, 8) prefilled, then the block at [8, 12) forwarded three
    times (its tokens changing), then the block at [12, 16): each
    against the full forward of what the rows held at that moment. Then
    ``2B`` positions a row, as the engine's round feeds them: the block
    at [12, 16) again with the next, all masked, behind it (a row that
    owes), whose rows [12, 16) the following forward reads; and the
    block at [16, 20) with four positions behind it that are not real
    (a row that owes nothing), only the open block's rows reaching the
    head."""
    model = _model()
    ids = _prompt(20, 2)
    cache = init_cache(model, 2, 32)

    def fed(tokens, at, **kw):
        nonlocal cache
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray(tokens)[None].repeat(2, 0), train=False, decode=True,
            mutable=["cache"], cache_positions=jnp.asarray([at, at]), **kw)
        cache = mutated["cache"]
        return logits[0]

    got = fed(ids[:8], 0)
    np.testing.assert_allclose(got, ref_forward(params, ids[:8]), atol=2e-5)
    for block in ([MASK] * 4, [ids[8], ids[9], MASK, MASK], ids[8:12]):
        seq = np.concatenate([ids[:8], block])
        np.testing.assert_allclose(
            fed(block, 8, block_round=True), ref_forward(params, seq)[8:],
            atol=2e-5)
    np.testing.assert_allclose(
        fed(ids[12:16], 12, block_round=True),
        ref_forward(params, ids[:16])[12:], atol=2e-5)
    # the rows [12, 16) spoiled, as a denoising step leaves them
    fed([ids[12], MASK, MASK, MASK], 12, block_round=True)
    two = np.concatenate([ids[12:16], [MASK] * 4])
    want = ref_forward(params, np.concatenate([ids[:12], two]))
    np.testing.assert_allclose(fed(two, 12, block_round=True), want[12:],
                               atol=2e-5)
    np.testing.assert_allclose(
        fed(two, 12, block_round=True,
            head_rows=jnp.asarray([[4, 5, 6, 7]] * 2)), want[16:], atol=2e-5)
    # the owed rows are the final tokens' now: the next block reads them
    real = jnp.arange(8)[None] < jnp.asarray([[4], [4]])
    np.testing.assert_allclose(
        fed(np.concatenate([ids[16:20], [MASK] * 4]), 16, block_round=True,
            token_mask=real, head_rows=jnp.asarray([[0, 1, 2, 3]] * 2)),
        ref_forward(params, ids)[16:], atol=2e-5)
    totals = np.asarray(cache["device_counters"]).reshape(-1)
    names = model.device_counter_names()
    by = {(n, l.get("kind"), l.get("layer")): int(v)
          for (n, l), v in zip(names, totals)}
    # one prefill of 2 rows x 8, five rounds of 2 rows x 4 positions,
    # two of 2 rows x 8 and one of 2 rows x 8 of which 4 are real
    assert by[("moe_calls_total", "prefill", "0")] == 1
    assert by[("moe_calls_total", "decode", "2")] == 8
    assert by[("moe_picks_total", "decode", "0")] == 2 * 40 * TOPK
    assert by[("attn_rows_read_total", "decode", "1")] == 2 * 40 * 32
    # a query of the block at [8, 12) sees 12 rows, of [12, 16) 16, of
    # [16, 20) 20: counted a real query, so a row that feeds two blocks
    # counts the rows before them twice over
    assert by[("attn_rows_attended_total", "decode", "1")] \
        == 2 * 4 * (3 * 12 + 2 * 16 + 2 * (16 + 20) + 20)


# -- the engine's rounds ----------------------------------------------------

@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static",
                                  "low_confidence_dynamic"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_served_tokens_are_the_reference_generators(params, rule, steps):
    """P in every residue mod B and under B, G a multiple of B and not,
    more requests than slots: rows join and leave mid-flight."""
    model = _model(denoising_steps=steps, remasking=rule)
    jobs = [(_prompt(P), G) for P, G in
            [(8, 8), (9, 7), (10, 5), (11, 6), (3, 5), (1, 2), (17, 1)]]
    obs.reset_registry()
    eng = _engine(model, params)
    served = _serve(eng, jobs)
    want = [ref_generate(params, p, g, steps=steps, rule=rule)
            for p, g in jobs]
    assert served == [w[0] for w in want]
    got = _block_counts(eng)
    assert got == [sum(w[i] for w in want) for i in (1, 2, 3)] \
        + [sum(g for _, g in jobs), sum(w[4] for w in want)]
    # no forward only commits; every block but a request's last is
    # written beside the next one's first step
    assert got[1] == 0 and got[4] == sum(
        -(-(len(p) % B + g) // B) - 1 for p, g in jobs)
    assert eng.summary()["tokens_emitted"] == sum(g for _, g in jobs)
    if rule != "low_confidence_dynamic":
        # the rounds the host planned for are the rounds the rows took
        assert got[0] == sum(engine._block_rounds(len(p), g, B, steps)
                             for p, g in jobs)


@pytest.mark.parametrize("slots,steps,rule", [
    (2, 2, "sequential"), (3, 2, "sequential"),
    (3, 3, "low_confidence_static"), (3, 4, "low_confidence_dynamic")])
def test_rows_admitted_mid_flight_equal_their_solo_runs(params, slots, steps,
                                                        rule, monkeypatch):
    """Prompts whose tails leave first blocks of one to four steps, so
    the rows fall out of phase: in one round a row that owes its last
    block, a row in the middle of one and a row admitted since the last
    round, behind a prefill or (P 2: no whole block) none."""
    model = _model(denoising_steps=steps, remasking=rule)
    jobs = [(_prompt(P, 3), G) for P, G in
            [(5, 9), (12, 3), (7, 11), (2, 6), (9, 4), (6, 7), (11, 13)]]
    obs.reset_registry()
    eng = _engine(model, params, max_slots=slots)
    seen = set()
    step = engine._serve_step

    def watched(model_, params_, cache, out, place, active, *rest):
        seen.update(zip(np.asarray(active).tolist(),
                        np.asarray(place["owes"]).tolist(),
                        np.asarray(place["step"] > 0).tolist()))
        return step(model_, params_, cache, out, place, active, *rest)
    monkeypatch.setattr(engine, "_serve_step", watched)
    together = _serve(eng, jobs)
    # live rows that owe, that are in the middle of a block, and that
    # open one owing nothing have all shared a program
    assert {(True, True, False), (True, False, True),
            (True, False, False)} <= seen
    alone = [_serve(_engine(model, params, max_slots=1), [job])[0]
             for job in jobs]
    assert together == alone
    want = [ref_generate(params, p, g, steps=steps, rule=rule)
            for p, g in jobs]
    assert together == [w[0] for w in want]
    got = _block_counts(eng)
    assert (got[0], got[4]) == (sum(w[1] for w in want),
                                sum(w[4] for w in want))


def test_peaked_logits_unmask_a_block_in_one_dynamic_step(params):
    """A head scaled up makes every proposal's probability nearly one:
    ``low_confidence_dynamic`` takes the whole block in its first step,
    one forward a block, where the same model under
    ``low_confidence_static`` takes the four steps it planned."""
    sharp = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 1e3 if "lm_head" in str(path) else x, params)
    jobs = [(_prompt(8, 4), 8), (_prompt(4, 5), 12)]
    counts = {}
    for rule in ("low_confidence_dynamic", "low_confidence_static"):
        obs.reset_registry()
        eng = _engine(_model(denoising_steps=4, remasking=rule), sharp)
        served = _serve(eng, jobs)
        want = [ref_generate(sharp, p, g, steps=4, rule=rule)
                for p, g in jobs]
        assert served == [w[0] for w in want]
        got = _block_counts(eng)
        counts[rule] = (got[0], got[1], got[4])
        assert counts[rule] == tuple(sum(w[i] for w in want)
                                     for i in (1, 2, 4))
    # five blocks, of which three are followed by another of their row
    assert counts["low_confidence_dynamic"] == (1 * 5, 0, 3)
    assert counts["low_confidence_static"] == (4 * 5, 0, 3)


@pytest.mark.parametrize("at", [5, 1, 9])
def test_eos_inside_a_block_ends_the_request_there(params, at):
    """``eos`` inside the second block, in the first block (which the
    prompt's tail opens) and as a block's last position: the request
    ends in the round whose step leaves that block whole, and its row
    runs no further forward: none to commit the block, none of the
    next."""
    model = _model()
    prompt = _prompt(6, 6)
    free = _serve(_engine(model, params), [(prompt, 12)])[0]
    eos = free[at]
    cut = free.index(eos)
    obs.reset_registry()
    eng = _engine(model, params, eos_token=eos)
    served = _serve(eng, [(prompt, 12)])
    want = ref_generate(params, prompt, 12, steps=2, rule="sequential",
                        eos=eos)
    assert served[0] == free[:cut + 1] == want[0]
    assert _block_counts(eng) == [want[1], 0, want[3], cut + 1, want[4]]
    # the first block's one step (two of its positions are the prompt's)
    # and two a block after it, up to the block that holds the eos
    assert want[1] == 1 + 2 * ((6 % B + cut) // B)
    other = _serve(_engine(model, params, eos_token=eos),
                   [(prompt, 12), (_prompt(9, 8), 3)])
    assert other[0] == served[0] and len(other[1]) <= 3


@pytest.mark.parametrize("P,G", [(6, 5), (6, 6), (8, 3), (7, 1)])
def test_a_budget_that_ends_inside_a_block_ends_the_request_there(params,
                                                                  P, G):
    """The block that holds the budget's last token is stepped to its
    end, handed out up to the budget and never committed."""
    obs.reset_registry()
    eng = _engine(_model(), params)
    prompt = _prompt(P, 16)
    served = _serve(eng, [(prompt, G)])[0]
    want = ref_generate(params, prompt, G, steps=2, rule="sequential")
    assert served == want[0] and len(served) == G
    assert _block_counts(eng) == [want[1], 0, want[3], G, want[4]]
    assert want[1] == engine._block_rounds(P, G, B, 2)


@pytest.mark.parametrize("P,G", [(25, 7), (29, 3), (28, 4), (20, 12)])
def test_a_row_at_the_end_of_its_cache_drops_the_dead_positions(params, P, G):
    """A row whose open block is the cache's last, ``max_seq_len - B``,
    and that owes nothing feeds ``B`` dead positions past the cache's
    end: they are dropped, not wrapped onto the row's start nor onto the
    next slot's, beside a row whose requests fill the same cache to the
    end one after another."""
    model = _model()
    jobs = [(_prompt(P, 17), G), (_prompt(5, 18), 27), (_prompt(31, 19), 1),
            (_prompt(P, 20), G)]
    together = _serve(_engine(model, params, max_slots=2, max_seq_len=32),
                      jobs)
    assert together == [ref_generate(params, p, g, steps=2,
                                     rule="sequential")[0] for p, g in jobs]


def test_the_mask_token_inside_a_prompt_is_a_token(params):
    """Whether a position is masked is the engine's boolean, not a
    comparison of ids."""
    model = _model()
    prompt = _prompt(10, 9)
    prompt[[2, 9]] = MASK               # one in the prefill, one in the tail
    served = _serve(_engine(model, params), [(prompt, 6)])[0]
    assert served == ref_generate(params, prompt, 6, steps=2,
                                  rule="sequential")[0]
    assert MASK not in served           # and it is never proposed


@pytest.mark.parametrize("P,G", [(45, 20), (45, 19), (41, 24), (48, 17)])
def test_a_retire_saves_the_pages_under_the_owed_block(params, P, G,
                                                       monkeypatch):
    """A row retires owing its last block, the one that holds position
    ``P + G - 1``: the pages saved, and indexed, are those wholly under
    that block's start, by one count, and a request that brings the
    whole sequence as its prompt is served from them what the reference
    generates: (45, 20) ends on a page's first position, (45, 19) and
    (41, 24) on a page's last (that page holds the owed block and is not
    saved), (48, 17) with the owed block the first of a page."""
    model = _model()
    eng = _engine(model, params, max_slots=2, max_seq_len=128)
    saved = []
    save = engine._save_blocks
    monkeypatch.setattr(
        engine, "_save_blocks",
        lambda cache, store, bs, slot, table, n: saved.append(int(n))
        or save(cache, store, bs, slot, table, n))
    first = _prompt(P, 21)
    served = _serve(eng, [(first, G)])[0]
    written = (P + G - 1) // B * B          # the owed block's start
    assert saved == [written // 16]
    assert eng.prefix_cache.stats()["prefix_nodes"] == saved[0]
    again = np.concatenate([first, served, _prompt(3, 22)])
    assert _serve(eng, [(again, 9)])[0] == ref_generate(
        params, again, 9, steps=2, rule="sequential")[0]
    assert eng.completed[-1]["cached_tokens"] == written // 16 * 16


def test_a_restored_prefix_serves_the_same_tokens(params):
    model = _model()
    eng = _engine(model, params, max_slots=2, max_seq_len=128)
    first = _prompt(45, 11)
    _serve(eng, [(first, 20)])
    again = np.concatenate([first[:32], _prompt(9, 12)])
    hits0 = eng.prefix_cache.stats()["prefix_hits"]
    served = _serve(eng, [(again, 7)])[0]
    assert eng.prefix_cache.stats()["prefix_hits"] == hits0 + 1
    # two pages of 16 restored, the blocks at [32, 40) prefilled
    assert eng.completed[-1]["cached_tokens"] == 32
    assert served == ref_generate(params, again, 7, steps=2,
                                  rule="sequential")[0]
    # 38 tokens agree, the last 6 with the head of a page: the copied
    # page is cut back to a whole block (36), which is also all the
    # whole blocks this prompt of 39 has: no prefill program at all
    third = np.concatenate([first[:38], _prompt(1, 13)])
    assert _serve(eng, [(third, 5)])[0] == ref_generate(
        params, third, 5, steps=2, rule="sequential")[0]
    assert eng.completed[-1]["cached_tokens"] == 36


def test_pages_and_rows_must_hold_whole_blocks(params):
    with pytest.raises(ValueError, match="multiples of it"):
        ServingEngine(_model(block_length=8), params, block_size=4,
                      max_seq_len=64)
    with pytest.raises(ValueError, match="multiples of it"):
        ServingEngine(_model(), params, block_size=16, max_seq_len=62)
    with pytest.raises(ValueError, match="remasking"):
        _model(remasking="random").block_decoding()


def test_branches_are_refused_by_name(params):
    eng = _engine(_model(), params)
    with pytest.raises(ValueError, match="n > 1 branches"):
        eng.submit(_prompt(5), 4, decode=DecodeSpec(temperature=1.0, n=2,
                                                    seed=1))


def test_ingest_into_a_block_under_way_is_refused_by_name(params):
    eng = _engine(_model(), params)
    blocks = eng.export_blocks([0])
    with pytest.raises(ValueError, match="end inside a block"):
        eng.ingest_blocks(_prompt(18), blocks)
    assert eng.ingest_blocks(_prompt(16), blocks) == 1


def test_the_flip_drill_is_refused_by_name(params):
    eng = _engine(_model(), params)
    eng.submit(_prompt(5), 4)
    chaos.maybe_init("flip@replica=0", rank=0)
    try:
        with pytest.raises(RuntimeError, match="chaos flip@"):
            eng.run_until_idle()
    finally:
        chaos.reset()


def test_a_sampled_row_draws_each_position_and_repeats_by_seed(params):
    model = _model()
    spec = DecodeSpec(temperature=0.8, top_k=20, seed=5)

    def run():
        eng = _engine(model, params)
        req = eng.submit(_prompt(6, 14), 10, decode=spec)
        greedy = eng.submit(_prompt(7, 15), 6)
        eng.run_until_idle()
        return [int(t) for t in req.tokens], [int(t) for t in greedy.tokens]
    (a, g), (b, _) = run(), run()
    assert a == b and len(a) == 10 and MASK not in a
    # a greedy row beside a sampled one keeps its argmax
    assert g == ref_generate(params, _prompt(7, 15), 6, steps=2,
                             rule="sequential")[0]
    assert a != ref_generate(params, _prompt(6, 14), 10, steps=2,
                             rule="sequential")[0]


# -- the other models' programs ---------------------------------------------

def _parents_step(model, params, cache, last_tok, lengths, active,
                  remaining, eos):
    """``_serve_step`` as the parent commit had it (greedy, no bank)."""
    logits, cache = engine._apply_decode_ragged(
        model, params, cache, last_tok, lengths,
        **engine._mask_kw(model, active[:, None]))
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, last_tok)
    lengths = jnp.where(active, lengths + 1, lengths)
    alive = active & (remaining > 1) & (nxt != eos)
    remaining = jnp.where(active, remaining - 1, remaining)
    return nxt, lengths, alive, remaining, cache, None


@pytest.mark.parametrize("name,extra", [
    ("llama3_8b", dict(vocab_size=64, num_layers=2, d_model=32, num_heads=4,
                       num_kv_heads=2, mlp_dim=64)),
    ("k_exaone", dict(vocab_size=64, num_layers=4, d_model=32, num_heads=4,
                      num_kv_heads=2, head_dim=8, mlp_dim=64, window=8,
                      expert_mlp_dim=16, num_experts=4, moe_topk=2)),
])
def test_other_models_step_lowers_to_the_parents_text(name, extra,
                                                      monkeypatch):
    """The block round is a branch taken while tracing, on the static
    model: a model that declares no ``block_decoding`` lowers to the
    text of the parent's function, and to the same text when every
    piece this PR added would raise if it were reached."""
    model = get_model(ModelConfig(name=name, dtype="float32",
                                  compute_dtype="float32", extra=extra))
    slots = 4
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    cache = jax.eval_shape(lambda: init_cache(model, slots, 32))
    state = [jax.ShapeDtypeStruct((slots,), dt) for dt in
             (jnp.int32, jnp.int32, jnp.bool_, jnp.int32)] \
        + [jax.ShapeDtypeStruct((), jnp.int32)]

    def lowered(fn):
        fn.__name__ = fn.__qualname__ = "_serve_step"
        return jax.jit(fn, static_argnums=(0,), donate_argnums=(2,)) \
            .lower(model, params, cache, *state).as_text()

    now = engine._serve_step.lower(model, params, cache, *state).as_text()
    assert now == lowered(lambda *a: _parents_step(*a))

    def unreachable(*a, **k):
        raise AssertionError("a block decoder's code on another's path")
    from pytorch_distributed_nn_tpu.nn import attention
    monkeypatch.setattr(engine, "_block_round", unreachable)
    monkeypatch.setattr(attention, "_block_update", unreachable)
    assert now == lowered(
        lambda *a: engine._serve_step.__wrapped__(*a))
