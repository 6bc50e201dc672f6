"""Plain reference for ``sdar_30b_a3b``.

SDAR-30B-A3B-Chat's language model (``JetLM/SDAR-30B-A3B-Chat``
``config.json`` for the sizes). The body is the Qwen3-MoE block, read
from ``transformers`` 4.57.6 ``models/qwen3_moe/modeling_qwen3_moe.py``
(``Qwen3MoeAttention`` lines 122-195, ``Qwen3MoeSparseMoeBlock``
213-265, ``Qwen3MoeRMSNorm`` 268-286, ``Qwen3MoeDecoderLayer`` 288-366);
what the family changes is the attention mask and the generation loop
(its ``generate.py``, ``block_diffusion_generate``). The configuration
file lists each such line under ``assumed``. ``N`` is RMSNorm with gain,
computed in float32, eps ``rms_norm_eps``; no projection has a bias.
Layer ``l``, for the token at position ``p``::

    h   = x + Attn_l(N_in(x))
    out = h + MoE_l(N_post(h))

    Attn_l: q = a W_q -> heads x head_dim; k, v = a W_k, a W_v -> kv heads
            q = N_q(q), k = N_k(k)          over each head's dims, one gain
            q, k = RoPE(q, k) at p, half rotation (i with i + head_dim / 2),
                   angle p * theta^(-2i/head_dim)
            s_pj = q_p . k_j / sqrt(head_dim)  for every j <= see(p)
            see(p) = p // B * B + B - 1      causal by blocks of B
            softmax in float32, o = p v; query heads of one group read
            one kv head; out = concat(o) W_o

    MoE_l:  r = softmax(float32(m) W_r)
            S = top_k(r);  w_e = r_e / sum_{e' in S} r_e'
            y = sum_{e in S} w_e (silu(m G_e) * (m U_e)) D_e

Embedding, the layers, final ``N``, untied head. Row ``p`` of the logits
scores position ``p`` itself, not the next.

**What is compared.** ``benchmark/lib/check.py`` hands over a prompt of
``P`` tokens with the served tokens but the last, and wants one row of
logits a served token: the row the token should be the best of. A token
of a block is chosen by a *denoising step*: a forward of the block in
which the positions not yet known are fed the mask token, against every
earlier block as committed. Under ``remasking: "sequential"`` which step
chose which token follows from ``P``, the block length ``B`` and
``denoising_steps`` ``S`` alone (:func:`steps_of`): step ``t`` of a
block takes the ``B // S`` leftmost masked places (one more in the
first ``B % S`` steps), the prompt's tail counted as known from the
start. So one forward runs over ``[the sequence as committed ; for each
step index t, every generated block as step t found it]`` under the
mask "a committed block sees the committed blocks up to itself; a block
under way sees the committed blocks before it and itself", and each
token's row is read from the copy of its step. The last served token is
never an input: every place after a chosen one is still masked.

The configuration may be one rank's share of an expert-parallel
deployment (``expert_parallel``; here ``ep_size`` 1: every expert is
held), as ``k_exaone_236b_ref.py`` has it.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a full mask, no cache, no
batching, no gathering of tokens by expert (every held expert runs over
every token and the unrouted are weighed by zero). Weights are
regenerated from the seed a layer at a time in the served type and
upcast a sublayer at a time (the experts' 1.2 GB an expert at a time);
attention runs a head at a time.

``quantize="int8"`` is the served cells' control: every matrix rounded
to int8 with one scale per output channel (per row for the token
table).

**The draw.** ``benchmark/lib/weights.py`` makes a leaf from its name:
one called ``embedding`` gets unit variance, any other matrix
``1 / shape[0]``. The program's token table is the leaf
``tok_embed/table`` (``models/sdar_moe.TokenTable`` says why), so its
entries are N(0, 1 / vocab_size), 2.6e-3, under the projections' 2.2e-2
and the family's ``initializer_range`` 0.02 and not fifty times over
them. Every token is chosen at a position that was fed the mask token's
row; at unit variance that one row was most of what such a position
held at the head, the rows compared were one row a seed and a little,
and ``gap_mean`` was one draw of the weights (``PERF.md`` sec. 6).

Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights

_ATTN = ("query/kernel", "key/kernel", "value/kernel", "q_norm/scale",
         "k_norm/scale", "out/kernel")
_MOE = ("router/kernel", "experts_gate", "experts_up", "experts_down")


def _sizes(cfg: dict) -> dict:
    if not cfg["norm_topk_prob"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["rope_scaling"]:
        raise ValueError("this reference writes down renormalised picks, "
                         "every layer sparse, no bias, an untied head and "
                         "an unscaled rotation")
    ep = cfg.get("expert_parallel", {"ep_size": 1, "ep_rank": 0})
    held = cfg["num_experts"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        eff=cfg["moe_intermediate_size"], held=held,
        first=int(ep["ep_rank"]) * held, routed=held * int(ep["ep_size"]),
        k=cfg["num_experts_per_tok"])


def generation(cfg: dict) -> tuple:
    """``(B, S, mask_token_id)`` as run, from the file's ``generation``."""
    g = cfg["generation"]
    if g["remasking"] != "sequential":
        raise ValueError(
            f"remasking {g['remasking']!r}: only the order of "
            f"'sequential' follows from the lengths; a rule by confidence "
            f"hangs on values that random weights put within rounding of "
            f"each other")
    return (int(g["block_length"]), int(g["denoising_steps"]),
            int(g["mask_token_id"]))


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, h, kv, hd = z["d"], z["heads"], z["kv"], z["hd"]
    attn = {"query/kernel": (d, h, hd), "key/kernel": (d, kv, hd),
            "value/kernel": (d, kv, hd), "q_norm/scale": (hd,),
            "k_norm/scale": (hd,), "out/kernel": (h, hd, d)}
    moe = {
        "router/kernel": (d, z["routed"]),
        # the held experts' matrices side by side, contracted axis first
        # (benchmark/lib/weights.py scales a kernel by shape[0]): expert
        # j is the column block [j * width, (j + 1) * width)
        "experts_gate": (d, z["held"] * z["eff"]),
        "experts_up": (d, z["held"] * z["eff"]),
        "experts_down": (z["eff"], z["held"] * d)}
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": cfg["num_hidden_layers"],
        "top": [("tok_embed/table", (cfg["vocab_size"], d)),
                ("final_norm/scale", (d,)),
                ("lm_head/kernel", (d, cfg["vocab_size"]))],
        "layer": ([("input_norm/scale", (d,))]
                  + [(f"attn/{n}", attn[n]) for n in _ATTN]
                  + [("post_attn_norm/scale", (d,))]
                  + [(f"moe/{n}", moe[n]) for n in _MOE]),
    }


def _int8(w, name: str):
    """Symmetric int8 with one scale per output channel (the last axis;
    per row for the token table), dequantised back to float32."""
    if w.ndim < 2:
        return w
    axes = (1,) if name.endswith("table") else tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _prep(flat: dict, quantize) -> dict:
    out = {}
    for name, w in flat.items():
        w = w.astype(jnp.float32)
        if quantize == "int8":
            w = _int8(w, name)
        elif quantize is not None:
            raise ValueError(f"unknown control precision {quantize!r}")
        out[name] = w
    return out


def _sub(flat: dict, prefix: str) -> dict:
    """The leaves under ``prefix/``, by their names below it."""
    return {k[len(prefix) + 1:]: v for k, v in flat.items()
            if k.startswith(prefix + "/")}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (N, heads, D) at positions ``pos`` (N,). Dim i turns with dim
    i + D/2, angle p * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- which step chose which token -----------------------------------------

def transfer_counts(B: int, S: int) -> list:
    """Places step ``t`` of a block unmasks, for t in range(S): the
    family's ``get_num_transfer_tokens``."""
    return [B // S + (t < B % S) for t in range(S)]


def filled_before(tail: int, B: int, S: int) -> list:
    """``f[t]``: the places of a block known when its step ``t`` runs,
    the block's first ``tail`` places being the prompt's; the last entry
    is B (the commit finds every place known). Under ``sequential`` step
    t chooses places ``[f[t], f[t + 1])``."""
    f, n = [tail], transfer_counts(B, S)
    while f[-1] < B:
        f.append(min(B, f[-1] + n[len(f) - 1]))
    return f


def steps_of(P: int, G: int, B: int, S: int) -> np.ndarray:
    """(G,): the step index, within its block, of the forward that chose
    each generated position ``P + i`` under ``sequential``."""
    out = np.zeros((G,), np.int64)
    for i in range(G):
        p = P + i
        block = p // B
        f = filled_before(P - block * B if block == P // B else 0, B, S)
        out[i] = max(t for t in range(len(f) - 1) if f[t] <= p - block * B)
    return out


def layout(tokens, first: int, B: int, S: int, mask_id: int) -> dict:
    """One request as the forward sees it. ``tokens`` are the prompt and
    the served tokens but the last, ``first + 1`` the prompt's length.
    Arrays over ``[committed ; copy 1 ; ... ; copy T]``: ``ids``,
    ``pos``, ``copy`` (0 the committed sequence, t + 1 the generated
    blocks as step t found them) and ``read`` (G,), each served token's
    row."""
    tokens = np.asarray(tokens, np.int64)
    P = first + 1
    G = len(tokens) - P + 1
    Lb = -(-(P + G) // B) * B            # whole blocks
    P0 = P // B * B
    clean = np.zeros((Lb,), np.int64)    # the last token is never read
    clean[:len(tokens)] = tokens
    step = steps_of(P, G, B, S)
    T = int(step.max()) + 1
    ids, pos, copy = [clean], [np.arange(Lb)], [np.zeros((Lb,), np.int64)]
    gen = np.arange(P0, Lb)
    for t in range(T):
        noised = clean[gen].copy()
        for c in range(P0, Lb, B):
            f = filled_before(P - P0 if c == P0 else 0, B, S)
            noised[c - P0 + f[min(t, len(f) - 1)]:c - P0 + B] = mask_id
        ids.append(noised)
        pos.append(gen)
        copy.append(np.full((len(gen),), t + 1))
    read = Lb + step * len(gen) + (P + np.arange(G) - P0)
    return dict(ids=np.concatenate(ids), pos=np.concatenate(pos),
                copy=np.concatenate(copy), read=read)


def visible(pos, copy, B: int):
    """(N, N) bool: what each row sees. A committed position sees the
    committed blocks up to its own; a position of copy t the committed
    blocks before its block and its own block of copy t; padding (copy
    < 0) itself alone."""
    blk = pos // B
    bi, bj = blk[:, None], blk[None, :]
    ci, cj = copy[:, None], copy[None, :]
    n = pos.shape[0]
    return jnp.where(
        ci == 0, (cj == 0) & (bj <= bi),
        jnp.where(ci > 0, ((cj == 0) & (bj < bi)) | ((cj == ci) & (bj == bi)),
                  jnp.eye(n, dtype=bool)))


# -- the layer --------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _attn(x, pos, copy, norm_scale, w, sizes, block, theta, eps, quantize):
    """x + Attn(N(x)) for one layout (N, d) under :func:`visible`."""
    z = dict(sizes)
    w = _prep(w, quantize)
    see = visible(pos, copy, block)
    a = _rms(x, norm_scale.astype(jnp.float32), eps)
    q = _rms(jnp.einsum("td,dhk->thk", a, w["query/kernel"]),
             w["q_norm/scale"], eps)
    k = _rms(jnp.einsum("td,dhk->thk", a, w["key/kernel"]),
             w["k_norm/scale"], eps)
    v = jnp.einsum("td,dhk->thk", a, w["value/kernel"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = z["heads"] // z["kv"]

    def one_head(args):
        qh, g = args                       # (N, hd), the head's kv head
        s = (qh @ k[:, g].T) * z["hd"] ** -0.5
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return p @ v[:, g]

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(z["heads"]) // group))
    return x + jnp.einsum("htk,hkd->td", o, w["out/kernel"])


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _moe(h, norm_scale, w, sizes, eps, quantize):
    """h + MoE(N(h)): this rank's held experts' part for tokens (N, d)."""
    z = dict(sizes)
    m = _rms(h, norm_scale.astype(jnp.float32), eps)
    scores = jax.nn.softmax(
        m @ _prep({"router/kernel": w["router/kernel"]},
                  quantize)["router/kernel"], axis=-1)
    picked, picks = jax.lax.top_k(scores, z["k"])
    picked = picked / picked.sum(axis=-1, keepdims=True)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], picks].set(picked)
    d, eff = m.shape[1], z["eff"]

    def one_expert(j, y):
        # an expert's column block, upcast (and rounded, in the control:
        # one scale an output channel, so a block's are the matrix's)
        # as it is used: the three matrices side by side are 1.2 GB in
        # the served type and would be 2.4 more upcast whole
        def cut(name, width):
            return _prep({name: jax.lax.dynamic_slice_in_dim(
                w[name], j * width, width, axis=1)}, quantize)[name]
        expert = (jax.nn.silu(m @ cut("experts_gate", eff))
                  * (m @ cut("experts_up", eff))) \
            @ cut("experts_down", d)
        return y + jax.lax.dynamic_slice_in_dim(
            weight, z["first"] + j, 1, axis=1) * expert

    return h + jax.lax.fori_loop(0, z["held"], one_expert, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tokens, table, quantize):
    return _prep({"tok_embed/table": table},
                 quantize)["tok_embed/table"][tokens]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(x, top, eps, quantize):
    w = _prep({k: top[k] for k in ("final_norm/scale", "lm_head/kernel")},
              quantize)
    return _rms(x, w["final_norm/scale"], eps) @ w["lm_head/kernel"]


def _bucket(n: int, floor: int = 128) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """What ``benchmark/lib/check.py`` compares: for each ``(tokens,
    first)`` of ``seqs`` the float32 logits, (served tokens, vocab) as
    numpy, of the row that chose each served token. Layers are the
    outer loop, so each layer's weights are made once for the whole
    sample; a layout is padded to a power of two with rows that see
    themselves alone and that nothing sees."""
    spec = param_spec(cfg)
    sizes = tuple(sorted(_sizes(cfg).items()))
    B, S, mask_id = generation(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        top = weights.top(seed, spec)
        lays, xs, copies, poss = [], [], [], []
        for tokens, first in seqs:
            lay = layout(tokens, first, B, S, mask_id)
            n = len(lay["ids"])
            pad = _bucket(n) - n
            ids = np.pad(lay["ids"], (0, pad))
            pos = jnp.asarray(np.pad(lay["pos"], (0, pad)), jnp.int32)
            copy = jnp.asarray(np.pad(lay["copy"], (0, pad),
                                      constant_values=-1), jnp.int32)
            lays.append(lay)
            poss.append(pos)
            copies.append(copy)
            xs.append(_embed(jnp.asarray(ids, jnp.int32),
                             top["tok_embed/table"], quantize))
        for i in range(int(spec["num_layers"])):
            w = weights.layer(seed, spec, i)
            for n, x in enumerate(xs):
                h = _attn(x, poss[n], copies[n], w["input_norm/scale"],
                          _sub(w, "attn"), sizes, B, theta, eps, quantize)
                xs[n] = _moe(h, w["post_attn_norm/scale"], _sub(w, "moe"),
                             sizes, eps, quantize)
        out = []
        for x, lay in zip(xs, lays):
            read = lay["read"]
            rows = np.resize(read, _bucket(len(read), 16))
            out.append(np.asarray(_head(x[jnp.asarray(rows)], top, eps,
                                        quantize))[:len(read)])
    return out
