"""Plain reference for ``k_exaone_236b`` (one expert-parallel rank).

K-EXAONE-236B-A23B's language model (``LGAI-EXAONE/K-EXAONE-236B-A23B``
``config.json`` for the sizes and the routing keys). What the config
does not say is the family's convention, read from the code the same
organisation published for the generation before (``transformers``
4.57.6 ``models/exaone4/modeling_exaone4.py``: ``Exaone4Attention``,
``Exaone4DecoderLayer``) and from the router whose key names the config
repeats (``models/deepseek_v3/modeling_deepseek_v3.py``:
``DeepseekV3TopkRouter``, ``DeepseekV3MoE``); the configuration file
lists each such line under ``assumed``. ``N`` is RMSNorm with gain in
float32, eps ``rms_norm_eps``; no projection has a bias. Layer ``l``::

    h   = x + N_post_attn(Attn_l(x))        no norm before a sublayer
    out = h + N_post_ffn(FFN_l(h))
    SwiGLU(y) = (silu(y W_gate) * (y W_up)) W_down

    Attn_l: q = x W_q -> heads x head_dim; k, v = x W_k, x W_v -> kv heads
            q = N_q(q), k = N_k(k)          over each head's dims, one gain
            sliding layers: q, k = RoPE(q, k), half rotation (i with
                            i + head_dim / 2), angle t * theta^(-2i/head_dim)
            full layers:    no rotation
            s_ij = q_i . k_j / sqrt(head_dim) for j <= i, and in a sliding
                   layer only for i - j < window (the query's own key
                   among the window's)
            softmax in float32, o = p v; query heads of one group read
            one kv head; out = concat(o) W_o

    FFN_l, dense layers:  SwiGLU at intermediate_size
    FFN_l, sparse layers: z = float32(h) W_r;  s = sigmoid(z)
            S = top_k(s + b)                b chooses and does not weigh
            w_e = scaling * s_e / (sum_{e' in S} s_e' + 1e-20)
            y = SwiGLU_shared(h) + sum_{e in S} w_e SwiGLU_e(h)

Embedding, the layers, final ``N``, untied head. The multi-token
prediction module is not part of the main model's logits and is not
built.

The configuration is one rank's share of an expert-parallel deployment
(``expert_parallel``: ``ep_size``, ``ep_rank``; ``num_experts`` is what
the rank holds): the router keeps its whole width, ``num_experts *
ep_size``, the denominator runs over all of a token's picks, and the
terms of the experts held elsewhere are left out, here as in the
program. ``b`` is not drawn with the weights: ``bias`` gives it (per
sparse layer), zeros otherwise.

The parameter tree (``param_spec``) names the leading dense layers
``dense<j>`` among the ``top`` leaves and the sparse layers ``layer<i>``
(model layer ``first_k_dense_replace + i``): ``benchmark/lib/weights.py``
draws every ``layer<i>`` from one spec.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: full masks (causal, and causal
and banded), no cache, no ring, no batching, no gathering of tokens by
expert (every held expert runs over every token and the unrouted are
weighed by zero). Weights are regenerated from the seed a layer at a
time in the served type and upcast a sublayer at a time; attention runs
a head at a time, so a 4,096-token request's scores stay at 67 MB.

``quantize="int8"`` is the served cells' control: every matrix rounded
to int8 with one scale per output channel (per row for the embedding).

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
_FFN = ("gate_proj/kernel", "up_proj/kernel", "down_proj/kernel")
_MOE = ("router/kernel", "experts_gate", "experts_up", "experts_down")


def _sizes(cfg: dict) -> dict:
    ep = cfg.get("expert_parallel", {"ep_size": 1, "ep_rank": 0})
    held = cfg["num_experts"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        ff=cfg["intermediate_size"], eff=cfg["moe_intermediate_size"],
        shared=cfg["num_shared_experts"], held=held,
        first=int(ep["ep_rank"]) * held, routed=held * int(ep["ep_size"]),
        k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]))


def layer_kinds(cfg: dict) -> list:
    """``(name in the parameter tree, window or 0, dense)`` of every
    layer, from ``layer_types``, ``sliding_windows`` and
    ``mlp_layer_types``."""
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this reference writes down the sigmoid router "
                         "with renormalised picks and no expert groups")
    n = cfg["num_hidden_layers"]
    dense = [t == "dense" for t in cfg["mlp_layer_types"][:n]]
    lead = cfg["first_k_dense_replace"]
    if dense != [i < lead for i in range(n)]:
        raise ValueError("dense layers must be the leading ones")
    out = []
    for i in range(n):
        window = int(cfg["sliding_windows"][i]) \
            if cfg["layer_types"][i] == "sliding_attention" else 0
        out.append((f"dense{i}" if dense[i] else f"layer{i - lead}",
                    window, dense[i]))
    return out


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, h, kv, hd = z["d"], z["heads"], z["kv"], z["hd"]
    attn = {"query/kernel": (d, h, hd), "key/kernel": (d, kv, hd),
            "value/kernel": (d, kv, hd), "q_norm/scale": (hd,),
            "k_norm/scale": (hd,), "out/kernel": (h, hd, d)}

    def ffn(width):
        return {"gate_proj/kernel": (d, width), "up_proj/kernel": (d, width),
                "down_proj/kernel": (width, d)}
    moe = {
        "router/kernel": (d, z["routed"]),
        # the held experts' matrices side by side, contracted axis first
        # (benchmark/lib/weights.py scales a kernel by shape[0]): expert
        # j is the column block [j * width, (j + 1) * width)
        "experts_gate": (d, z["held"] * z["eff"]),
        "experts_up": (d, z["held"] * z["eff"]),
        "experts_down": (z["eff"], z["held"] * d)}

    def block(ffn_name, ffn_leaves, extra=()):
        return ([(f"attn/{n}", attn[n]) for n in _ATTN]
                + [("post_attn_norm/scale", (d,))]
                + [(f"{ffn_name}/{n}", ffn_leaves[n]) for n in _FFN]
                + list(extra) + [("post_ffn_norm/scale", (d,))])
    top = [("tok_embed/embedding", (cfg["vocab_size"], d)),
           ("final_norm/scale", (d,)),
           ("lm_head/kernel", (d, cfg["vocab_size"]))]
    lead = cfg["first_k_dense_replace"]
    for j in range(lead):
        top += [(f"dense{j}/{n}", s) for n, s in block("ffn", ffn(z["ff"]))]
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": cfg["num_hidden_layers"] - lead,
        "top": top,
        "layer": block("shared_expert", ffn(z["eff"] * z["shared"]),
                       [(f"moe/{n}", moe[n]) for n in _MOE]),
    }


def _int8(w, name: str):
    """Symmetric int8 with one scale per output channel (the last axis;
    per row for the embedding), dequantised back to float32."""
    if w.ndim < 2:
        return w
    axes = (1,) if name.endswith("embedding") else tuple(range(w.ndim - 1))
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


def _rope(x, theta):
    """x: (T, heads, D). Dim i turns with dim i + D/2, angle
    t * theta^(-2i/D)."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask(t: int, window: int):
    """(t, t) bool: key j is visible to query i iff j <= i and, in a
    sliding layer, i - j < window."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    return (j <= i) & ((i - j < window) if window else True)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _attn(x, norm_scale, w, sizes, window, theta, eps, quantize):
    """x + N(Attn(x)) for one sequence (T, d)."""
    z = dict(sizes)
    w = _prep(w, quantize)
    t = x.shape[0]
    q = _rms(jnp.einsum("td,dhk->thk", x, w["query/kernel"]),
             w["q_norm/scale"], eps)
    k = _rms(jnp.einsum("td,dhk->thk", x, w["key/kernel"]),
             w["k_norm/scale"], eps)
    v = jnp.einsum("td,dhk->thk", x, w["value/kernel"])
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    visible = mask(t, window)
    group = z["heads"] // z["kv"]

    def one_head(args):
        qh, g = args                       # (T, hd), the head's kv head
        s = (qh @ k[:, g].T) * z["hd"] ** -0.5
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return p @ v[:, g]

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(z["heads"]) // group))
    a = jnp.einsum("htk,hkd->td", o, w["out/kernel"])
    return x + _rms(a, norm_scale.astype(jnp.float32), eps)


def _swiglu(y, w):
    return (jax.nn.silu(y @ w["gate_proj/kernel"])
            * (y @ w["up_proj/kernel"])) @ w["down_proj/kernel"]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense_ffn(h, w, norm_scale, eps, quantize):
    f = _swiglu(h, _prep(w, quantize))
    return h + _rms(f, norm_scale.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _moe(h, w, bias, sizes, quantize):
    """(y, picks): this rank's part of the routed experts' result for
    tokens ``h`` (T, d), without the shared expert, and which experts
    each token picked, (T, k)."""
    z = dict(sizes)
    w = _prep(w, quantize)
    scores = jax.nn.sigmoid(h @ w["router/kernel"])
    _, picks = jax.lax.top_k(scores + bias, z["k"])
    picked = jnp.take_along_axis(scores, picks, axis=1)
    picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) \
        * z["scaling"]
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], picks].set(picked)
    y = jnp.zeros_like(h)
    d, eff = h.shape[1], z["eff"]
    for j in range(z["held"]):
        cols = slice(j * eff, (j + 1) * eff)
        expert = (jax.nn.silu(h @ w["experts_gate"][:, cols])
                  * (h @ w["experts_up"][:, cols])) \
            @ w["experts_down"][:, j * d:(j + 1) * d]
        y = y + weight[:, z["first"] + j, None] * expert
    return y, picks


@functools.partial(jax.jit, static_argnums=(4, 5))
def _sparse_join(h, routed, w_shared, norm_scale, eps, quantize):
    f = routed + _swiglu(h, _prep(w_shared, quantize))
    return h + _rms(f, norm_scale.astype(jnp.float32), eps)


def _layer(x, w, bias, sizes, window, dense, theta, eps, quantize):
    """One layer over one sequence; returns (out, picks or None)."""
    h = _attn(x, w["post_attn_norm/scale"], _sub(w, "attn"), sizes, window,
              theta, eps, quantize)
    if dense:
        return _dense_ffn(h, _sub(w, "ffn"), w["post_ffn_norm/scale"], eps,
                          quantize), None
    routed, picks = _moe(h, _sub(w, "moe"), bias, sizes, quantize)
    return _sparse_join(h, routed, _sub(w, "shared_expert"),
                        w["post_ffn_norm/scale"], eps, quantize), picks


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tokens, table, quantize):
    return _prep({"tok_embed/embedding": table},
                 quantize)["tok_embed/embedding"][tokens]


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


def forward(cfg: dict, seed: int, seqs, quantize=None, bias=None) -> tuple:
    """``(logits, picks)`` for each ``(tokens, first)`` of ``seqs``:
    float32 logits of positions ``first .. len-1`` as numpy arrays
    (len - first, vocab), and the experts every token picked in every
    sparse layer, (sparse layers, len, k). Layers are the outer loop, so
    each layer's weights are made once for the whole sample; a sequence
    is padded to a power of two (the masks are causal and no token's
    experts depend on another's, so the pad changes nothing before it).
    ``bias``: the selection bias, (sparse layers, routed), or None for
    zeros."""
    spec = param_spec(cfg)
    z = _sizes(cfg)
    sizes = tuple(sorted(z.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        top = weights.top(seed, spec)
        xs, picks = [], [[] for _ in seqs]
        for tokens, _ in seqs:
            padded = np.zeros((_bucket(len(tokens)),), np.int32)
            padded[:len(tokens)] = np.asarray(tokens, np.int32)
            xs.append(_embed(jnp.asarray(padded), top["tok_embed/embedding"],
                             quantize))
        sparse = 0
        for name, window, dense in layer_kinds(cfg):
            if dense:
                w, b = _sub(top, name), None
            else:
                w = weights.layer(seed, spec, sparse)
                b = jnp.zeros((z["routed"],), jnp.float32) if bias is None \
                    else jnp.asarray(bias[sparse], jnp.float32)
                sparse += 1
            for n, x in enumerate(xs):
                xs[n], p = _layer(x, w, b, sizes, window, dense, theta, eps,
                                  quantize)
                if p is not None:
                    picks[n].append(np.asarray(p)[:len(seqs[n][0])])
        out = []
        for x, (tokens, first) in zip(xs, seqs):
            n = len(tokens)
            rows = np.minimum(first + np.arange(_bucket(n - first, 16)),
                              n - 1)
            out.append(np.asarray(_head(x[jnp.asarray(rows)], top, eps,
                                        quantize))[:n - first])
    return out, [np.stack(p) for p in picks]


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """What ``benchmark/lib/check.py`` compares: :func:`forward`'s
    logits."""
    return forward(cfg, seed, seqs, quantize)[0]


def routing_counts(cfg: dict, picks) -> np.ndarray:
    """Per sparse layer ``[picks, pairs on held experts, distinct held
    experts picked]`` of one sequence's ``picks`` (layers, T, k)."""
    z = _sizes(cfg)
    held = (picks >= z["first"]) & (picks < z["first"] + z["held"])
    return np.stack([
        np.full(len(picks), picks[0].size), held.sum(axis=(1, 2)),
        [len(np.unique(p[m])) for p, m in zip(picks, held)]], axis=1)


def attended_counts(cfg: dict, length: int) -> np.ndarray:
    """Per layer, the (query, key) pairs inside the layer's mask for a
    sequence of ``length`` tokens."""
    return np.asarray([int(np.asarray(mask(length, window)).sum())
                       for _, window, _ in layer_kinds(cfg)])
