"""Plain reference for ``lfm2_8b_a1b``.

LFM2-8B-A1B's language model (``LiquidAI/LFM2-8B-A1B`` ``config.json``,
``model_type`` ``lfm2_moe``). The operators are written down from the
dense sibling's modelling file, ``transformers`` 4.57.6
``models/lfm2/modeling_lfm2.py`` (``Lfm2RMSNorm`` lines 44-62,
``Lfm2Attention`` 341-414, ``Lfm2ShortConv`` 417-525,
``Lfm2DecoderLayer`` 528-570); the expert layer is the family's
``Lfm2MoeSparseMoeBlock`` (``transformers`` >= 4.58, not in the
container: the configuration file lists it under ``assumed``). ``N`` is
RMSNorm with gain in float32, eps ``norm_eps``; no projection has a
bias. Layer ``i``::

    h   = x + Op_i(N_op(x))
    out = h + F_i(N_ffn(h))
    SwiGLU(y) = (silu(y W_gate) * (y W_up)) W_down

    Op_i, ``layer_types[i] == "conv"`` (K = conv_L_cache), u = N_op(x):
        [B | C | x] = u W_in
        z    = B * x
        c_t  = sum_{j=0..K-1} w[j] * z_{t-(K-1)+j}             z_{<0} = 0
        out  = (C * c) W_out
    Op_i, ``"full_attention"``: q, k, v = u W_q, u W_k, u W_v; q, k =
        N_q(q), N_k(k) over each head's 64 dims, one gain for all heads;
        q, k turned at their positions (dim i with dim i + 32, angle
        p * theta^(-2i/64)); s_ij = q_i . k_j / sqrt(64) for j <= i,
        softmax in float32; query head h reads key-value head h // 4;
        out = concat(o) W_o

    F_i, i < num_dense_layers:  SwiGLU at intermediate_size
    F_i, the others:  p = sigmoid(float32(y) W_r);  S = top_k(p + b)
        w_e = routed_scaling_factor * p_e / (sum_{e' in S} p_e' + 1e-20)
        F = sum_{e in S} w_e SwiGLU_e(y)         at moe_intermediate_size

The token table, the layers, ``embedding_norm``, and the head is the
table transposed.

Departures from the source, none of which changes a value in float32
but the second:

- the convolution is the sum of its ``K`` shifted products (lines
  480-484 call ``nn.Conv1d`` with padding ``K - 1`` and drop the tail;
  its weight is ``(d, 1, K)``, here ``(K, d)``);
- the renormalisation adds 1e-20 where the family adds 1e-6 (the
  program's module's, ``parallel/expert.py``): four sigmoid scores add
  up to more than 1e-2, so a weight moves by under 1e-4 relative;
- ``b`` (``expert_bias``, which chooses and does not weigh) is not drawn
  with the weights: ``bias`` gives it (per sparse layer), zeros
  otherwise, as ``ax_k1_ref.py`` holds its own;
- matrices are ``(in, out)``, as the program's tree and
  ``benchmark/lib/weights.py`` have them; the experts' side by side,
  expert j the column block ``[j * width, (j + 1) * width)``;
- every expert runs over every token and the unrouted are weighed by
  zero; attention runs a query head at a time, so a 4,096-token
  request's scores stay at 67 MB;
- nothing is cached: the whole sequence goes through at once.

The parameter tree (``param_spec``) names a layer by what it holds,
counted in model order within its kind: ``layer<j>`` a convolution
before experts, and among the ``top`` leaves ``attn<j>`` (attention
before experts) and ``dense<j>`` (a convolution before the dense
feed-forward): ``benchmark/lib/weights.py`` draws every ``layer<j>``
from one spec. Under its rules every matrix is N(0, 1 / fan-in), the
depthwise kernel ``(K, d)`` too (fan-in K: each tap about 0.58, the
three add a variance of 1), the gains 1 + 0.1 N(0, 1), and the token
table, a leaf called ``table``, N(0, 1 / vocab_size) as a kernel
(``models/sdar_moe.TokenTable`` says why it is not called
``embedding``): the tied head's logits have a deviation of about
sqrt(2048 / 65536) = 0.18. :func:`forward` takes any weights;
``tests/test_lfm2.py`` gives it the program's own initialisers' draw.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``. Weights are regenerated from
the seed a layer at a time in the served type and upcast.

``quantize="int8"`` is the served cell's control: every matrix rounded
to int8 with one scale per output channel (per row for the table).

Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights

_NAMES = {(False, True): "layer", (True, True): "attn",
          (False, False): "dense", (True, False): "dense_attn"}


def _sizes(cfg: dict) -> dict:
    if not cfg["norm_topk_prob"] or cfg.get("conv_bias"):
        raise ValueError("this reference writes down renormalised picks "
                         "and a convolution without bias")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["intermediate_size"],
                eff=cfg["moe_intermediate_size"], heads=heads,
                kv=cfg["num_key_value_heads"], hd=d // heads,
                conv=cfg["conv_L_cache"], experts=cfg["num_experts"],
                k=cfg["num_experts_per_tok"],
                scaling=float(cfg["routed_scaling_factor"]))


def layer_kinds(cfg: dict) -> list:
    """``(name in the parameter tree, attention, experts)`` of every
    layer: the head of ``layer_types``, experts from
    ``num_dense_layers`` on."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if len(kinds) < cfg["num_hidden_layers"]:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    out, seen = [], {}
    for i, kind in enumerate(kinds):
        key = (kind == "full_attention", i >= cfg["num_dense_layers"])
        out.append((f"{_NAMES[key]}{seen.get(key, 0)}", *key))
        seen[key] = seen.get(key, 0) + 1
    return out


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, hd = z["d"], z["hd"]
    norms = [("operator_norm/scale", (d,)), ("ffn_norm/scale", (d,))]
    conv = [("conv/in_proj/kernel", (d, 3 * d)),
            ("conv/conv/kernel", (z["conv"], d)),
            ("conv/out_proj/kernel", (d, d))]
    attn = [("attn/query/kernel", (d, z["heads"], hd)),
            ("attn/key/kernel", (d, z["kv"], hd)),
            ("attn/value/kernel", (d, z["kv"], hd)),
            ("attn/q_norm/scale", (hd,)), ("attn/k_norm/scale", (hd,)),
            ("attn/out/kernel", (z["heads"], hd, d))]
    dense = [("ffn/gate_proj/kernel", (d, z["ff"])),
             ("ffn/up_proj/kernel", (d, z["ff"])),
             ("ffn/down_proj/kernel", (z["ff"], d))]
    # the experts' matrices side by side, contracted axis first
    # (benchmark/lib/weights.py scales a kernel by shape[0])
    moe = [("moe/router/kernel", (d, z["experts"])),
           ("moe/experts_gate", (d, z["experts"] * z["eff"])),
           ("moe/experts_up", (d, z["experts"] * z["eff"])),
           ("moe/experts_down", (z["eff"], z["experts"] * d))]
    kinds = layer_kinds(cfg)
    if any(a and not s for _, a, s in kinds):
        raise ValueError("attention before a dense feed-forward: no "
                         "published model of the family has one")
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": sum(not a and s for _, a, s in kinds),
        "top": [("tok_embed/table", (cfg["vocab_size"], d)),
                ("embedding_norm/scale", (d,))]
        + [(f"{name}/{leaf}", shape) for name, a, s in kinds
           if a or not s
           for leaf, shape in norms + (attn if a else conv)
           + (moe if s else dense)],
        "layer": norms + conv + moe,
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


def _rope(x, theta):
    """x: (T, heads, D) at positions 0 .. T-1. Dim i turns with dim
    i + D/2, angle p * theta^(-2i/D)."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(u, w):
    """The operator over one sequence u (T, d), from zeros."""
    T = u.shape[0]
    gate_in, gate_out, x = jnp.split(u @ w["in_proj/kernel"], 3, axis=-1)
    z = gate_in * x
    K = w["conv/kernel"].shape[0]
    zp = jnp.pad(z, ((K - 1, 0), (0, 0)))
    c = sum(w["conv/kernel"][j] * zp[j:j + T] for j in range(K))
    return (gate_out * c) @ w["out_proj/kernel"]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _conv_op(x, norm_scale, w, eps, quantize):
    """x + ShortConv(N(x)) for one sequence (T, d)."""
    w = _prep(w, quantize)
    return x + short_conv(_rms(x, norm_scale.astype(jnp.float32), eps), w)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _attn_op(x, norm_scale, w, sizes, theta, eps, quantize):
    """x + Attn(N(x)) for one sequence (T, d)."""
    z = dict(sizes)
    w = _prep(w, quantize)
    t = x.shape[0]
    u = _rms(x, norm_scale.astype(jnp.float32), eps)
    q = _rms(jnp.einsum("td,dhk->thk", u, w["query/kernel"]),
             w["q_norm/scale"], eps)
    k = _rms(jnp.einsum("td,dhk->thk", u, w["key/kernel"]),
             w["k_norm/scale"], eps)
    v = jnp.einsum("td,dhk->thk", u, w["value/kernel"])
    q, k = _rope(q, theta), _rope(k, theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = z["heads"] // z["kv"]

    def one_head(args):
        qh, g = args                       # (T, hd), the head's kv head
        s = (qh @ k[:, g].T) * z["hd"] ** -0.5
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) \
            @ v[:, g]

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(z["heads"]) // group))
    return x + jnp.einsum("htk,hkd->td", o, w["out/kernel"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense_ffn(h, norm_scale, w, eps, quantize):
    w = _prep(w, quantize)
    y = _rms(h, norm_scale.astype(jnp.float32), eps)
    return h + (jax.nn.silu(y @ w["gate_proj/kernel"])
                * (y @ w["up_proj/kernel"])) @ w["down_proj/kernel"]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _moe(h, norm_scale, w, bias, sizes, eps, quantize):
    """(h + the experts' result, picks (T, k))."""
    z = dict(sizes)
    w = _prep(w, quantize)
    y = _rms(h, norm_scale.astype(jnp.float32), eps)
    scores = jax.nn.sigmoid(y @ w["router/kernel"])
    picks = jnp.argsort(-(scores + bias), axis=-1)[:, :z["k"]]
    picked = jnp.take_along_axis(scores, picks, axis=1)
    picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) \
        * z["scaling"]
    weight = jnp.zeros_like(scores).at[
        jnp.arange(y.shape[0])[:, None], picks].set(picked)
    out = jnp.zeros_like(y)
    d, eff = y.shape[1], z["eff"]
    for j in range(z["experts"]):
        cols = slice(j * eff, (j + 1) * eff)
        expert = (jax.nn.silu(y @ w["experts_gate"][:, cols])
                  * (y @ w["experts_up"][:, cols])) \
            @ w["experts_down"][:, j * d:(j + 1) * d]
        out = out + weight[:, j, None] * expert
    return h + out, picks


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tokens, table, quantize):
    return _prep({"tok_embed/table": table}, quantize)[
        "tok_embed/table"][tokens]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(x, top, eps, quantize):
    w = _prep({k: top[k] for k in ("embedding_norm/scale",
                                   "tok_embed/table")}, quantize)
    return _rms(x, w["embedding_norm/scale"], eps) @ w["tok_embed/table"].T


def _bucket(n: int, floor: int = 128) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def forward(cfg: dict, top: dict, conv_layer, seqs, quantize=None,
            bias=None) -> tuple:
    """``(logits, picks)`` for each ``(tokens, first)`` of ``seqs``:
    float32 logits of positions ``first .. len-1`` of ``tokens``
    (position p's row scores the token at p + 1) as numpy arrays (len -
    first, vocab), and the experts every token picked in every sparse
    layer, (sparse layers, len, k). ``top`` holds the leaves outside the
    ``layer<j>`` by name and ``conv_layer(j)`` gives those of
    ``layer<j>``. Layers are the outer loop, so each layer's weights are
    made once for the whole sample. A sequence is padded to a power of
    two so that the sample shares a few compiled programs; attention and
    convolution are causal and no token's experts depend on another's,
    so the pad changes nothing before it. ``bias``: the selection bias,
    (sparse layers, experts), or None for zeros."""
    z = _sizes(cfg)
    sizes = tuple(sorted(z.items()))
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        xs, picks = [], [[] for _ in seqs]
        for tokens, _ in seqs:
            padded = np.zeros((_bucket(len(tokens)),), np.int32)
            padded[:len(tokens)] = np.asarray(tokens, np.int32)
            xs.append(_embed(jnp.asarray(padded), top["tok_embed/table"],
                             quantize))
        n_conv = n_sparse = 0
        for name, attention, sparse in layer_kinds(cfg):
            if attention or not sparse:
                w = _sub(top, name)
            else:
                w = conv_layer(n_conv)
                n_conv += 1
            b = None
            if sparse:
                b = jnp.zeros((z["experts"],), jnp.float32) if bias is None \
                    else jnp.asarray(bias[n_sparse], jnp.float32)
                n_sparse += 1
            for n, x in enumerate(xs):
                if attention:
                    h = _attn_op(x, w["operator_norm/scale"], _sub(w, "attn"),
                                 sizes, theta, eps, quantize)
                else:
                    h = _conv_op(x, w["operator_norm/scale"], _sub(w, "conv"),
                                 eps, quantize)
                if sparse:
                    xs[n], p = _moe(h, w["ffn_norm/scale"], _sub(w, "moe"),
                                    b, sizes, eps, quantize)
                    picks[n].append(np.asarray(p)[:len(seqs[n][0])])
                else:
                    xs[n] = _dense_ffn(h, w["ffn_norm/scale"],
                                       _sub(w, "ffn"), eps, quantize)
        out = []
        for x, (tokens, first) in zip(xs, seqs):
            n = len(tokens)
            # the scored rows, padded to a power of two as well
            rows = np.minimum(first + np.arange(_bucket(n - first, 16)),
                              n - 1)
            out.append(np.asarray(_head(x[jnp.asarray(rows)], top, eps,
                                        quantize))[:n - first])
    return out, [np.stack(p) if p else np.zeros((0, len(s[0]), z["k"]), int)
                 for p, s in zip(picks, seqs)]


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """What ``benchmark/lib/check.py`` compares: :func:`forward`'s
    logits on the weights of ``seed``."""
    spec = param_spec(cfg)
    return forward(cfg, weights.top(seed, spec),
                   lambda i: weights.layer(seed, spec, i), seqs, quantize)[0]
