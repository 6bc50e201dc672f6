"""Plain reference for ``jamba2_3b``.

AI21-Jamba2-3B's language model (``ai21labs/AI21-Jamba2-3B``
``config.json``, ``model_type`` ``jamba``), written down from
``transformers`` 4.57.6 ``models/jamba/modeling_jamba.py``. ``N`` is
RMSNorm with gain in float32, eps ``rms_norm_eps`` (``JambaRMSNorm``
lines 159-173); no projection has a bias except ``dt_proj`` and the
convolution. Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba otherwise (``configuration_jamba.py``
lines 216-220), and every layer's feed-forward is the dense ``JambaMLP``
(``num_experts`` 1; lines 912, 995)::

    h   = x + Mixer_i(N_in(x))           lines 960-975, 1043-1054
    out = h + SwiGLU(N_ff(h))            SwiGLU(y) = (silu(y W_g) * (y W_u)) W_d

    Mamba (``JambaMambaMixer.slow_forward``, lines 725-808), u = N_in(x):
        [x | z] = u W_in
        c_t  = silu(b_conv + sum_{j=0..K-1} w_conv[j] * x_{t-(K-1)+j})   x_{<0} = 0
        [r | B | C] = c W_x;  r, B, C = N_dt(r), N_b(B), N_c(C)
        dt   = softplus(r W_dt + b_dt)
        A    = -exp(A_log)                                  (d_inner, d_state)
        h_t  = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t[None, :]
        y_t  = h_t C_t + D * c_t
        out  = (y * silu(z)) W_out

    Attention (``JambaAttention``, lines 274-365): q, k, v = u W_q, u W_k,
        u W_v; no rotation, no position signal of any kind; s_ij = q_i . k_j
        / sqrt(head_dim) for j <= i, softmax in float32, every query head
        reads the one key-value head; out = concat(o) W_o

Embedding, the layers, ``final_layernorm``, and the head is the embedding
transposed (``tie_word_embeddings``).

Departures from that file, none of which changes a value in float32:

- the recurrence is a ``lax.scan`` of one position a step over a float32
  state; lines 793-797 loop in Python and cast the state to the model's
  type before ``h C`` (line 795), which in float32 is no cast;
- the convolution is the sum of its ``K`` shifted products (lines 754-762
  call ``nn.Conv1d`` with padding ``K - 1`` and drop the tail; its weight
  is ``(d_inner, 1, K)``, here ``(K, d_inner)``);
- matrices are ``(in, out)``, as the program's tree and
  ``benchmark/lib/weights.py`` have them (``nn.Linear`` keeps ``(out,
  in)``); attention runs a query head at a time against the one key-value
  head (lines 334-335 repeat it 20 times), so a 4,096-token request's
  scores stay at 67 MB;
- nothing is cached: the whole sequence goes through at once.

The parameter tree (``param_spec``) names the Mamba layers ``layer<i>`` in
model order and the attention layers ``attn<j>`` among the ``top``
leaves: ``benchmark/lib/weights.py`` draws every ``layer<i>`` from one
spec. Under its rules ``A_log`` and ``D`` draw as kernels (about 0, so
``A`` is about -1 in every channel and the skip term nearly absent) and
the step is ``softplus`` of a unit normal (~0.8): a state forgets in a
few positions (``PERF.md`` sec. 7). :func:`forward` takes any weights;
``tests/test_jamba.py`` gives it the program's own initialisers' draw,
in Mamba's ranges.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``. Weights are regenerated from the
seed a layer at a time in the served type and upcast, so the 12 GB the
model is in float32 never stand on the chip together.

``quantize="int8"`` is the served cell's control: every matrix rounded to
int8 with one scale per output channel (per row for the embedding).

Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights


def _sizes(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["intermediate_size"], heads=heads,
                kv=cfg["num_key_value_heads"], hd=d // heads,
                inner=cfg["mamba_expand"] * d, state=cfg["mamba_d_state"],
                conv=cfg["mamba_d_conv"], rank=cfg["mamba_dt_rank"])


def layer_kinds(cfg: dict) -> list:
    """``(name in the parameter tree, attention)`` of every layer."""
    if cfg["num_experts"] != 1:
        raise ValueError("this reference writes down the dense feed-forward "
                         "(num_experts 1) and no routed experts")
    out, n_attn = [], 0
    for i in range(cfg["num_hidden_layers"]):
        attention = \
            i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        out.append((f"attn{n_attn}" if attention else f"layer{i - n_attn}",
                    attention))
        n_attn += attention
    return out


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, ff, D, N, R = z["d"], z["ff"], z["inner"], z["state"], z["rank"]
    shared = [("input_norm/scale", (d,)), ("pre_ff_norm/scale", (d,)),
              ("mlp/gate_proj/kernel", (d, ff)),
              ("mlp/up_proj/kernel", (d, ff)),
              ("mlp/down_proj/kernel", (ff, d))]
    attn = [("attn/query/kernel", (d, z["heads"], z["hd"])),
            ("attn/key/kernel", (d, z["kv"], z["hd"])),
            ("attn/value/kernel", (d, z["kv"], z["hd"])),
            ("attn/out/kernel", (z["heads"], z["hd"], d))]
    mamba = [("mamba/in_proj/kernel", (d, 2 * D)),
             ("mamba/conv1d/kernel", (z["conv"], D)),
             ("mamba/conv1d/bias", (D,)),
             ("mamba/x_proj/kernel", (D, R + 2 * N)),
             ("mamba/dt_norm/scale", (R,)),
             ("mamba/b_norm/scale", (N,)),
             ("mamba/c_norm/scale", (N,)),
             ("mamba/dt_proj/kernel", (R, D)),
             ("mamba/dt_proj/bias", (D,)),
             ("mamba/A_log", (D, N)),
             ("mamba/D", (D,)),
             ("mamba/out_proj/kernel", (D, d))]
    kinds = layer_kinds(cfg)
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": sum(not a for _, a in kinds),
        "top": [("tok_embed/embedding", (cfg["vocab_size"], d)),
                ("final_norm/scale", (d,))]
        + [(f"{name}/{leaf}", shape) for name, a in kinds if a
           for leaf, shape in shared + attn],
        "layer": shared + mamba,
    }


def _int8(w, name: str):
    """Symmetric int8 with one scale per output channel (the last axis;
    per row for the embedding), dequantised back to float32; a matrix
    only (``kernel``, ``embedding``)."""
    last = name.rsplit("/", 1)[-1]
    if last not in ("kernel", "embedding") or w.ndim < 2:
        return w
    axes = (1,) if last == "embedding" else tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _prep(flat: dict, quantize) -> dict:
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown control precision {quantize!r}")
    out = {}
    for name, w in flat.items():
        w = w.astype(jnp.float32)
        out[name] = _int8(w, name) if quantize == "int8" else w
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mlp(h, w, eps):
    y = _rms(h, w["pre_ff_norm/scale"], eps)
    return h + (jax.nn.silu(y @ w["mlp/gate_proj/kernel"])
                * (y @ w["mlp/up_proj/kernel"])) @ w["mlp/down_proj/kernel"]


def mamba_mixer(u, w, eps):
    """The mixer over one sequence u (T, d), from zeros."""
    T = u.shape[0]
    x, z = jnp.split(u @ w["mamba/in_proj/kernel"], 2, axis=-1)
    K = w["mamba/conv1d/kernel"].shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(w["mamba/conv1d/bias"] + sum(
        w["mamba/conv1d/kernel"][j] * xp[j:j + T] for j in range(K)))
    R, N = w["mamba/dt_norm/scale"].shape[0], w["mamba/b_norm/scale"].shape[0]
    r, b, c_out = jnp.split(c @ w["mamba/x_proj/kernel"], [R, R + N], axis=-1)
    r = _rms(r, w["mamba/dt_norm/scale"], eps)
    b = _rms(b, w["mamba/b_norm/scale"], eps)
    c_out = _rms(c_out, w["mamba/c_norm/scale"], eps)
    dt = jax.nn.softplus(r @ w["mamba/dt_proj/kernel"]
                         + w["mamba/dt_proj/bias"])
    a = -jnp.exp(w["mamba/A_log"])                           # (D, N)

    def step(h, xs):
        dt_t, c_t, b_t, co_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * c_t)[:, None] * b_t[None, :]
        return h, h @ co_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (dt, c, b, c_out))
    y = y + w["mamba/D"] * c
    return (y * jax.nn.silu(z)) @ w["mamba/out_proj/kernel"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba_block(x, w, eps, quantize):
    w = _prep(w, quantize)
    h = x + mamba_mixer(_rms(x, w["input_norm/scale"], eps), w, eps)
    return _mlp(h, w, eps)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attn_block(x, w, eps, quantize):
    w = _prep(w, quantize)
    t = x.shape[0]
    u = _rms(x, w["input_norm/scale"], eps)
    q = jnp.einsum("td,dhk->htk", u, w["attn/query/kernel"])
    if w["attn/key/kernel"].shape[1] != 1:
        raise ValueError("this reference writes down one key-value head")
    k = u @ w["attn/key/kernel"][:, 0]
    v = u @ w["attn/value/kernel"][:, 0]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(qh):
        s = qh @ k.T * k.shape[-1] ** -0.5
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v

    o = jax.lax.map(one_head, q)                             # (H, T, hd)
    h = x + jnp.einsum("htk,hkd->td", o, w["attn/out/kernel"])
    return _mlp(h, w, eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tokens, table, quantize):
    return _prep({"tok_embed/embedding": table},
                 quantize)["tok_embed/embedding"][tokens]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(x, top, eps, quantize):
    w = _prep({k: top[k] for k in ("final_norm/scale",
                                   "tok_embed/embedding")}, quantize)
    return _rms(x, w["final_norm/scale"], eps) @ w["tok_embed/embedding"].T


def _bucket(n: int, floor: int = 128) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _sub(flat: dict, prefix: str) -> dict:
    return {k[len(prefix) + 1:]: v for k, v in flat.items()
            if k.startswith(prefix + "/")}


def forward(cfg: dict, top: dict, mamba_layer, seqs, quantize=None) -> list:
    """Float32 logits for each ``(tokens, first)`` of ``seqs``: the
    model's rows at positions ``first .. len-1`` of ``tokens`` (position
    p's row scores the token at p + 1), as numpy arrays (len - first,
    vocab). ``top`` holds the leaves outside the Mamba layers by name and
    ``mamba_layer(i)`` gives those of ``layer<i>``. Layers are the outer
    loop, so each layer's weights are made once for the whole sample. A
    sequence is padded to a power of two so that the sample shares a few
    compiled programs; attention, convolution and recurrence are causal,
    so the pad changes nothing before it."""
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        xs = []
        for tokens, _ in seqs:
            padded = np.zeros((_bucket(len(tokens)),), np.int32)
            padded[:len(tokens)] = np.asarray(tokens, np.int32)
            xs.append(_embed(jnp.asarray(padded),
                             top["tok_embed/embedding"], quantize))
        n_mamba = 0
        for name, attention in layer_kinds(cfg):
            if attention:
                w = _sub(top, name)
                xs = [_attn_block(x, w, eps, quantize) for x in xs]
            else:
                w = mamba_layer(n_mamba)
                n_mamba += 1
                xs = [_mamba_block(x, w, eps, quantize) for x in xs]
        out = []
        for x, (tokens, first) in zip(xs, seqs):
            n = len(tokens)
            # the scored rows, padded to a power of two as well
            rows = np.minimum(first + np.arange(_bucket(n - first, 16)),
                              n - 1)
            out.append(np.asarray(_head(x[jnp.asarray(rows)], top, eps,
                                        quantize))[:n - first])
    return out


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """What ``benchmark/lib/check.py`` compares: :func:`forward` on the
    weights of ``seed``."""
    spec = param_spec(cfg)
    return forward(cfg, weights.top(seed, spec),
                   lambda i: weights.layer(seed, spec, i), seqs, quantize)
