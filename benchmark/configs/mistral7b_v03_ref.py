"""Plain reference for ``mistral7b_v03`` (and any config of its family).

The Mistral-7B decoder as published (Jiang et al. 2023; the v0.3
``config.json``): token embedding; per layer RMSNorm -> grouped-query
causal attention with rotary embeddings (half-split pairing, as the HF
implementation applies it) -> residual, RMSNorm -> SwiGLU MLP ->
residual; final RMSNorm; untied LM head. No sliding window (v0.3 sets
none), no biases.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no cache, no batching, no
kernels. Weights are regenerated from the seed one layer at a time
(``benchmark/lib/weights.py``) in the type the configuration serves
them in, and upcast, so the whole model never sits in memory in
float32 and nothing the program made is read. Attention runs one
key/value head at a time so that a 4096-token sequence's scores stay
under 300 MB.

``quantize="int8"`` is the control of the served cells: the same
forward with every matrix rounded to int8 with one scale per output
channel (and per row of the embedding), the step below bfloat16 that
would tempt a later PR. It must come out as not correct.

Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights


def param_spec(cfg: dict) -> dict:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": cfg["num_hidden_layers"],
        "top": [("tok_embed/embedding", (cfg["vocab_size"], d)),
                ("final_norm/scale", (d,)),
                ("lm_head/kernel", (d, cfg["vocab_size"]))],
        "layer": [("attn_norm/scale", (d,)),
                  ("attn/query/kernel", (d, h, hd)),
                  ("attn/key/kernel", (d, kv, hd)),
                  ("attn/value/kernel", (d, kv, hd)),
                  ("attn/out/kernel", (h, hd, d)),
                  ("mlp_norm/scale", (d,)),
                  ("gate_proj/kernel", (d, ff)),
                  ("up_proj/kernel", (d, ff)),
                  ("down_proj/kernel", (ff, d))],
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, heads, D). Pairs (i, i + D/2), angle t * theta^(-2i/D)."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, theta, eps, quantize):
    w = _prep(w, quantize)
    t = x.shape[0]
    h = _rms(x, w["attn_norm/scale"], eps)
    q = _rope(jnp.einsum("td,dhk->thk", h, w["attn/query/kernel"]), theta)
    k = _rope(jnp.einsum("td,dhk->thk", h, w["attn/key/kernel"]), theta)
    v = jnp.einsum("td,dhk->thk", h, w["attn/value/kernel"])
    n_kv, hd = k.shape[1], k.shape[2]
    group = q.shape[1] // n_kv
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(args):
        qg, kh, vh = args            # (T, G, D), (T, D), (T, D)
        s = jnp.einsum("tgd,sd->gts", qg, kh) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vh)

    qg = q.reshape(t, n_kv, group, hd).transpose(1, 0, 2, 3)
    o = jax.lax.map(one_kv_head,
                    (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(t, n_kv * group, hd)
    x = x + jnp.einsum("thk,hkd->td", o, w["attn/out/kernel"])
    h = _rms(x, w["mlp_norm/scale"], eps)
    gate = h @ w["gate_proj/kernel"]
    up = h @ w["up_proj/kernel"]
    return x + (jax.nn.silu(gate) * up) @ w["down_proj/kernel"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(tokens, top, eps, quantize):
    del eps
    return _prep({"tok_embed/embedding": top["tok_embed/embedding"]},
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


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """Float32 logits for each ``(tokens, first)`` of ``seqs``: the
    model's rows at positions ``first .. len-1`` of ``tokens`` (position
    p's row scores the token at p + 1), as numpy arrays
    (len - first, vocab). Layers are the outer loop, so each layer's
    weights are made once for the whole sample. A sequence is padded to
    a power of two so that the sample shares a few compiled programs;
    attention is causal, so the pad changes nothing before it."""
    spec = param_spec(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        top = weights.top(seed, spec)
        xs = []
        for tokens, _ in seqs:
            padded = np.zeros((_bucket(len(tokens)),), np.int32)
            padded[:len(tokens)] = np.asarray(tokens, np.int32)
            xs.append(_embed(jnp.asarray(padded), top, eps, quantize))
        for i in range(spec["num_layers"]):
            w = weights.layer(seed, spec, i)
            xs = [_block(x, w, theta, eps, quantize) for x in xs]
        out = []
        for x, (tokens, first) in zip(xs, seqs):
            n = len(tokens)
            # the scored rows, padded to a power of two as well
            rows = np.minimum(first + np.arange(_bucket(n - first, 16)),
                              n - 1)
            out.append(np.asarray(_head(x[jnp.asarray(rows)], top, eps,
                                        quantize))[:n - first])
    return out
