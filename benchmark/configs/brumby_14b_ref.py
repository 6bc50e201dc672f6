"""Plain reference for ``brumby_14b``.

Brumby-14B-Base's language model (``manifestai/Brumby-14B-Base``
``config.json``, ``model_type`` ``brumby``): the 14B grouped-query
decoder it was retrained from with every attention replaced by gated
power retention (Gelada, Buckman, Zhang, Bach, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239; the ``retention`` package's
``power_retention(Q, K, V, log_G, deg, ...)``). ``N`` is RMSNorm with
gain in float32, eps ``rms_norm_eps``; no projection has a bias::

    h   = x + Ret(N_in(x))
    out = h + SwiGLU(N_ff(h))            SwiGLU(y) = (silu(y W_g) * (y W_u)) W_d

    Ret, u = N_in(x), key-value head j, a query head i of its group:
        q_t = Rot_t(N_q(u_t W_q^i))    k_t = Rot_t(N_k(u_t W_k^j))    v_t = u_t W_v^j
        log g_t = logsigmoid(u_t W_gate^j)
        a_ts = (q_t . k_s)^2 * exp(sum_{r=s+1..t} log g_r)     s <= t
        y_t  = sum_s a_ts v_s / (sum_s a_ts + 1e-6)
        out  = concat_i(y^i) W_o

Embedding, the layers, the final norm, an untied head. What the
``config.json`` does not say (degree 2, the gate, the normaliser, the
rotation and the per-head norms kept from the decoder) is listed in
``brumby_14b.json`` under ``assumed``.

**This is the ``a_ts`` form**: scores squared under a decay mask,
normalised, a block of queries against every key before them. It builds
no state: the program serves the recurrence (``phi``, a state of 8,704 x
128 a head, chunks), so the two share no formulation, and
``phi(q) . phi(k) = (q . k)^2`` is checked by their agreement.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``. Matrices are ``(in, out)``, as
the program's tree and ``benchmark/lib/weights.py`` have them. Weights
are regenerated from the seed a layer at a time in the served type and
upcast. Nothing is cached: the whole sequence goes through at once.

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

EPS = 1e-6          # the normaliser's (``assumed.normaliser``)
QUERY_BLOCK = 1024  # queries scored at once against every key


def _sizes(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["intermediate_size"], heads=heads,
                kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // heads))


def param_spec(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, ff, h, kv, hd = z["d"], z["ff"], z["heads"], z["kv"], z["hd"]
    return {
        "dtype": cfg["torch_dtype"],
        "num_layers": cfg["num_hidden_layers"],
        "top": [("tok_embed/embedding", (cfg["vocab_size"], d)),
                ("final_norm/scale", (d,)),
                ("lm_head/kernel", (d, cfg["vocab_size"]))],
        "layer": [("input_norm/scale", (d,)),
                  ("ret/query/kernel", (d, h, hd)),
                  ("ret/key/kernel", (d, kv, hd)),
                  ("ret/value/kernel", (d, kv, hd)),
                  ("ret/q_norm/scale", (hd,)),
                  ("ret/k_norm/scale", (hd,)),
                  ("ret/gate/kernel", (d, kv)),
                  ("ret/out/kernel", (h, hd, d)),
                  ("mlp_norm/scale", (d,)),
                  ("mlp/gate_proj/kernel", (d, ff)),
                  ("mlp/up_proj/kernel", (d, ff)),
                  ("mlp/down_proj/kernel", (ff, d))],
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


def _rope(x, theta):
    """x (T, heads, D) at positions 0 .. T-1. Dim i turns with dim i +
    D/2, angle p * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(u, w, theta, eps, block: int = QUERY_BLOCK):
    """The mixer over one sequence u (T, d), in the ``a_ts`` form."""
    T = u.shape[0]
    q = _rope(_rms(jnp.einsum("td,dhk->thk", u, w["ret/query/kernel"]),
                   w["ret/q_norm/scale"], eps), theta)
    k = _rope(_rms(jnp.einsum("td,dhk->thk", u, w["ret/key/kernel"]),
                   w["ret/k_norm/scale"], eps), theta)
    v = jnp.einsum("td,dhk->thk", u, w["ret/value/kernel"])
    # sum_{r <= t} log g_r, a column a key-value head
    cum = jnp.cumsum(jax.nn.log_sigmoid(u @ w["ret/gate/kernel"]), axis=0)
    group = q.shape[1] // k.shape[1]
    block = min(block, T)
    at = jnp.arange(T)

    def one_head(args):
        qh, j = args                                   # (T, hd), its kv head
        kj, vj, cj = k[:, j], v[:, j], cum[:, j]

        def one_block(first):
            t = first + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(qh, first, block)
            ct = jax.lax.dynamic_slice_in_dim(cj, first, block)
            s = qb @ kj.T                              # (block, T)
            a = s * s * jnp.exp(jnp.where(
                at[None, :] <= t[:, None], ct[:, None] - cj[None, :],
                -jnp.inf))
            return (a @ vj) / (a.sum(axis=-1, keepdims=True) + EPS)

        return jax.lax.map(one_block, jnp.arange(0, T, block)) \
            .reshape(T, -1)

    y = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(q.shape[1]) // group))
    return jnp.einsum("htk,hkd->td", y, w["ret/out/kernel"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(x, w, theta, eps, quantize):
    w = _prep(w, quantize)
    h = x + retention(_rms(x, w["input_norm/scale"], eps), w, theta, eps)
    y = _rms(h, w["mlp_norm/scale"], eps)
    return h + (jax.nn.silu(y @ w["mlp/gate_proj/kernel"])
                * (y @ w["mlp/up_proj/kernel"])) @ w["mlp/down_proj/kernel"]


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


def forward(cfg: dict, top: dict, layer, seqs, quantize=None) -> list:
    """Float32 logits for each ``(tokens, first)`` of ``seqs``: the
    model's rows at positions ``first .. len-1`` of ``tokens`` (position
    p's row scores the token at p + 1), as numpy arrays (len - first,
    vocab). ``top`` holds the leaves outside the layers by name and
    ``layer(i)`` gives those of ``layer<i>``. Layers are the outer loop,
    so each layer's weights are made once for the whole sample. A
    sequence is padded to a power of two so that the sample shares a few
    compiled programs; the mask is causal, so the pad changes nothing
    before it."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        xs = []
        for tokens, _ in seqs:
            padded = np.zeros((_bucket(len(tokens)),), np.int32)
            padded[:len(tokens)] = np.asarray(tokens, np.int32)
            xs.append(_embed(jnp.asarray(padded),
                             top["tok_embed/embedding"], quantize))
        for i in range(cfg["num_hidden_layers"]):
            w = layer(i)
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


def logits(cfg: dict, seed: int, seqs, quantize=None) -> list:
    """What ``benchmark/lib/check.py`` compares: :func:`forward` on the
    weights of ``seed``."""
    spec = param_spec(cfg)
    return forward(cfg, weights.top(seed, spec),
                   lambda i: weights.layer(seed, spec, i), seqs, quantize)
