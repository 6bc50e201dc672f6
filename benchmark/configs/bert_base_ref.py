"""Plain reference for ``bert_base``: forward, loss, gradients, AdamW.

BERT as published (Devlin et al. 2018; ``bert_config.json`` of
``bert-base-uncased``): token + position embeddings -> LayerNorm; per
layer (post-LN) multi-head self-attention with biases -> add -> LN,
GELU MLP -> add -> LN; masked-LM head: dense + GELU + LN, decoder to
the vocabulary with a bias. Departures, both the program's and noted
here: no segment embedding is added (the synthetic batches carry one
segment), GELU is the tanh approximation, dropout is 0, the decoder is
not tied to the embedding, and the loss is the mean over masked
positions *of each data-parallel shard*, averaged over shards (what
torch DDP computes with ``ignore_index``; equal to the global mean on
one chip).

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")`` and ``jax.grad``; AdamW with
the warm-up and linear decay the configuration states, written out
here. Rows go through in slices so that a four-chip global batch fits
one device. Imports nothing of the program.

``precision="fp8"`` is the control: every matrix product takes both
operands rounded to float8 (e4m3, one scale per tensor), the step below
bfloat16 that would tempt a later PR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights


def param_spec(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    hd = d // h

    def ln(name):
        return [(f"{name}/scale", (d,)), (f"{name}/bias", (d,))]

    layer = []
    for p in ("query", "key", "value"):
        layer += [(f"attn/{p}/kernel", (d, h, hd)), (f"attn/{p}/bias", (h, hd))]
    layer += [("attn/out/kernel", (h, hd, d)), ("attn/out/bias", (d,))]
    layer += ln("ln1")
    layer += [("mlp_in/kernel", (d, ff)), ("mlp_in/bias", (ff,)),
              ("mlp_out/kernel", (ff, d)), ("mlp_out/bias", (d,))]
    layer += ln("ln2")
    top = [("tok_embed/embedding", (v, d)),
           ("pos_embed/embedding", (cfg["max_position_embeddings"], d))]
    top += ln("ln_embed")
    top += [("mlm_dense/kernel", (d, d)), ("mlm_dense/bias", (d,))]
    top += ln("mlm_ln")
    top += [("mlm_decoder/kernel", (d, v)), ("mlm_decoder/bias", (v,))]
    return {"dtype": cfg["param_dtype"],
            "num_layers": cfg["num_hidden_layers"],
            "top": top, "layer": layer}


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)   # straight-through


def _mm(spec: str, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision is not None:
        raise ValueError(f"unknown control precision {precision!r}")
    return jnp.einsum(spec, a, b)


def _ln(x, p, name, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p[f"{name}/scale"] \
        + p[f"{name}/bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _forward(params, tokens, n_layers, eps, precision):
    mm = functools.partial(_mm, precision=precision)
    top = params["top"]
    t = tokens.shape[1]
    x = top["tok_embed/embedding"][tokens] \
        + top["pos_embed/embedding"][:t][None]
    x = _ln(x, top, "ln_embed", eps)
    for i in range(n_layers):
        p = params["layers"][i]
        q = mm("btd,dhk->bthk", x, p["attn/query/kernel"]) \
            + p["attn/query/bias"]
        k = mm("btd,dhk->bthk", x, p["attn/key/kernel"]) + p["attn/key/bias"]
        v = mm("btd,dhk->bthk", x, p["attn/value/kernel"]) \
            + p["attn/value/bias"]
        s = mm("bthk,bshk->bhts", q, k) * q.shape[-1] ** -0.5
        o = mm("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
        y = mm("bthk,hkd->btd", o, p["attn/out/kernel"]) + p["attn/out/bias"]
        x = _ln(x + y, p, "ln1", eps)
        y = _gelu(mm("btd,df->btf", x, p["mlp_in/kernel"])
                  + p["mlp_in/bias"])
        y = mm("btf,fd->btd", y, p["mlp_out/kernel"]) + p["mlp_out/bias"]
        x = _ln(x + y, p, "ln2", eps)
    x = _gelu(mm("btd,de->bte", x, top["mlm_dense/kernel"])
              + top["mlm_dense/bias"])
    x = _ln(x, top, "mlm_ln", eps)
    return mm("btd,dv->btv", x, top["mlm_decoder/kernel"]) \
        + top["mlm_decoder/bias"]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _slice_grads(params, tokens, labels, inv_count, n_layers, eps,
                 precision):
    """Loss and gradients of one slice of one shard: the slice's summed
    token loss over the *shard's* masked count (``inv_count``)."""
    def loss_fn(p):
        logits = _forward(p, tokens, n_layers, eps, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(labels >= 0, picked, 0.0)) * inv_count
    return jax.value_and_grad(loss_fn)(params)


def loss_and_grads(params, x, y, *, shards, rows, n_layers, eps, precision):
    """Mean over ``shards`` equal row-blocks of each block's masked mean
    loss, and its gradient, ``rows`` rows at a time."""
    n = x.shape[0]
    per = n // shards
    loss, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
    for s in range(shards):
        lo, hi = s * per, (s + 1) * per
        inv = 1.0 / max(int((y[lo:hi] >= 0).sum()), 1) / shards
        for a in range(lo, hi, rows):
            b = min(a + rows, hi)
            lv, g = _slice_grads(params, jnp.asarray(x[a:b]),
                                 jnp.asarray(y[a:b]), jnp.float32(inv),
                                 n_layers, eps, precision)
            loss += float(lv)
            grads = jax.tree.map(jnp.add, grads, g)
    return loss, grads


def learning_rate(opt: dict, count: int, total_steps: int) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then linear decay to
    0 at ``total_steps`` (the configuration's recipe)."""
    warm = int(opt["warmup_steps"])
    if count < warm:
        return opt["lr"] * count / warm
    span = max(total_steps - warm, 1)
    return opt["lr"] * max(0.0, 1.0 - (count - warm) / span)


@jax.jit
def _adamw(p, g, mu, nu, lr, c1, c2, b1, b2, eps, wd):
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    p = jax.tree.map(
        lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * w), p, mu, nu)
    return p, mu, nu


def _norms(tree) -> dict:
    flat = {}
    for name, v in tree["top"].items():
        flat[name] = float(jnp.linalg.norm(v))
    for i, layer in enumerate(tree["layers"]):
        for name, v in layer.items():
            flat[f"layer{i}/{name}"] = float(jnp.linalg.norm(v))
    return flat


def first_steps(cfg: dict, seed: int, batches: list, *, shards: int,
                total_steps: int, rows: int = 64, precision=None) -> dict:
    """The first ``len(batches)`` optimizer steps from the seeded
    weights: each step's loss, the first gradient's norm by leaf, and
    the norm by leaf of the parameters' change after the last step."""
    spec = param_spec(cfg)
    opt = cfg["optimizer"]
    n_layers, eps = spec["num_layers"], float(cfg["layer_norm_eps"])
    with jax.default_matmul_precision("highest"):
        f32 = lambda d: {k: v.astype(jnp.float32) for k, v in d.items()}  # noqa: E731
        params = {"top": f32(weights.top(seed, spec)),
                  "layers": [f32(weights.layer(seed, spec, i))
                             for i in range(n_layers)]}
        start = params
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for step, (x, y) in enumerate(batches):
            loss, g = loss_and_grads(
                params, np.asarray(x), np.asarray(y), shards=shards,
                rows=rows, n_layers=n_layers, eps=eps, precision=precision)
            losses.append(loss)
            if step == 0:
                grad_norms = _norms(g)
            t = step + 1
            params, mu, nu = _adamw(
                params, g, mu, nu,
                jnp.float32(learning_rate(opt, step, total_steps)),
                jnp.float32(1 - opt["b1"] ** t),
                jnp.float32(1 - opt["b2"] ** t),
                jnp.float32(opt["b1"]), jnp.float32(opt["b2"]),
                jnp.float32(opt["eps"]), jnp.float32(opt["weight_decay"]))
        delta = _norms(jax.tree.map(jnp.subtract, params, start))
    return dict(losses=losses, grad_norms=grad_norms, delta_norms=delta)
