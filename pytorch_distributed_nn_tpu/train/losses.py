"""Loss functions. Mean-reduction over the *global* batch, matching the
reference's ``nn.CrossEntropyLoss`` default so distributed loss curves are
directly comparable to single-device ones (SURVEY.md §4)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def valid_mask(labels) -> jnp.ndarray:
    """THE ignore-index convention, in one place: targets >= 0 are
    valid, negative targets (-1, torch ignore_index style) contribute
    neither loss nor denominator. Every consumer of the convention —
    masked_lm_xent, the smoothed variant, eval accuracy, and the 1F1B
    pipeline's per-microbatch valid-count weighting
    (parallel/pipeline.py) — must derive its mask here so a future
    loss with different masking can't silently diverge from one path
    only."""
    return labels >= 0


def softmax_xent(logits, labels) -> jnp.ndarray:
    """Classification: logits (B, C) float, labels (B,) int."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    ).mean()


def lm_xent(logits, targets) -> jnp.ndarray:
    """Causal LM: logits (B, T, V), targets (B, T) int."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    ).mean()


def masked_lm_xent(logits, labels) -> jnp.ndarray:
    """BERT MLM: logits (B, T, V); labels (B, T) with -1 = ignore. Mean
    over masked positions only (torch ``CrossEntropyLoss(ignore_index)``
    semantics).

    Note: the denominator is the *local* masked count. Under the
    compiler-sharded 'dp' path the whole batch is one computation, so
    this is the exact global mean; under 'dp_explicit' each device
    divides by its shard's count before the pmean — which is precisely
    torch DDP's per-rank behavior for ignore_index losses (reference
    parity), not the global mean."""
    valid = valid_mask(labels)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), jnp.maximum(labels, 0)
    )
    per_tok = jnp.where(valid, per_tok, 0.0)
    return per_tok.sum() / jnp.maximum(valid.sum(), 1)


def _to_chunks(hidden, targets, chunk: int):
    """(B, T, ·) -> per-chunk scan operands (nb, B, chunk, ·), or None
    when T is indivisible — logged loudly, because the dense fallback
    materializes the (B, T, V) logits the chunked path exists to avoid
    (api.make_train_step rejects this at config time; direct callers
    get the warning)."""
    B, T = targets.shape
    if T % chunk:
        import logging

        logging.getLogger(__name__).warning(
            "chunked LM loss: T=%d %% chunk=%d != 0 — dense fallback, "
            "(B, T, V) logits WILL materialize", T, chunk,
        )
        return None
    nb = T // chunk
    h = hidden.reshape(B, nb, chunk, -1).transpose(1, 0, 2, 3)
    t = targets.reshape(B, nb, chunk).transpose(1, 0, 2)
    return h, t


def chunked_lm_xent(hidden, kernel, targets, *, chunk: int = 2048
                    ) -> jnp.ndarray:
    """Causal-LM xent without ever materializing the (B, T, V) logits.

    At long context the logits — not attention — are the HBM limiter
    (B=1, T=32k, V=128k f32 is 16 GB before gradients). This computes
    the head projection + cross-entropy per T-chunk inside a
    ``lax.scan`` whose body is ``jax.checkpoint``-ed, so forward AND
    backward keep only one (B, chunk, V) logits block live.

    hidden: (B, T, D) final-norm'd trunk output (model ``return_hidden``
    path); kernel: (D, V) lm_head weight; targets: (B, T) int.
    Numerically identical to ``lm_xent(hidden @ kernel, targets)``.
    """
    chunks = _to_chunks(hidden, targets, chunk)
    if chunks is None:
        return lm_xent(
            jnp.einsum("btd,dv->btv", hidden, kernel), targets
        )
    h, t = chunks
    B, T, _ = hidden.shape

    @jax.checkpoint
    def body(acc, ht):
        h_blk, t_blk = ht
        logits = jnp.einsum(
            "bcd,dv->bcv", h_blk, kernel,
            preferred_element_type=jnp.float32,
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), t_blk
        ).sum()
        return acc + loss, None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, t))
    return total / (B * T)


def chunked_lm_eval(hidden, kernel, targets, *, chunk: int = 2048
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eval twin of :func:`chunked_lm_xent`: (mean loss, accuracy)
    per T-chunk, still never materializing full logits (an eval pass at
    long context would otherwise OOM exactly like training did)."""
    chunks = _to_chunks(hidden, targets, chunk)
    if chunks is None:
        logits = jnp.einsum("btd,dv->btv", hidden, kernel)
        return lm_xent(logits, targets), accuracy(logits, targets)
    h, t = chunks
    B, T, _ = hidden.shape

    def body(carry, ht):
        loss_acc, hit_acc = carry
        h_blk, t_blk = ht
        logits = jnp.einsum(
            "bcd,dv->bcv", h_blk, kernel,
            preferred_element_type=jnp.float32,
        ).astype(jnp.float32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, t_blk
        ).sum()
        hits = (logits.argmax(-1) == t_blk).sum()
        return (loss_acc + loss, hit_acc + hits), None

    (loss, hits), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h, t),
    )
    n = B * T
    return loss / n, hits.astype(jnp.float32) / n


def accuracy(logits, labels) -> jnp.ndarray:
    return (logits.argmax(-1) == labels).mean()


_LOSSES = {
    "lm_synthetic": lm_xent,
    "token_file": lm_xent,
    "mlm_synthetic": masked_lm_xent,
}


def _smoothed(base, eps: float):
    """torch ``CrossEntropyLoss(label_smoothing=eps)`` semantics:
    per-element loss = (1-eps)·nll + eps·(uniform xent over classes);
    the -1=ignore masking of :func:`masked_lm_xent` is preserved by
    applying the same formula under its mask."""

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        uniform = -logp.mean(-1)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], axis=-1
        )[..., 0]
        per = (1.0 - eps) * nll + eps * uniform
        if base is masked_lm_xent:
            valid = valid_mask(labels)
            per = jnp.where(valid, per, 0.0)
            return per.sum() / jnp.maximum(valid.sum(), 1)
        return per.mean()

    return loss_fn


def get_loss_fn(dataset_name: str, *, label_smoothing: float = 0.0):
    base = _LOSSES.get(dataset_name, softmax_xent)
    if label_smoothing == 0.0:
        return base
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(
            f"label_smoothing must be in [0, 1), got {label_smoothing}"
        )
    return _smoothed(base, label_smoothing)


def model_nll(model, params, batches) -> float:
    """Teacher-forced mean per-token NLL of a causal LM over an
    iterable of (tokens, targets) batches — the whole-model quality
    metric (int8-vs-bf16 NLL delta, tests/test_quality.py; VERDICT r4
    Missing #3). Works for float and int8-quantized
    param trees alike (the model's lm_head emits f32 logits either
    way). Perplexity = exp(return value).

    The xent lives INSIDE the jit: the (B, T, V) logits then exist
    once on device (f32, 2.1 GB at the 8B's B=1/T=4096/V=128k) with
    the log-softmax reduction fused behind them, instead of surviving
    the program boundary and feeding eager optax temporaries of the
    same size. Raise B with the 8B only as that peak allows."""

    @jax.jit
    def batch_nll(params, x, y):
        logits = model.apply({"params": params}, x, train=False)
        return lm_xent(logits, y)

    total, count = 0.0, 0
    for x, y in batches:
        nll = batch_nll(params, jnp.asarray(x), jnp.asarray(y))
        n = int(jnp.asarray(y).size)
        total += float(jax.device_get(nll)) * n
        count += n
    if count == 0:
        raise ValueError("model_nll needs at least one batch")
    return total / count
