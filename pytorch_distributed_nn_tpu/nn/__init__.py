"""NN utilities shared by the model zoo.

The reference's models are ordinary ``torch.nn.Module`` subclasses
(SURVEY.md §2a Models row). Here models are flax.linen modules — the
idiomatic JAX compute path — and this package holds the cross-cutting
pieces: the mixed-precision dtype policy (bf16 compute / f32 params, the
TPU-native analogue of CUDA amp), rematerialisation helpers, and
which rows of a language model's trunk reach its head.
"""

import jax.numpy as jnp

from pytorch_distributed_nn_tpu.nn.dtypes import Policy, get_policy

__all__ = ["Policy", "get_policy", "head_input"]


def head_input(x, last_only: bool = False, head_rows=None):
    """The rows of a trunk's output ``x`` (B, T, D) that reach the final
    norm and the head: the last one (``last_only``), those the caller
    names (``head_rows`` (B, K) int32: a served prefill's last real
    position, a block round's open block), or all T. A norm is a row's
    own, so choosing first is the same mathematics, and the head then
    multiplies K rows by the vocabulary, not T.

    XLA moves a gather of one row (a slice) up through the residual
    adds, so a prefill keeps every layer's output until its end: more
    temporaries than the bucket's logits were, in another layout of the
    trunk that is the faster one at Mistral's 4,096 (PERF.md sec. 6,
    PR 51, which also measured a select and a sum in its place)."""
    if last_only:
        x = x[:, -1:]
    if head_rows is not None:
        x = jnp.take_along_axis(x, head_rows[..., None], axis=1)
    return x
