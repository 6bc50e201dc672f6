"""Pallas TPU kernels for the hot ops (SURVEY.md §2b: the TPU-native
replacement for the reference's cuDNN/NCCL kernel layer). Every kernel has
a jnp reference implementation used on non-TPU backends (CPU tests) and as
the correctness oracle."""

import functools
import logging

log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def warn_reference_fallback(kernel: str, shape: tuple) -> None:
    """On a TPU, a shape no block size fits makes a dispatcher run its
    jnp reference instead of the kernel. Correct, and slow enough that
    it must not be silent: one WARNING per kernel and shape (the cache
    is the "once")."""
    log.warning(
        "%s: no block size fits shape %s — the jnp reference runs "
        "instead of the Pallas kernel", kernel, shape)
