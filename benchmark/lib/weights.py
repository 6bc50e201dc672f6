"""Weights from ``--seed``, made on the device, for program and reference.

A configuration's reference module states the parameter layout as a
spec: ``{"top": [(name, shape), ...], "layer": [(name, shape), ...],
"num_layers": n, "dtype": "bfloat16"}``. Names are ``/``-joined paths
of the program's parameter tree (``layer`` names are relative to
``layer<i>``). The benchmark makes every leaf from the seed and the
leaf's name alone, so the program gets its tree from here and the
reference regenerates, a layer at a time, exactly the same values:
nothing the program made reaches the reference.

One compiled program makes one whole layer (its key is
``fold_in(key, i)`` with ``i`` passed as data), so a 24-layer model
costs one small compile and 25 dispatches, not a compile per leaf or a
program the size of the model. Values are drawn in float32 and cast to
the serving type, so the draw does not depend on that type.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

_LAYER_SALT = 0x1A7E5


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key, name: str, shape: tuple, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(k, shape, jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last == "scale":       # norm gains: near one, none equal
        val = 1.0 + 0.1 * noise
    elif last == "bias":
        val = 0.02 * noise
    elif last == "embedding":
        val = noise
    else:                     # kernels: unit-variance outputs
        # DenseGeneral "out" kernels are (heads, head_dim, d): every
        # axis but the last is contracted; all others contract axis 0
        fan_in = math.prod(shape[:-1]) if name.endswith("out/kernel") \
            else shape[0]
        val = noise / math.sqrt(fan_in)
    return val.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _group(key, spec: tuple, dtype: str):
    return tuple(_leaf(key, n, s, jnp.dtype(dtype)) for n, s in spec)


def _freeze(spec) -> tuple:
    return tuple((n, tuple(int(d) for d in s)) for n, s in spec)


def top(seed: int, spec: dict) -> dict:
    """The leaves outside the layers, by name."""
    frozen = _freeze(spec["top"])
    vals = _group(seed_key(seed), frozen, spec["dtype"])
    return {n: v for (n, _), v in zip(frozen, vals)}


def layer(seed: int, spec: dict, i: int) -> dict:
    """Layer ``i``'s leaves, by their name inside the layer."""
    frozen = _freeze(spec["layer"])
    key = jax.random.fold_in(
        jax.random.fold_in(seed_key(seed), _LAYER_SALT), jnp.int32(i))
    vals = _group(key, frozen, spec["dtype"])
    return {n: v for (n, _), v in zip(frozen, vals)}


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, val in flat.items():
        node = tree
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def tree(seed: int, spec: dict) -> dict:
    """The program's whole parameter tree."""
    out = _nest(top(seed, spec))
    for i in range(int(spec["num_layers"])):
        out[f"layer{i}"] = _nest(layer(seed, spec, i))
    return out


def named_leaves(tree) -> dict:
    """``{"layer0/attn/query/kernel": leaf, ...}`` of a parameter tree."""
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def check_layout(params: dict, program_shapes) -> None:
    """The spec must be the program's own layout, leaf for leaf."""
    def flat(t):
        return {name: (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
                for name, leaf in named_leaves(t).items()}
    got, want = flat(params), flat(program_shapes)
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))[:8]
        raise SystemExit(f"benchmark: the reference's parameter spec is "
                         f"not the program's layout; first differences: "
                         f"{diff}")
