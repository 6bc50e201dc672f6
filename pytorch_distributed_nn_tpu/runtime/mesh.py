"""Device mesh / topology.

The reference delegates topology to c10d process groups: a flat
``rank``/``world_size`` with NCCL communicators built per collective
(SURVEY.md §1 "Communication backend"; §3.5 init/rendezvous). TPU-native
design replaces the flat rank world with a *named* ``jax.sharding.Mesh``
whose axes map onto the hardware fabric:

- inner axes (``tensor``, ``seq``) ride ICI — highest bandwidth, so they
  carry the per-layer collectives (TP all-reduce, ring-attention ppermute);
- ``fsdp`` (sharded-DP / ZeRO) sits next — its all-gather/reduce-scatter
  wants ICI too;
- outer axes (``data``, ``pipe``) can span DCN across slices — DP gradient
  allreduce tolerates lower bandwidth, pipeline p2p is narrow.

Every strategy in :mod:`pytorch_distributed_nn_tpu.parallel` addresses the
mesh only by axis *name*, so a size-1 axis composes for free — strategies
never special-case "axis absent".
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, AbstractMesh, PartitionSpec as P

# Canonical axis order: outermost (DCN-tolerant) → innermost (ICI-hungry).
# `pipe` outermost: stages exchange only activation edges (narrow traffic,
# DCN-capable per MPMD-pipeline practice); `tensor` innermost: per-layer
# allreduce is the most bandwidth-hungry collective.
AXIS_PIPE = "pipe"
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
AXIS_TENSOR = "tensor"

AXES: tuple[str, ...] = (
    AXIS_PIPE,
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_EXPERT,
    AXIS_SEQ,
    AXIS_TENSOR,
)


@dataclasses.dataclass
class MeshSpec:
    """Logical parallelism degrees. Unused axes default to 1 and are kept in
    the mesh (size-1 axes cost nothing and keep PartitionSpecs uniform).

    ``data = -1`` means "absorb all remaining devices" — the common case
    where you fix tensor/pipe degrees and data-parallelism fills the pod.
    """

    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = self.sizes()
        bad = {name: s for name, s in sizes.items() if s < 1 and s != -1}
        if bad:
            raise ValueError(f"axis sizes must be positive or -1, got {bad}")
        wildcard = [name for name, s in sizes.items() if s == -1]
        if len(wildcard) > 1:
            raise ValueError(f"at most one -1 axis, got {wildcard}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcard:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh spec {sizes} wants {fixed} devices, have {n_devices}"
            )
        return MeshSpec(**sizes)

    def sizes(self) -> dict[str, int]:
        return {name: getattr(self, name) for name in AXES}

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.sizes()[a] for a in AXES)

    def world_size(self) -> int:
        if -1 in self.shape:
            raise ValueError("unresolved MeshSpec; call .resolve(n_devices)")
        return math.prod(self.shape)


def slice_count(devices: Sequence[jax.Device]) -> int:
    """Number of distinct TPU slices (pods connected by DCN) among
    ``devices``. CPU/single-slice devices report 1."""
    ids = set()
    for d in devices:
        idx = getattr(d, "slice_index", None)
        ids.add(0 if idx is None else idx)
    return max(len(ids), 1)


def dcn_factors(spec: MeshSpec, n_slices: int) -> dict[str, int]:
    """Split each logical axis into (DCN, ICI) degrees for a multi-slice
    job: the product of the returned per-axis DCN factors equals
    ``n_slices``, and factors are peeled onto the outermost axes first
    (``pipe``, then ``data``, …) — those tolerate DCN bandwidth, while
    inner axes (tensor/seq/fsdp) want to stay inside a slice on ICI.

    Raises when the slice count cannot be factored onto the mesh at
    all; when the only possible placement puts a factor on an
    ICI-hungry inner axis (e.g. tensor parallelism wider than a slice),
    the mesh still builds but a warning flags the bandwidth hit.
    """
    sizes = spec.sizes()
    remaining = n_slices
    factors = {name: 1 for name in AXES}
    for name in AXES:  # outermost first
        f = math.gcd(sizes[name], remaining)
        factors[name] = f
        remaining //= f
        if remaining == 1:
            break
    if remaining != 1:
        raise ValueError(
            f"cannot place {n_slices} slices on mesh {sizes}: outer-axis "
            f"sizes don't factor the slice count (residual {remaining})"
        )
    dcn_inner = {k: v for k, v in factors.items()
                 if v > 1 and k in (AXIS_FSDP, AXIS_EXPERT, AXIS_SEQ,
                                    AXIS_TENSOR)}
    if dcn_inner:
        logging.getLogger(__name__).warning(
            "DCN factors landed on ICI-hungry axes %s — expect degraded "
            "collective bandwidth; prefer putting pipe/data across slices",
            dcn_inner,
        )
    return factors


def make_mesh(
    spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
    *,
    force_slices: int | None = None,
) -> Mesh:
    """Build a named Mesh over ``devices`` (default: all).

    Single slice: ``mesh_utils.create_device_mesh`` assignment so inner
    axes land on physically adjacent chips (ICI rings). Multi-slice
    (devices spanning DCN): ``create_hybrid_device_mesh`` with the DCN
    degrees peeled onto the outermost axes (:func:`dcn_factors`), so
    cross-slice traffic is only pipe edges / DP gradient allreduce.
    Where the assigner refuses a shape, CPU test meshes fall back to a
    row-major reshape; on TPU devices the refusal raises.

    ``force_slices``: treat the device list as that many DCN-connected
    slices (row-major groups) even when the backend reports one — the
    CPU-harness hook that lets tests and ``dryrun_multichip`` exercise
    the hybrid dcn-factor placement and prove the pipeline's ppermute
    schedule lowers with ``pipe`` on the DCN axis, without TPU slices.
    """
    if devices is None:
        devices = jax.devices()
    spec = (spec or MeshSpec()).resolve(len(devices))
    n_slices = force_slices or slice_count(devices)
    if force_slices and len(devices) % force_slices:
        raise ValueError(
            f"{len(devices)} devices don't split into "
            f"{force_slices} equal slices"
        )
    if n_slices > 1:
        # Outside the try: an unplaceable multi-slice spec must raise,
        # not silently fall back to slice-unaware row-major placement.
        dcn = dcn_factors(spec, n_slices)
        ici_shape = tuple(s // dcn[a] for a, s in zip(AXES, spec.shape))
    if force_slices and n_slices > 1:
        # CPU harness: build the hybrid arrangement by hand (the real
        # create_hybrid_device_mesh groups by device slice_index, which
        # CPU devices lack). Row-major slice groups; axis a's index is
        # (dcn_a, ici_a) interleaved dcn-major — the same layout the
        # hybrid assigner produces, so pipe-over-DCN placement and the
        # resulting ppermute lowering are exercised faithfully.
        dcn_shape = tuple(dcn[a] for a in AXES)
        arr = np.asarray(devices, dtype=object).reshape(
            dcn_shape + ici_shape)
        n = len(AXES)
        order = [ax for i in range(n) for ax in (i, n + i)]
        return Mesh(arr.transpose(order).reshape(spec.shape), AXES)
    from jax.experimental import mesh_utils

    try:
        if n_slices > 1:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, tuple(dcn[a] for a in AXES),
                devices=list(devices),
            )
        else:
            dev_array = mesh_utils.create_device_mesh(
                spec.shape, devices=list(devices)
            )
    except Exception as e:  # topology assigner rejected the shape
        if devices[0].platform == "tpu":
            # on a chip a refused assignment means inner axes off the
            # ICI rings: an error, not a slower mesh
            raise
        logging.getLogger(__name__).warning(
            "mesh_utils device assignment failed (%s); falling back to "
            "row-major placement (CPU mesh: adjacency means nothing)", e
        )
        dev_array = np.asarray(devices, dtype=object).reshape(spec.shape)
    return Mesh(dev_array, AXES)


def global_device_put(tree, shardings):
    """``jax.device_put`` that also works under multi-process: a
    multi-host NamedSharding cannot be device_put directly (non-
    addressable devices), so each process materializes only its
    addressable shards via ``make_array_from_callback``. Correct for
    values that are identical on every process (deterministic seeded
    init, restored checkpoints) — the per-process host value is the
    global value."""
    if jax.process_count() == 1:
        return jax.device_put(tree, shardings)

    def put(x, sh):
        is_key = (hasattr(x, "dtype")
                  and jnp.issubdtype(x.dtype, jax.dtypes.prng_key))
        if is_key:
            impl = jax.random.key_impl(x)
            x = jax.random.key_data(x)
        host = np.asarray(jax.device_get(x))
        out = jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx]
        )
        if is_key:
            out = jax.random.wrap_key_data(out, impl=impl)
        return out

    return jax.tree.map(put, tree, shardings)


def place_like(tree, template):
    """``device_put`` each leaf of ``tree`` with the dtype and sharding
    of the matching ``template`` leaf (host values → a live state's
    layout; used by the convert/eval CLIs to install restored or
    converted weights)."""
    return jax.tree.map(
        lambda a, t: jax.device_put(
            np.asarray(a, dtype=t.dtype), t.sharding),
        tree, template,
    )


def make_abstract_mesh(spec: MeshSpec, n_devices: int) -> AbstractMesh:
    """Shape-only mesh for compile-only checks (no devices needed)."""
    resolved = spec.resolve(n_devices)
    return AbstractMesh(resolved.shape, AXES)


def batch_pspec(extra_inner: str | None = None) -> P:
    """PartitionSpec for a per-example batch dimension: sharded over every
    data-like axis (data × fsdp), the TPU analogue of torch's
    ``DistributedSampler`` per-rank split (SURVEY.md §2a data-loading row)."""
    first = (AXIS_DATA, AXIS_FSDP)
    return P(first, extra_inner) if extra_inner else P(first)


def replicated_pspec() -> P:
    return P()


def data_axis_size(mesh: Mesh) -> int:
    """Total data-parallel degree (data × fsdp), i.e. how many ways the
    global batch is split."""
    return mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
