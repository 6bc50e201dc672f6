"""pytorch_distributed_nn_tpu — a TPU-native distributed training framework.

A brand-new framework with the capability surface of the reference repo
``chao1224/pytorch_distributed_nn`` (a pure-Python harness over
``torch.distributed``: DDP bucketed allreduce, parameter broadcast, p2p
pipeline stages, all-gather/reduce-scatter sharded DP), re-designed
TPU-first:

- process bootstrap via ``jax.distributed`` instead of ``torchrun``/NCCL
  (reference capability: ``dist.init_process_group`` — see SURVEY.md §1),
- data-parallel gradient allreduce via ``jax.lax.psum`` over ICI instead of
  NCCL ring allreduce (SURVEY.md §2c),
- sharded DP via ``NamedSharding`` so XLA emits all-gather/reduce-scatter
  (SURVEY.md §3.4),
- pipeline stages via ``shard_map`` + ``lax.ppermute`` instead of
  ``dist.send/recv`` (SURVEY.md §3.3),
- tensor/sequence/context parallelism and ring attention as first-class
  mesh axes (SURVEY.md §2c),
- Pallas kernels for the hot ops and a C++ native runtime substrate
  (rendezvous store, host data pipeline) where the reference leaned on
  c10d's C++ core.

The reference mount was empty at survey time (SURVEY.md provenance note);
parity targets come from /root/repo/BASELINE.json.
"""

from pytorch_distributed_nn_tpu.version import __version__

__all__ = ["__version__"]
