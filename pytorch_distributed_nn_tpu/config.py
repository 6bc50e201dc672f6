"""Config / flag system.

The reference configures each trainer with argparse flags (``--rank``,
``--world-size``, ``--backend``, ``--lr``, …; SURVEY.md §5 "Config/flag
system"). Here configs are typed dataclasses with dotted CLI overrides
(``--optim.lr=0.1``), and the five benchmark configs from
/root/repo/BASELINE.json:6-12 are named presets.
"""

from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field
from typing import Any

from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec


@dataclass
class OptimConfig:
    name: str = "sgd"  # sgd | momentum | adam | adamw | adafactor | lamb | lion
    lr: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    grad_clip_norm: float = 0.0  # 0 = off
    warmup_steps: int = 0
    schedule: str = "constant"  # constant | cosine | linear | step
    # schedule="step" (torch StepLR): decay by step_gamma at these
    # fractions of the post-warmup run
    step_milestones: tuple[float, ...] = (0.5, 0.75)
    step_gamma: float = 0.1
    # skip weight decay on 1-D params (norm scales/biases) — the usual
    # LLM recipe; False reproduces torch's decay-everything default
    decay_mask_norms: bool = False
    # store momentum/adam/adamw/lion first moments in this dtype
    # ("" = param dtype): "bfloat16" halves that slice of optimizer HBM
    # (rejected for optimizers without moment-dtype control)
    mu_dtype: str = ""


@dataclass
class DataConfig:
    # mnist | cifar10 | imagenet_synthetic | lm_synthetic | mlm_synthetic
    # | token_file (causal LM from a memory-mapped .bin/.npy token dump)
    # | array_file (classification from a .npz with arrays x, y)
    # | mnist_idx (LeCun idx files; t10k-* pair = real eval split)
    # | cifar10_bin (data_batch_*.bin; test_batch.bin = real eval split)
    # | image_folder (torchvision layout root/<class>/<img>, lazy PIL
    #   decode; train/ + val/ dirs honored as the split)
    dataset: str = "mnist"
    path: str = ""  # file/directory for the file-backed datasets
    image_size: int = 224  # image_folder: decode target (short side +
    #                        center crop, torchvision eval transform)
    token_dtype: str = "uint16"  # raw .bin token width (token_file)
    # array_file sampling: 'shuffle' (per-epoch permutation, torch
    # DistributedSampler semantics) or 'replacement' (i.i.d.)
    sample: str = "shuffle"
    # token_file/array_file: fraction of the file reserved for held-out
    # eval (0 = none; file-dataset eval is then IN-SAMPLE — it reports
    # training-set performance). Synthetic streams are infinite and
    # always genuinely held out.
    holdout_frac: float = 0.0
    batch_size: int = 128  # global batch size
    seq_len: int = 512
    vocab_size: int = 32000
    prefetch: int = 2  # background host batches kept ready (0 = sync)
    # decode threads per batch for image_folder (torch DataLoader
    # num_workers semantics: 0 = inline, -1 = one per core capped 16;
    # PIL/libjpeg releases the GIL so threads scale across cores)
    num_workers: int = -1


@dataclass
class ModelConfig:
    name: str = "mlp"  # mlp | lenet | resnet50 | bert_base | transformer_lm | llama3_8b
    dtype: str = "float32"  # param dtype
    compute_dtype: str = "bfloat16"
    remat: bool = False  # jax.checkpoint on blocks
    # offload the remat block boundaries to pinned host RAM instead of
    # HBM (XLA host-offload; needs remat=True; llama only for now) —
    # the long-context enabler: HBM holds one layer's recompute, not
    # every boundary
    remat_offload: bool = False
    extra: dict[str, Any] = field(default_factory=dict)


@dataclass
class ParallelConfig:
    strategy: str = "dp"  # single | dp | zero | pipeline | ps
    # DDP-style bucket controller (SURVEY.md §2b Reducer row):
    bucket_mb: float = 25.0
    overlap: bool = True
    zero_stage: int = 3  # 1 = optimizer-state shard; 3 = params too
    microbatches: int = 1  # pipeline microbatching
    # gradient accumulation (single/dp/zero): split the global batch into
    # this many sequentially-scanned microbatches per optimizer step —
    # ~grad_accum× lower peak activation memory. Identical math to
    # accum=1 for deterministic stateless models; dropout masks are
    # re-drawn per microbatch and BatchNorm stats update per microbatch
    # (torch-accumulation-loop semantics), so those curves differ
    # slightly from the one-shot step
    grad_accum: int = 1
    # "gpipe": AD-transposed fill-drain — simplest, but the scan
    # transpose saves residuals for every in-flight tick, so activation
    # memory grows with `microbatches`. "1f1b": PipeDream-flush with a
    # manual per-stage backward (parallel/pipeline.py::_make_1f1b_step)
    # — activation memory bounded by ~2*stages. All three schedules
    # support dropout (shared deterministic rng stream; gpipe and 1f1b
    # draw bit-identical masks).
    # "interleaved": Megatron virtual-chunk 1F1B — `pipe_chunks` chunks
    # per device round-robin over virtual stages, pipeline bubble cut
    # to ~1/pipe_chunks of 1f1b's at the cost of more in-flight
    # activations and 2x ppermute traffic (full rings).
    pipeline_schedule: str = "gpipe"
    # virtual chunks per device for pipeline_schedule='interleaved'
    # (model layers must divide stages x chunks; microbatches must
    # divide by stages — Megatron's group structure)
    pipe_chunks: int = 1
    quantized_allreduce: str = ""  # "" | "bf16" | "int8" (EQuARX-style)


@dataclass
class TrainConfig:
    preset: str = ""
    seed: int = 0
    steps: int = 100
    # device-side training loop (train/multistep.py): fuse this many
    # optimizer steps into ONE dispatch via lax.scan. Identical math to
    # k sequential steps on the same batches; checkpoint/eval cadences
    # round UP to the next dispatch boundary (the device program is not
    # interruptible mid-scan); per-step losses still log via the scan's
    # stacked metrics. The dispatch-latency amortizer for small models.
    multistep_k: int = 1
    # 0 = each fused step trains on a FRESH batch (k batches stacked and
    # transferred per dispatch — the production setting). N > 0 = cycle
    # a fixed pool of N device-resident batches inside the scan:
    # repeats data, which is wrong for real training but exactly what a
    # device-rate benchmark wants.
    multistep_pool: int = 0
    log_every: int = 10
    eval_every: int = 0  # 0 = no eval; else eval every N steps
    eval_batches: int = 8  # batches per eval pass (held-out seed stream)
    # chunk the LM softmax-xent over T (tokens per chunk; 0 = dense
    # logits). At long context the (B, T, V) logits are the HBM
    # limiter; chunking keeps one (B, chunk, V) block live instead.
    xent_chunk: int = 0
    # torch CrossEntropyLoss(label_smoothing=...) semantics; not
    # combinable with xent_chunk
    label_smoothing: float = 0.0
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    resume: bool = True
    profile_dir: str = ""
    # structured JSONL metrics (utils/metrics.MetricsLogger): every
    # log_every step + every eval + goodput breakdowns as machine-
    # readable events, emitted by the coordinator only ("" = off)
    metrics_path: str = ""
    # Prometheus textfile exposition (obs/registry.py): the process
    # registry (counters/gauges/histograms, goodput, mesh topology,
    # heartbeat state) written here at log cadence and on close
    # ("" = off) — node_exporter textfile-collector layout
    prom_path: str = ""
    mesh: MeshSpec = field(default_factory=MeshSpec)
    optim: OptimConfig = field(default_factory=OptimConfig)
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)

    def override(self, **dotted: Any) -> "TrainConfig":
        cfg = copy.deepcopy(self)  # nested sub-configs must not alias self's
        for key, value in dotted.items():
            _set_dotted(cfg, key.replace("-", "_"), value)
        return cfg


def _set_dotted(obj: Any, dotted: str, value: Any) -> None:
    parts = dotted.split(".")
    for part in parts[:-1]:
        obj = getattr(obj, part)
    leaf = parts[-1]
    if not hasattr(obj, leaf):
        raise AttributeError(f"unknown config field {dotted!r}")
    current = getattr(obj, leaf)
    if current is not None and not isinstance(value, type(current)):
        if isinstance(current, bool):
            value = str(value).lower() in ("1", "true", "yes", "on")
        elif isinstance(current, (int, float)):
            value = type(current)(value)
        elif isinstance(current, dict):
            value = json.loads(value)  # e.g. --model.extra '{"d_model":64}'
        elif isinstance(current, (tuple, list)):
            # e.g. --optim.step_milestones '[0.3, 0.6, 0.9]'
            value = type(current)(json.loads(value))
    setattr(obj, leaf, value)


def parse_overrides(argv: list[str]) -> dict[str, str]:
    """Parse ``--a.b=c`` / ``--a.b c`` style CLI overrides."""
    out: dict[str, str] = {}
    i = 0
    while i < len(argv):
        arg = argv[i]
        if not arg.startswith("--"):
            raise ValueError(f"unexpected argument {arg!r}")
        arg = arg[2:]
        if "=" in arg:
            key, value = arg.split("=", 1)
        else:
            if i + 1 >= len(argv):
                raise ValueError(f"flag --{arg} expects a value")
            key, value = arg, argv[i + 1]
            i += 1
        out[key] = value
        i += 1
    return out


# ---------------------------------------------------------------------------
# The five benchmark presets (BASELINE.json "configs", lines 6-12).
# ---------------------------------------------------------------------------

def _mlp_mnist() -> TrainConfig:
    # Config 1: "2-layer MLP on MNIST, single process (gloo backend, CPU)".
    # Gloo-on-CPU maps to the XLA host platform (SURVEY.md §4).
    return TrainConfig(
        preset="mlp_mnist",
        steps=200,
        optim=OptimConfig(name="momentum", lr=0.01),
        data=DataConfig(dataset="mnist", batch_size=128),
        model=ModelConfig(name="mlp", compute_dtype="float32"),
        parallel=ParallelConfig(strategy="dp"),
    )


def _lenet_cifar10() -> TrainConfig:
    # The reference's classic small-net config (SURVEY.md §2a Models row
    # [R]: "LeNet-ish CNN on MNIST/CIFAR-10") — not one of the five
    # BASELINE configs, kept as a named preset for parity breadth.
    return TrainConfig(
        preset="lenet_cifar10",
        steps=200,
        optim=OptimConfig(name="momentum", lr=0.05),
        data=DataConfig(dataset="cifar10", batch_size=128),
        model=ModelConfig(name="lenet", compute_dtype="float32"),
        parallel=ParallelConfig(strategy="dp"),
    )


def _resnet50_dp() -> TrainConfig:
    # Config 2: "ResNet-50 / ImageNet, pure data-parallel DDP allreduce".
    return TrainConfig(
        preset="resnet50_dp",
        steps=100,
        optim=OptimConfig(name="momentum", lr=0.1, weight_decay=1e-4,
                          warmup_steps=5, schedule="cosine"),
        data=DataConfig(dataset="imagenet_synthetic", batch_size=1024),
        model=ModelConfig(name="resnet50"),
        parallel=ParallelConfig(strategy="dp", bucket_mb=25.0, overlap=True),
    )


def _bert_base_buckets() -> TrainConfig:
    # Config 3: "BERT-base pretraining, large fused gradient buckets".
    return TrainConfig(
        preset="bert_base_buckets",
        steps=100,
        optim=OptimConfig(name="adamw", lr=1e-4, weight_decay=0.01,
                          warmup_steps=10, schedule="linear"),
        data=DataConfig(dataset="mlm_synthetic", batch_size=256, seq_len=128,
                        vocab_size=30522),
        model=ModelConfig(name="bert_base"),
        # dp_explicit so the named "large fused gradient buckets" actually
        # run through the bucket controller (ops/buckets.py)
        parallel=ParallelConfig(strategy="dp_explicit", bucket_mb=100.0,
                                overlap=True),
    )


def _transformer_lm_pp() -> TrainConfig:
    # Config 4: "Transformer-LM pipeline-parallel (send/recv p2p)".
    return TrainConfig(
        preset="transformer_lm_pp",
        steps=50,
        mesh=MeshSpec(pipe=4, data=-1),
        optim=OptimConfig(name="adam", lr=3e-4, warmup_steps=10,
                          schedule="cosine"),
        data=DataConfig(dataset="lm_synthetic", batch_size=64, seq_len=1024),
        model=ModelConfig(name="transformer_lm", remat=True),
        parallel=ParallelConfig(strategy="pipeline", microbatches=8,
                                pipeline_schedule="gpipe"),
    )


def _llama3_8b_zero() -> TrainConfig:
    # Config 5: "Llama-3-8B sharded data-parallel (allgather params +
    # reduce-scatter grads)".
    return TrainConfig(
        preset="llama3_8b_zero",
        steps=20,
        mesh=MeshSpec(fsdp=-1, data=1),
        optim=OptimConfig(name="adamw", lr=3e-4, weight_decay=0.1,
                          grad_clip_norm=1.0, warmup_steps=10,
                          schedule="cosine"),
        data=DataConfig(dataset="lm_synthetic", batch_size=16, seq_len=4096,
                        vocab_size=128256),
        model=ModelConfig(name="llama3_8b", remat=True),
        parallel=ParallelConfig(strategy="zero", zero_stage=3),
        # at V=128k the dense (B, T, V) f32 logits + their cotangent are
        # the per-chip HBM limiter (~4 GiB at B=16/T=4096 over 16 chips
        # — scripts/validate_8b_layout.py); chunking keeps one
        # (B, 2048, V) block live. Falls back to dense when T <= chunk
        # (the scaled single-chip bench).
        xent_chunk=2048,
    )


def _llama3_longcontext() -> TrainConfig:
    # Beyond the reference (SURVEY.md §5 "Long-context" row): 32k-token
    # causal-LM training. Single chip: Pallas flash attention (blockwise
    # fwd + bwd, never materializing the (T, T) scores) + remat; on a
    # pod, add mesh.seq for ring-attention context parallelism.
    return TrainConfig(
        preset="llama3_longcontext",
        steps=10,
        mesh=MeshSpec(seq=1, data=-1),
        optim=OptimConfig(name="adamw", lr=1e-4, weight_decay=0.1,
                          grad_clip_norm=1.0, warmup_steps=2,
                          schedule="cosine"),
        data=DataConfig(dataset="lm_synthetic", batch_size=1,
                        seq_len=32768, vocab_size=32000),
        # head_dim 128 = the REAL Llama-3 per-head geometry (4096/32).
        # The r1-r3 stand-in used 16 heads at d=1024 (head_dim 64),
        # which half-fills the MXU contraction in every attention
        # matmul — measured r4 at T=32k fwd+bwd: 165 ms vs 102 ms for
        # the same H*D with head_dim 128 (1.62x). Same param count,
        # same FLOPs, realistic kernel shape.
        model=ModelConfig(name="llama3_8b", remat=True,
                          extra=dict(num_layers=8, d_model=1024,
                                     num_heads=8, num_kv_heads=4,
                                     mlp_dim=3584, vocab_size=32000)),
        parallel=ParallelConfig(strategy="dp"),
        # at T=32k the (T, vocab) logits are the HBM limiter (dense
        # f32 logits + grads OOM a 16 GB chip at vocab 32k); the
        # chunked xent keeps one (B, 2048, V) block live instead
        xent_chunk=2048,
    )


def _llama3_longcontext_96k() -> TrainConfig:
    # SURVEY.md §5 names 32k-512k; this preset TRAINS at 96k tokens on
    # ONE 16 GB v5e chip (sized on the 2026-08-01 runtime, which is
    # gone; not re-measured since — compile-time analysis says
    # 10.25 GiB total at 128k, so longer may fit today).
    # Beyond one chip, 128k+ runs the dryrun-proven ring/seq-parallel
    # mesh path, and 512k is covered at kernel level by
    # scripts/validate_tpu_kernels.py's long-context check.
    # Same scaled-llama stand-in as llama3_longcontext; the streamed
    # flash kernels keep attention VMEM/HBM T-independent, remat holds
    # layer boundaries only, and chunked xent bounds the logits.
    cfg = _llama3_longcontext()
    cfg.preset = "llama3_longcontext_96k"
    cfg.data.seq_len = 98304
    cfg.steps = 5
    return cfg


def _moe_lm_ep() -> TrainConfig:
    # Beyond the reference (SURVEY.md §2c EP row): mixture-of-experts LM,
    # experts sharded over the `expert` mesh axis, token dispatch via the
    # XLA all-to-all the SPMD partitioner derives from the layout.
    return TrainConfig(
        preset="moe_lm_ep",
        steps=50,
        mesh=MeshSpec(expert=-1, data=1),
        optim=OptimConfig(name="adamw", lr=3e-4, weight_decay=0.1,
                          warmup_steps=10, schedule="cosine"),
        data=DataConfig(dataset="lm_synthetic", batch_size=32, seq_len=1024),
        # no remat: this MoE fits activations at any topology (experts
        # shard over the expert axis, batch over data) and recompute
        # costs 13% measured throughput (r3 A/B: 43.6 -> 49.3
        # samples/s/chip, 40.3% MFU); override model.remat=true for
        # bigger variants
        model=ModelConfig(name="moe_lm", remat=False),
        parallel=ParallelConfig(strategy="zero", zero_stage=3),
    )


PRESETS = {
    "mlp_mnist": _mlp_mnist,
    "lenet_cifar10": _lenet_cifar10,
    "moe_lm_ep": _moe_lm_ep,
    "llama3_longcontext": _llama3_longcontext,
    "llama3_longcontext_96k": _llama3_longcontext_96k,
    "resnet50_dp": _resnet50_dp,
    "bert_base_buckets": _bert_base_buckets,
    "transformer_lm_pp": _transformer_lm_pp,
    "llama3_8b_zero": _llama3_8b_zero,
}


def get_config(preset: str, **overrides: Any) -> TrainConfig:
    if preset not in PRESETS:
        raise KeyError(f"unknown preset {preset!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[preset]()
    return cfg.override(**overrides) if overrides else cfg
