#!/usr/bin/env python
"""Generation entrypoint: checkpoint -> KV-cache decode.

Usage:
    python scripts/generate.py --preset llama3_longcontext \
        [--checkpoint-dir runs/ckpt] [--prompt "5 17 42"] \
        [--max-new 32] [--temperature 0.8] [--top-k 40] [--seed 0] \
        [--tokenizer path/to/tokenizer_dir_or_json]

Prompts are space-separated token ids, or text when ``--tokenizer``
names a local HF tokenizer (a saved directory, or a tokenizer.json) —
the output is then detokenized too, and the tokenizer's eos stops
generation. Without --checkpoint-dir the model is randomly
initialized — useful only for smoke-testing the decode path.
"""

from __future__ import annotations

import argparse
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="llama3_longcontext")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--prompt", default="1 2 3 4")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (0 = off)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="consume the prompt in chunks of N tokens "
                         "(bounds prefill attention memory for long "
                         "prompts; 0 = one-shot)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokenizer", default="",
                    help="local HF tokenizer dir or tokenizer.json; "
                         "prompt/output become text")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: decode SPMD over a "
                         "(tensor=tp, data=rest) mesh with sharded "
                         "params and KV cache")
    # remaining --a.b style flags are config overrides, as in train.py
    # (the model dims must match the checkpoint being decoded)
    args, rest = ap.parse_known_args(argv)

    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
    from pytorch_distributed_nn_tpu.inference import generate
    from pytorch_distributed_nn_tpu.models import get_model

    cfg = get_config(args.preset, **parse_overrides(rest))
    model = get_model(cfg.model)

    tokenizer = None
    eos_token = None
    if args.tokenizer:
        import transformers

        if args.tokenizer.endswith(".json"):
            tokenizer = transformers.PreTrainedTokenizerFast(
                tokenizer_file=args.tokenizer
            )
        else:
            tokenizer = transformers.AutoTokenizer.from_pretrained(
                args.tokenizer
            )
        eos_token = tokenizer.eos_token_id
        if eos_token is None:
            # a bare tokenizer.json carries no special-token config;
            # recover the conventional eos from the vocab so "eos stops
            # generation" holds, and say so if it can't
            vocab = tokenizer.get_vocab()
            for cand in ("</s>", "<|endoftext|>", "<eos>", "[SEP]"):
                if cand in vocab:
                    tokenizer.eos_token = cand
                    eos_token = vocab[cand]
                    break
            else:
                print("[generate] tokenizer defines no eos token; "
                      "generation will not early-stop", file=sys.stderr)
        ids = tokenizer.encode(args.prompt)
        if not ids:
            print("tokenizer produced an empty prompt", file=sys.stderr)
            return 1
        prompt = jnp.asarray([ids], jnp.int32)
    else:
        prompt = jnp.asarray(
            [[int(t) for t in args.prompt.split()]], jnp.int32
        )

    if args.checkpoint_dir:
        cfg.checkpoint_dir = args.checkpoint_dir
        cfg.steps = 0  # Trainer restores; no training
        from pytorch_distributed_nn_tpu.train.trainer import Trainer

        trainer = Trainer(cfg)
        if trainer.ckpt is None or trainer.ckpt.latest_step() is None:
            print(f"no checkpoint found in {args.checkpoint_dir}",
                  file=sys.stderr)
            return 1
        params = jax.device_get(trainer.state.params)
        trainer.close()
    else:
        print("[generate] no --checkpoint-dir: random init (smoke test)",
              file=sys.stderr)
        params = model.init(
            jax.random.key(cfg.seed), prompt, train=False
        )["params"]

    mesh = None
    if args.tp > 1:
        from pytorch_distributed_nn_tpu.runtime.mesh import (
            MeshSpec,
            make_mesh,
        )

        mesh = make_mesh(MeshSpec(tensor=args.tp, data=-1))

    rng = (jax.random.key(args.seed)
           if args.temperature > 0 else None)
    out = generate(model, params, prompt, args.max_new,
                   temperature=args.temperature, top_k=args.top_k,
                   top_p=args.top_p, rng=rng, eos_token=eos_token,
                   mesh=mesh, prefill_chunk=args.prefill_chunk)
    ids = [int(t) for t in np.asarray(out)[0]]
    if tokenizer is not None:
        print(tokenizer.decode(ids, skip_special_tokens=True))
    else:
        print(" ".join(str(t) for t in ids))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
