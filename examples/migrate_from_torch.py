#!/usr/bin/env python
"""Migration walkthrough: HF/torch checkpoint → this framework.

Builds a tiny HF Llama, converts its weights, proves logits match,
greedy-decodes token-identically to `hf.generate`, and exports back.

Run: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 python examples/migrate_from_torch.py
"""

import sys

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import numpy as np
import torch
import transformers

import jax

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.inference import generate
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.utils import torch_interop as ti

# --- the torch side: any LlamaForCausalLM checkpoint --------------------
hf_cfg = transformers.LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=500000.0,
    tie_word_embeddings=False, attention_bias=False,
    attn_implementation="eager")
torch.manual_seed(0)
hf = transformers.LlamaForCausalLM(hf_cfg).eval()

# --- convert: state_dict → flax params (rotary conventions match 1:1) ---
params = ti.llama_params_from_torch(
    hf.state_dict(), num_layers=2, num_heads=4, num_kv_heads=2)
params = jax.tree.map(np.asarray, params)

# our model with the SAME dims (incl. the checkpoint's norm eps)
model = get_model(ModelConfig(
    name="llama3_8b", dtype="float32", compute_dtype="float32",
    extra=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               mlp_dim=128, vocab_size=256, norm_eps=1e-5)))

# --- proof 1: logits agree ---------------------------------------------
tokens = np.random.default_rng(1).integers(0, 256, size=(2, 16))
ours = np.asarray(model.apply({"params": params},
                              tokens.astype(np.int32), train=False))
with torch.no_grad():
    theirs = hf(torch.from_numpy(tokens)).logits.numpy()
print(f"max logit diff vs HF: {np.abs(ours - theirs).max():.2e}")

# --- proof 2: greedy decode is token-identical to hf.generate ----------
prompt = np.array([[5, 9, 42, 7]], np.int32)
out = generate(model, params, prompt, max_new_tokens=12)
with torch.no_grad():
    want = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                       max_new_tokens=12, do_sample=False)
assert np.asarray(out)[0].tolist() == want[0].tolist()
print("greedy decode: token-identical to hf.generate")

# --- and back: export to an HF-layout state_dict -----------------------
back = ti.llama_params_to_torch(params)
print(f"exported {len(back)} tensors back to HF layout")

# For real checkpoints, the same flow via CLI:
#   python scripts/convert.py --arch llama3 --preset llama3_8b_zero \
#       --torch-checkpoint ckpt.pt --out runs/ckpt --model.extra '{...}'
#   python scripts/generate.py --checkpoint-dir runs/ckpt --tokenizer tok/
