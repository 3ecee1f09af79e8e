"""Dense Llama-class decoders (GQA, RoPE, RMSNorm, SwiGLU) through the
program's ``models/llama.py``. A configuration file names this family by
``"family": "llama_dense"``; its keys are the published ``config.json``'s.

The family is the only place that knows what its architecture computes:
``run.py`` holds the contract (``FAMILY_CONTRACT``) and ``jobs/train.py``
asks through it. This one is a plain decoder, so its yardstick is the
library's: ``reference/decoder.py`` and ``harness/flops.py``.
"""

import jax.numpy as jnp

from benchmarks.harness import flops
from benchmarks.reference import decoder
from dlrover_tpu.models import llama

# --rehearsal only: control flow on the CPU, never a measurement
REHEARSAL_FIELDS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "sliding_window": None,
    # bf16 at width 64 strays further from float32 than at width 4096
    "reference_tolerance": {"loss_rel": 2e-3, "grad_norm_rel": 2e-2},
}

init_params = llama.init_params
logical_axes = llama.param_logical_axes


def program_config(fields: dict, seq: int) -> llama.LlamaConfig:
    if fields["hidden_size"] != (fields["num_attention_heads"]
                                 * fields["head_dim"]):
        raise ValueError("models/llama.py ties head_dim to hidden/heads")
    if fields["torch_dtype"] != "bfloat16" or fields["hidden_act"] != "silu":
        raise ValueError("this family serves bf16 SwiGLU models only")
    return llama.LlamaConfig(
        vocab_size=fields["vocab_size"], dim=fields["hidden_size"],
        n_layers=fields["num_hidden_layers"],
        n_heads=fields["num_attention_heads"],
        n_kv_heads=fields["num_key_value_heads"],
        ffn_dim=fields["intermediate_size"], max_seq_len=seq,
        rope_theta=fields["rope_theta"], norm_eps=fields["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=True,
        remat_policy=fields.get("remat_policy"),
    )


def loss_fn(config, mesh):
    return lambda params, tokens: llama.next_token_loss(
        params, tokens, config, mesh)


def reference(fields: dict, seq: int):
    return lambda params, tokens: decoder.loss_and_grad_norm(
        params, tokens, fields)


param_count = flops.param_count
train_flops_per_token = flops.train_flops_per_token
flash_attention_flops = flops.flash_attention_flops
