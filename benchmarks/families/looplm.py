"""Looped language models (the Ouro models): one stack of dense layers
with sandwich norms run ``total_ut_steps`` times with the same weights, a
norm, head and exit gate after every pass, a loss over the exit
distribution; through the program's ``models/looped.py``.
``"family": "looplm"``.

No plain decoder, so the yardstick is this file's own: the reference is
``reference/looplm.py``, and the counts below are ``harness/flops.py``'s
shapes of one pass (the layers are a plain decoder's but for two norms)
with the README's FLOP rule's passes in them: a weight a token meets
``T`` times counts ``T`` times; the flash kernels are called ``T x L``
times a microbatch.
"""

import jax.numpy as jnp

from benchmarks.harness import flops
from benchmarks.reference import looplm
from dlrover_tpu.models import looped

# --rehearsal only: control flow on the CPU, never a measurement
REHEARSAL_FIELDS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
    "num_hidden_layers": 2, "total_ut_steps": 4,
    # bf16 at width 64 strays further from float32 than at width 2048
    "reference_tolerance": {"loss_rel": 2e-3, "grad_norm_rel": 2e-2},
}

init_params = looped.init_params
logical_axes = looped.param_logical_axes


def program_config(fields: dict, seq: int) -> looped.LoopedConfig:
    f = fields
    if f["hidden_size"] != f["num_attention_heads"] * f["head_dim"]:
        raise ValueError("models/llama.py ties head_dim to hidden/heads")
    if f["torch_dtype"] != "bfloat16" or f["hidden_act"] != "silu":
        raise ValueError("this family serves bf16 SwiGLU models only")
    if (set(f["layer_types"]) != {"full_attention"} or f["sliding_window"]
            or f["use_sliding_window"] or f["rope_scaling"]
            or f["tie_word_embeddings"]):
        raise ValueError(
            "models/looped.py computes full causal attention with plain "
            "RoPE in every layer and an untied head, nothing else")
    return looped.LoopedConfig(
        vocab_size=f["vocab_size"], dim=f["hidden_size"],
        n_layers=f["num_hidden_layers"], n_heads=f["num_attention_heads"],
        n_kv_heads=f["num_key_value_heads"], ffn_dim=f["intermediate_size"],
        max_seq_len=seq, rope_theta=f["rope_theta"],
        norm_eps=f["rms_norm_eps"], dtype=jnp.bfloat16, remat=True,
        remat_policy=f.get("remat_policy"), n_passes=f["total_ut_steps"],
        exit_entropy_beta=f["exit_entropy_beta"],
    )


loss_fn = looped.make_loss_fn


def reference(fields: dict, seq: int):
    return lambda params, tokens: looplm.loss_and_grad_norm(
        params, tokens, fields)


def param_count(f: dict) -> int:
    """A plain decoder's tree (embedding and head, two norms a layer, the
    final norm) plus two more norms a layer, the gate and its bias. The
    passes share all of it."""
    d = f["hidden_size"]
    return flops.param_count(f) + f["num_hidden_layers"] * 2 * d + d + 1


def train_flops_per_token(f: dict, seq: int) -> float:
    """Every pass meets every layer, the head and the gate: 6 a matmul
    parameter a pass, plus causal attention (no recompute) a layer
    application."""
    matmul = flops.matmul_params_per_token(f) + f["hidden_size"]  # the gate
    attn = ((flops.ATTN_FWD_MATMULS + flops.ATTN_BWD_MATMULS - 1)
            * flops.attention_matmul_flops(f, seq) / seq)
    return f["total_ut_steps"] * (
        6.0 * matmul + f["num_hidden_layers"] * attn)


def flash_attention_flops(f: dict, seq: int, rows: int):
    """Every layer of every pass calls the kernels once."""
    fwd, bwd = flops.flash_attention_flops(f, seq, rows)
    return f["total_ut_steps"] * fwd, f["total_ut_steps"] * bwd
