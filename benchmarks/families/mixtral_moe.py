"""Mixtral-class sparse-expert decoders through the program's
``models/moe.py``: the llama attention block, a top-k router with
renormalised gates, SwiGLU experts. ``"family": "mixtral_moe"``.

``fields["program"]`` holds what the program needs beyond the published
keys: ``capacity_factor`` and ``route_group_size`` of its slot layout.

Its yardstick is the library's (``reference/decoder.py``'s top-k FFN,
``harness/flops.py``'s active experts and one attention call a layer).
"""

import jax.numpy as jnp

from benchmarks.harness import flops
from benchmarks.reference import decoder
from dlrover_tpu.models import moe

REHEARSAL_FIELDS = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "program": {"capacity_factor": 2.0, "route_group_size": 32},
    # bf16 at width 64 strays further from float32 than at width 4096
    "reference_tolerance": {"loss_rel": 2e-3, "grad_norm_rel": 2e-2},
}

logical_axes = moe.param_logical_axes


def init_params(config, key):
    """``moe.init_params``, then the embedding rows brought to unit rms
    (times ``dim ** 0.5``, in the leaf's type; nothing else of the tree
    moves, and the reference reads the same tree). The published files
    give no initialisation. The program's default draws the rows at rms
    ``dim ** -0.5`` where its attention block's output on those weights
    has 0.07: at depth 1 the router then reads four parts causal mean of
    the value vectors, nearly one vector for every late token, to one
    part token, and one chip's experts draw 1.6 to 1.9 times the mean
    share of the pairs, by the seed. A trained model's residual stream
    is the token's own and its experts draw near-equal shares
    (arXiv:2401.04088, routing analysis): with unit rows the router sees
    the token (``configs/mixtral-8x7b.json`` ``assumed``; PERF.md, PR 32).
    """
    params = moe.init_params(config, key)
    rows = params["tok_embed"]
    return {**params,
            "tok_embed": rows * jnp.asarray(config.dim ** 0.5, rows.dtype)}


def program_config(fields: dict, seq: int) -> moe.MoEConfig:
    if fields["hidden_size"] != (fields["num_attention_heads"]
                                 * fields["head_dim"]):
        raise ValueError("models/moe.py ties head_dim to hidden/heads")
    if fields["torch_dtype"] != "bfloat16" or fields["hidden_act"] != "silu":
        raise ValueError("this family serves bf16 SwiGLU models only")
    program = fields["program"]
    experts, top_k = fields["num_local_experts"], fields["num_experts_per_tok"]
    if program["capacity_factor"] * top_k < experts:
        raise ValueError(
            "capacity_factor below experts / top_k drops tokens: not the "
            "published mathematics, and not what the reference computes")
    return moe.MoEConfig(
        vocab_size=fields["vocab_size"], dim=fields["hidden_size"],
        n_layers=fields["num_hidden_layers"],
        n_heads=fields["num_attention_heads"],
        n_kv_heads=fields["num_key_value_heads"],
        ffn_dim=fields["intermediate_size"], n_experts=experts, top_k=top_k,
        capacity_factor=program["capacity_factor"],
        route_group_size=program["route_group_size"],
        router_aux_weight=fields["router_aux_loss_coef"], max_seq_len=seq,
        rope_theta=fields["rope_theta"], norm_eps=fields["rms_norm_eps"],
        dtype=jnp.bfloat16, remat=True,
        remat_policy=fields.get("remat_policy"),
    )


def loss_fn(config, mesh):
    return lambda params, tokens: moe.next_token_loss(
        params, tokens, config, mesh)


def reference(fields: dict, seq: int):
    # only the auxiliary term depends on the program's routing group
    group = fields["program"]["route_group_size"]
    return lambda params, tokens: decoder.loss_and_grad_norm(
        params, tokens, fields, route_group=group)


param_count = flops.param_count
train_flops_per_token = flops.train_flops_per_token
flash_attention_flops = flops.flash_attention_flops
