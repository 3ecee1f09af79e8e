"""DeepSeek-V3-class decoders (the Moonlight models) through the
program's ``models/moe.py`` and ``models/mla.py``: latent attention in
every layer, ``first_k_dense_replace`` leading dense SwiGLU layers, then
expert layers of a sigmoid router with a selection bias over
``program.router_experts`` experts, of which this chip holds
``n_routed_experts`` from ``program.first_expert`` on, beside
``n_shared_experts`` shared ones. ``"family": "mla_moe"``. The keys are
the published ``config.json``'s; ``reduced`` says which hold this chip's
share (the experts held, the vocabulary's slice) and the depth.

No plain decoder, so the yardstick is this file's own: the reference is
``reference/mla_moe.py``, and the counts follow the README's FLOP rule
(6 a matmul parameter a token meets, causal attention without
recompute), with two rules of this architecture's:

- **Unequal query/key and value widths.** A causal score-sized matmul
  over one row of S positions, one head and one layer costs
  ``2 * S (S + 1) / 2 * d`` FLOPs where d is the width it contracts or
  produces: ``D_qk = qk_nope + qk_rope`` for QK^T, dQ and dK, ``D_v =
  v_head_dim`` for PV, dP = dO V^T and dV. The flash kernels' least is
  ``(D_qk + D_v)`` such units forward and ``(3 D_qk + 2 D_v)`` backward
  (the recomputed QK^T among them); training counts ``3 (D_qk + D_v)``
  (no recompute).
- **A chip's share of routed experts.** A token meets ``k`` of the
  router's ``E`` experts; this chip holds ``n`` of them, so a token
  meets ``k * n / E`` held experts in the mean: 6 x 8 / 64 = 0.75
  expert applications a token and layer here. The router counts at its
  whole width E, the shared experts for every token, the head over the
  vocabulary's slice. Parameters counted are the held state.
"""

import jax.numpy as jnp

from benchmarks.reference import mla_moe as reference_impl
from dlrover_tpu.models import mla, moe

# --rehearsal only: control flow on the CPU, never a measurement. Values
# narrower than queries and keys, and a router wider than the experts held
REHEARSAL_FIELDS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "vocab_size": 256,
    "num_hidden_layers": 3,
    "program": {"router_experts": 16, "first_expert": 4,
                "capacity_factor": 6.0},
    # bf16 at width 64 strays further from float32 than at width 2048
    "reference_tolerance": {"loss_rel": 2e-3, "grad_norm_rel": 3e-2},
}

logical_axes = moe.param_logical_axes


def program_config(fields: dict, seq: int) -> moe.MoEConfig:
    f, program = fields, fields["program"]
    if f["torch_dtype"] != "bfloat16" or f["hidden_act"] != "silu":
        raise ValueError("this family serves bf16 SwiGLU models only")
    if (f["q_lora_rank"] is not None or f["scoring_func"] != "sigmoid"
            or f["topk_method"] != "noaux_tc" or f["n_group"] != 1
            or f["topk_group"] != 1 or f["moe_layer_freq"] != 1
            or not f["norm_topk_prob"] or f["tie_word_embeddings"]
            or f.get("rope_scaling") or f["attention_bias"]
            or f["num_key_value_heads"] != f["num_attention_heads"]):
        raise ValueError(
            "models/moe.py computes latent attention without query "
            "compression, plain RoPE, and a sigmoid top-k router of one "
            "group with renormalized gates in every layer after the dense "
            "ones, an untied head, nothing else")
    if seq > f["max_position_embeddings"]:
        raise ValueError(f"sequence {seq} is past the model's context")
    experts, top_k = program["router_experts"], f["num_experts_per_tok"]
    if program["capacity_factor"] * top_k < experts:
        raise ValueError(
            "capacity_factor below router experts / top_k drops tokens: "
            "not the published mathematics, and not what the reference "
            "computes")
    return moe.MoEConfig(
        vocab_size=f["vocab_size"], dim=f["hidden_size"],
        n_layers=f["num_hidden_layers"], n_heads=f["num_attention_heads"],
        n_kv_heads=f["num_key_value_heads"],
        ffn_dim=f["moe_intermediate_size"], n_experts=f["n_routed_experts"],
        top_k=top_k, router_experts=experts,
        first_expert=program["first_expert"], router_score="sigmoid",
        routed_scaling=f["routed_scaling_factor"],
        shared_ffn_dim=f["n_shared_experts"] * f["moe_intermediate_size"],
        n_dense_layers=f["first_k_dense_replace"],
        dense_ffn_dim=f["intermediate_size"],
        mla=mla.MLAShape(
            kv_lora_rank=f["kv_lora_rank"],
            qk_nope_dim=f["qk_nope_head_dim"],
            qk_rope_dim=f["qk_rope_head_dim"], v_head_dim=f["v_head_dim"],
            latent_eps=f["kv_a_layernorm_eps"]),
        capacity_factor=program["capacity_factor"],
        router_aux_weight=f["aux_loss_alpha"] if f["seq_aux"] else 0.0,
        max_seq_len=seq, rope_theta=f["rope_theta"],
        norm_eps=f["rms_norm_eps"], dtype=jnp.bfloat16, remat=True,
        remat_policy=f.get("remat_policy"),
    )


def init_params(config, key):
    """``moe.init_params``, then the embedding rows brought to unit rms,
    as ``families/mixtral_moe.py`` does and for its reason: the router
    then reads the token, not the causal mean of the value vectors that
    the program's default leaves it at initialisation (the
    configuration's ``assumed``)."""
    params = moe.init_params(config, key)
    rows = params["tok_embed"]
    return {**params,
            "tok_embed": rows * jnp.asarray(config.dim ** 0.5, rows.dtype)}


def loss_fn(config, mesh):
    return moe.make_loss_fn(config, mesh)


def reference(fields: dict, seq: int):
    return lambda params, tokens: reference_impl.loss_and_grad_norm(
        params, tokens, fields)


def _attention_params(f: dict) -> int:
    """One layer's attention matmul parameters."""
    d, h = f["hidden_size"], f["num_attention_heads"]
    nope, rope, dv = (f["qk_nope_head_dim"], f["qk_rope_head_dim"],
                      f["v_head_dim"])
    latent = f["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (latent + rope)
            + latent * h * (nope + dv) + h * dv * d)


def _swiglu(f: dict, width: int) -> int:
    return 3 * f["hidden_size"] * width


def _layers(f: dict):
    dense = f["first_k_dense_replace"]
    return dense, f["num_hidden_layers"] - dense


def param_count(f: dict) -> int:
    """The held state: embedding and head (the vocabulary's slice), the
    final norm; a layer's attention, its latent norm and two norms; a
    dense layer's SwiGLU; an expert layer's router and selection bias at
    the router's width, its shared experts and the held experts."""
    d, vocab = f["hidden_size"], f["vocab_size"]
    router = f["program"]["router_experts"]
    dense, expert = _layers(f)
    attention = _attention_params(f) + f["kv_lora_rank"] + 2 * d
    moe_width = f["moe_intermediate_size"]
    return (2 * vocab * d + d
            + dense * (attention + _swiglu(f, f["intermediate_size"]))
            + expert * (attention + d * router + router
                        + _swiglu(f, f["n_shared_experts"] * moe_width)
                        + f["n_routed_experts"] * _swiglu(f, moe_width)))


def matmul_params_per_token(f: dict) -> float:
    """Matmul parameters a token meets on this chip, in the mean: every
    layer's attention, the dense SwiGLU, the shared experts, the router
    at its whole width, ``k * held / E`` held experts, the head's slice."""
    d = f["hidden_size"]
    router = f["program"]["router_experts"]
    dense, expert = _layers(f)
    moe_width = f["moe_intermediate_size"]
    held_applications = (f["num_experts_per_tok"] * f["n_routed_experts"]
                         / router)
    return (f["num_hidden_layers"] * _attention_params(f)
            + dense * _swiglu(f, f["intermediate_size"])
            + expert * (_swiglu(f, f["n_shared_experts"] * moe_width)
                        + d * router
                        + held_applications * _swiglu(f, moe_width))
            + d * f["vocab_size"])


def _score(f: dict, seq: int, rows: int, width: int) -> float:
    """One causal score-sized matmul of ``width`` over ``rows`` sequences,
    all heads, one layer."""
    return rows * f["num_attention_heads"] * 2.0 * width * seq * (seq + 1) / 2


def flash_attention_flops(f: dict, seq: int, rows: int):
    """(forward, backward): the least the flash kernels need for one
    microbatch of ``rows`` sequences, every layer calling them once."""
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    v = f["v_head_dim"]
    layers = f["num_hidden_layers"]
    return (layers * _score(f, seq, rows, qk + v),
            layers * _score(f, seq, rows, 3 * qk + 2 * v))


def train_flops_per_token(f: dict, seq: int) -> float:
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    attention = _score(f, seq, 1, 3 * (qk + f["v_head_dim"])) / seq
    return (6.0 * matmul_params_per_token(f)
            + f["num_hidden_layers"] * attention)
