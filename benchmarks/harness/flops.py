"""Operations the algorithm needs, from shapes alone.

``fields`` is a configuration file's dict (the published ``config.json``
keys). Everything here is a count: valid on any machine.

Training FLOPs per token are what the forward and backward passes
require: 6 per matmul parameter the token meets (2 forward, 4 backward)
plus causal attention. The embedding gather is no matmul and is not
counted; of the experts only the ``num_experts_per_tok`` a token is
routed to count; recomputation (remat) and the slots an expert layout
pads (capacity factor) are the program's cost, not the algorithm's.

This is the library of the plain-decoder families: ``llama_dense`` and
``mixtral_moe`` answer ``param_count``, ``train_flops_per_token`` and
``flash_attention_flops`` from it. ``jobs/train.py`` and the readers ask
the cell's family, never this file (but ``named_kernels._least_flops``,
for a job that carries no count): an architecture these shapes do not
describe counts for itself in its own family, by the rule above.
"""


def head_dim(fields: dict) -> int:
    return fields.get("head_dim") or (
        fields["hidden_size"] // fields["num_attention_heads"])


def _attn_params(f: dict) -> int:
    d, hd = f["hidden_size"], head_dim(f)
    q, kv = f["num_attention_heads"] * hd, f["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def _ffn_params(f: dict) -> int:
    return 3 * f["hidden_size"] * f["intermediate_size"]


def param_count(f: dict) -> int:
    """Every parameter the state holds (embedding, norms, all experts)."""
    d, layers = f["hidden_size"], f["num_hidden_layers"]
    experts = f.get("num_local_experts", 0)
    per_layer = 2 * d + _attn_params(f)
    if experts:
        per_layer += d * experts + experts * _ffn_params(f)
    else:
        per_layer += _ffn_params(f)
    return 2 * f["vocab_size"] * d + d + layers * per_layer


def matmul_params_per_token(f: dict) -> int:
    """Parameters a token is multiplied with: attention projections, its
    FFN (or the router and its top-k experts' FFNs), the output head."""
    d = f["hidden_size"]
    experts = f.get("num_local_experts", 0)
    per_layer = _attn_params(f)
    if experts:
        per_layer += d * experts + f["num_experts_per_tok"] * _ffn_params(f)
    else:
        per_layer += _ffn_params(f)
    return f["num_hidden_layers"] * per_layer + d * f["vocab_size"]


def attention_matmul_flops(f: dict, seq: int, rows: int = 1) -> float:
    """One causal score-sized matmul over ``rows`` sequences, all heads,
    one layer: 2 * S * (S + 1) / 2 * head_dim multiply-adds' worth of
    FLOPs a head. QK^T is one such matmul, PV another."""
    return (rows * f["num_attention_heads"]
            * 2.0 * head_dim(f) * seq * (seq + 1) / 2)


# score-sized matmuls flash attention needs: forward QK^T and PV; backward
# the recomputed QK^T, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q
ATTN_FWD_MATMULS = 2
ATTN_BWD_MATMULS = 5


def flash_attention_flops(f: dict, seq: int, rows: int):
    """(forward, backward): the least the flash kernels need for one
    microbatch of ``rows`` sequences where every layer calls them once."""
    one = f["num_hidden_layers"] * attention_matmul_flops(f, seq, rows)
    return ATTN_FWD_MATMULS * one, ATTN_BWD_MATMULS * one


def train_flops_per_token(f: dict, seq: int) -> float:
    attn = ((ATTN_FWD_MATMULS + ATTN_BWD_MATMULS - 1)  # no recompute
            * attention_matmul_flops(f, seq) / seq)
    return 6.0 * matmul_params_per_token(f) + f["num_hidden_layers"] * attn
