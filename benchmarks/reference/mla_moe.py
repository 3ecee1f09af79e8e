"""The plain reference of a DeepSeek-V3-class decoder (the Moonlight
models): latent attention, leading dense layers, then layers of a
sigmoid-routed share of experts beside shared ones; next-token loss and
its gradient in straightforward ``jax.numpy`` and float32.

Written from the published descriptions (DeepSeek-V2, arXiv:2405.04434
§2.1; DeepSeek-V3, arXiv:2412.19437 §2.1) and the configuration file's
keys, independent of ``dlrover_tpu/models``. It reads the program's
parameter pytree because the comparison needs the same seeded weights:
``tok_embed``, ``dense_layers`` and ``layers`` (each stacked on a leading
layer axis: ``attn_norm, wq, wkv_a, kv_norm, wkv_b, wo, ffn_norm`` and
the FFN leaves, ``w1, w3, w2`` dense; ``router, router_bias, w1, w3, w2``
of the held experts, ``shared_w1, shared_w3, shared_w2`` in the expert
layers), ``final_norm``, ``lm_head``. Every leaf is cast to float32.

The expert layer is the chip's share (``fields["program"]``:
``first_expert``; ``n_routed_experts`` experts held of
``router_experts``): the router scores all of them, and what the held
experts give, with the shared experts, is the layer's output here. What
the other chips' experts would add is left out, as the program leaves it
out. Each held expert runs over every token, weighted by its gate where
the token chose it and by zero elsewhere.

Departures, each noted where it is made:
- RoPE rotates interleaved pairs (x0, x1), (x2, x3), ... of the rope
  columns, as DeepSeek's own code does (the Hugging Face port
  de-interleaves first and rotates half-split pairs: the same function).
- The sequence-wise balance loss weighs each layer's term by
  ``aux_loss_alpha`` and sums over the layers, as DeepSeek's code adds each
  layer's; its coefficient is assumed (the configuration's ``assumed``).
- The selection bias is moved by the optimizer: the loss holds a term of
  value zero, ``sum_e (b_e - sg(b_e)) * sg(load_e - 1 / E)`` a layer,
  whose gradient is each expert's share of the pairs less the mean
  share. It adds nothing to the loss and enters the gradient's norm as
  the program's does (the configuration's ``departures``).

Attention is computed over blocks of queries under ``lax.map``, each
block rematerialised, and every layer under ``jax.checkpoint``: the
gradient at 8,192 positions then holds one block's scores and one
layer's activations at a time beside the parameters, where whole
``[1, 16, 8192, 8192]`` float32 scores would be 4.3 GB a layer. On a TPU
a float32 matmul runs in bf16 passes unless told otherwise, so everything
runs under ``default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, H, R); rotate pair (2i, 2i+1) by position * theta^(-2i/R)."""
    _, S, _, R = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _causal_softmax_attention(q, k, v):
    """q, k (B, S, H, Dqk), v (B, S, H, Dv) → (B, S, H, Dv), over blocks
    of ``QUERY_BLOCK`` queries (S a multiple of it, or one block)."""
    B, S, H, Dqk = q.shape
    block = min(QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"sequence {S} is no multiple of {block}")

    @jax.checkpoint
    def one_block(args):
        start, qb = args                          # qb (B, block, H, Dqk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(1.0 * Dqk)
        rows = start + jnp.arange(block)[:, None]
        allowed = jnp.arange(S)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = q.reshape(B, S // block, block, H, Dqk).swapaxes(0, 1)
    starts = jnp.arange(S // block) * block
    out = jax.lax.map(one_block, (starts, blocks))   # (n, B, block, H, Dv)
    return out.swapaxes(0, 1).reshape(B, S, H, v.shape[-1])


def _attention(x, layer, f):
    B, S, _ = x.shape
    H = f["num_attention_heads"]
    nope, rope = f["qk_nope_head_dim"], f["qk_rope_head_dim"]
    latent, dv = f["kv_lora_rank"], f["v_head_dim"]
    q = (x @ layer["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ layer["wkv_a"]
    c = _rms_norm(kv_a[..., :latent], layer["kv_norm"],
                  f["kv_a_layernorm_eps"])
    k_rope = _rope(kv_a[..., None, latent:], f["rope_theta"])   # one head
    kv = (c @ layer["wkv_b"]).reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              f["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    out = _causal_softmax_attention(q, k, kv[..., nope:])
    return out.reshape(B, S, H * dv) @ layer["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _expert_layer(x, layer, f):
    """(output, sequence-wise balance term, the bias's pull) of one
    expert layer's FFN on normed x (B, S, D)."""
    B, S, D = x.shape
    program = f["program"]
    E, k = program["router_experts"], f["num_experts_per_tok"]
    first, held = program["first_expert"], f["n_routed_experts"]
    scores = jax.nn.sigmoid(x @ layer["router"])            # (B, S, E)
    bias = layer["router_bias"]
    _, chosen = jax.lax.top_k(scores + bias, k)           # bias chooses
    top = jnp.take_along_axis(scores, chosen, -1)         # scores gate
    gates = f["routed_scaling_factor"] * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    out = _swiglu(x, layer["shared_w1"], layer["shared_w3"],
                  layer["shared_w2"])
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        out = out + weight[..., None] * _swiglu(
            x, layer["w1"][e], layer["w3"][e], layer["w2"][e])
    # DeepSeek-V3's sequence-wise balance loss, a sequence at a time:
    # f_e = E / (k S) * picks of e, P_e = mean over t of s_e,t / sum_j s_j,t
    picks = jax.nn.one_hot(chosen, E).sum(axis=(1, 2))    # (B, E)
    share = (scores / scores.sum(-1, keepdims=True)).mean(axis=1)
    seq_aux = jnp.mean(jnp.sum(E / (k * S) * picks * share, -1))
    load = picks.sum(0) / (B * S * k)
    pull = jnp.sum((bias - jax.lax.stop_gradient(bias))
                   * jax.lax.stop_gradient(load - 1.0 / E))
    return out, seq_aux, pull


def _layer(x, layer, f, expert):
    eps = f["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, layer["attn_norm"], eps), layer, f)
    h = _rms_norm(x, layer["ffn_norm"], eps)
    if not expert:
        return x + _swiglu(h, layer["w1"], layer["w3"], layer["w2"]), 0.0
    y, seq_aux, pull = _expert_layer(h, layer, f)
    return x + y, f["aux_loss_alpha"] * seq_aux + pull


def next_token_loss(params, tokens, fields):
    """Mean next-token negative log-likelihood of ``tokens`` (B, S + 1)
    over the vocabulary the head holds, plus the expert layers' balance
    terms."""
    f = fields
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = p["tok_embed"][inputs]
        extra = 0.0
        stacks = (("dense_layers", False), ("layers", True))
        for stack, expert in stacks:
            for n in range(p[stack]["attn_norm"].shape[0]):
                layer = jax.tree.map(lambda a: a[n], p[stack])
                x, term = jax.checkpoint(
                    lambda x, layer: _layer(x, layer, f, expert))(x, layer)
                extra = extra + term
        x = _rms_norm(x, p["final_norm"], f["rms_norm_eps"])
        logp = jax.nn.log_softmax(x @ p["lm_head"], axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
        return nll + extra


def loss_and_grad_norm(params, tokens, fields):
    """(loss, global L2 norm of its gradient over every parameter)."""
    # differentiate with respect to the float32 copy: a gradient taken
    # through the cast would be rounded back to the stored type
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    loss, grads = jax.value_and_grad(next_token_loss)(params, tokens, fields)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                        jax.tree.leaves(grads)))
    return loss, norm
