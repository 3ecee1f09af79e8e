"""The plain reference: a decoder-only transformer's next-token loss in
straightforward ``jax.numpy`` and float32, dense or top-k expert FFN.

No kernel, no remat, no scan, no capacity: written from the published
descriptions (Mistral 7B, arXiv:2310.06825; Mixtral of Experts,
arXiv:2401.04088), independent of ``dlrover_tpu/models``. It reads the
program's parameter pytree (``tok_embed``, ``layers.{attn_norm, wq, wk,
wv, wo, ffn_norm, w1, w3, w2[, router]}`` stacked on a leading layer
axis, ``final_norm``, ``lm_head``) because the comparison needs the same
seeded weights; every leaf is cast to float32 first. ``fields`` is the
configuration file's dict.

Departures, each noted where it is made:
- RoPE rotates interleaved pairs (x0,x1), (x2,x3), ... as Mistral's own
  reference code does; the Hugging Face port rotates half-split pairs on
  permuted weights. Same function of differently ordered weights.
- The auxiliary (load-balancing) term is the program's definition, so
  that the two sides compute one loss: see ``_aux``.
- Sequences here are at most ``sliding_window`` long, so full causal
  attention is the published attention; longer ones are refused.

On a TPU a float32 matmul runs in bf16 passes unless told otherwise, so
everything runs under ``default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, H, D); rotate pair (2i, 2i+1) by position * theta^(-2i/D)."""
    _, S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _attention(x, layer, f):
    B, S, _ = x.shape
    H, KV = f["num_attention_heads"], f["num_key_value_heads"]
    D = f.get("head_dim") or f["hidden_size"] // H
    q = _rope((x @ layer["wq"]).reshape(B, S, H, D), f["rope_theta"])
    k = _rope((x @ layer["wk"]).reshape(B, S, KV, D), f["rope_theta"])
    v = (x @ layer["wv"]).reshape(B, S, KV, D)
    # grouped queries: query head h reads key/value head h // (H // KV)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * D)
    return out @ layer["wo"]


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _aux(probs, chosen, f, group):
    """The program's load-balancing term (``models/moe._route``): within
    each routing group of ``group`` tokens, E * sum_e (share of the k*g
    choices that picked e) * (mean router probability of e); mean over
    groups. Hugging Face's Mixtral pools all tokens and sums over the k
    choices instead of averaging them (twice this, at k = 2)."""
    E = f["num_local_experts"]
    picks = jax.nn.one_hot(chosen, E).reshape(-1, group, chosen.shape[-1], E)
    frac = picks.mean(axis=(1, 2))
    mean_prob = probs.reshape(-1, group, E).mean(axis=1)
    return E * jnp.mean(jnp.sum(frac * mean_prob, axis=-1))


def _expert_ffn(x, layer, f, group):
    """Top-k routing with renormalised gates, no token dropped: every
    token's output is the gate-weighted sum of its k experts' FFNs."""
    B, S, Dm = x.shape
    t = x.reshape(B * S, Dm)
    probs = jax.nn.softmax(t @ layer["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, f["num_experts_per_tok"])
    gates = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(t)
    for e in range(f["num_local_experts"]):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _swiglu(
            t, layer["w1"][e], layer["w3"][e], layer["w2"][e])
    return out.reshape(B, S, Dm), _aux(probs, chosen, f, group)


def next_token_loss(params, tokens, fields, *, route_group=None):
    """Mean next-token negative log-likelihood of ``tokens`` (B, S + 1),
    plus ``router_aux_loss_coef`` times the mean auxiliary term for an
    expert model. ``route_group`` is the program's routing group size
    (only the auxiliary term depends on it)."""
    f = fields
    window = f.get("sliding_window")
    if window and tokens.shape[1] - 1 > window:
        raise ValueError(
            f"sequence {tokens.shape[1] - 1} exceeds the sliding window "
            f"{window}: the reference has no windowed attention")
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = p["tok_embed"][inputs]
        experts = f.get("num_local_experts", 0)
        aux_sum = 0.0
        for n in range(f["num_hidden_layers"]):
            layer = jax.tree.map(lambda a: a[n], p["layers"])
            x = x + _attention(
                _rms_norm(x, layer["attn_norm"], f["rms_norm_eps"]), layer, f)
            h = _rms_norm(x, layer["ffn_norm"], f["rms_norm_eps"])
            if experts:
                y, aux = _expert_ffn(h, layer, f,
                                     route_group or inputs.shape[1])
                aux_sum = aux_sum + aux
            else:
                y = _swiglu(h, layer["w1"], layer["w3"], layer["w2"])
            x = x + y
        x = _rms_norm(x, p["final_norm"], f["rms_norm_eps"])
        logits = x @ p["lm_head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
        if experts:
            nll = nll + (f["router_aux_loss_coef"] * aux_sum
                         / f["num_hidden_layers"])
        return nll


def loss_and_grad_norm(params, tokens, fields, *, route_group=None):
    """(loss, global L2 norm of its gradient over every parameter)."""
    # differentiate with respect to the float32 copy: a gradient taken
    # through the cast would be rounded back to the stored type
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    loss, grads = jax.value_and_grad(next_token_loss)(
        params, tokens, fields, route_group=route_group)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    return loss, norm
