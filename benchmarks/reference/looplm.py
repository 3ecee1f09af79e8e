"""The plain reference of a looped language model: one stack of layers
run ``total_ut_steps`` times with the same weights, a norm, a head and an
exit gate after every pass, and a loss over the exit distribution, in
straightforward ``jax.numpy`` and float32.

Written from the published description (ByteDance, "Scaling Latent
Reasoning via Looped Language Models", the Ouro models, and their
modelling code), independent of ``dlrover_tpu/models``:

- one layer, sandwich norm (four RMSNorms): ``a = x + N2(Attn(N1(x)))``,
  ``y = a + N4(SwiGLU(N3(a)))``; ``Attn`` is full causal multi-head
  attention with RoPE on q and k, no bias;
- the loop: ``h_0 = E[tokens]``; ``h_t = N_f(Stack(h_{t-1}))`` for
  ``t = 1..T``, the same layers and the same final norm at every ``t``;
  the normed ``h_t`` is what the next pass takes;
- after every pass ``logits_t = h_t W_head`` and, per token,
  ``lambda_t = sigmoid(w_g . h_t + b_g)``;
- exit distribution ``p(t) = lambda_t prod_{j<t}(1 - lambda_j)`` for
  ``t < T``, ``p(T) = prod_{j<T}(1 - lambda_j)``: the last gate output
  takes no part;
- loss (stage I, uniform prior): the mean over tokens of ``sum_t p(t)
  NLL_t - beta H(p)``, ``H(p) = -sum_t p(t) log p(t)``, ``NLL_t`` the
  next-token negative log-likelihood under ``logits_t``; ``beta`` is the
  configuration file's ``exit_entropy_beta``.

It reads the program's parameter tree (``tok_embed``, ``layers.{attn_norm,
wq, wk, wv, wo, attn_post_norm, ffn_norm, w1, w3, w2, ffn_post_norm}``
stacked on a leading layer axis, ``final_norm``, ``lm_head``,
``exit_gate.{w, b}``) because the comparison needs the same seeded
weights; every leaf is cast to float32 first. The blocks are
``decoder.py``'s (RoPE on interleaved pairs, as there).

Computed in blocks so that it fits beside the bf16 parameters at
published widths: each layer application and each pass's head runs under
``jax.checkpoint``, which keeps only the block's input for the backward
pass and computes the block again there. That changes no arithmetic: the
same operations on the same values in the same order, twice. Without it
32 score tensors of 16 x 4096^2 x 4 B = 1.07 GB would all be alive. The
passes and the layers of a pass are walked by ``lax.scan``, which changes
no arithmetic either: the compiler then sees one layer and not 32 (the
unrolled walk took 270 s of set-up to compile for the chip).

``fault`` plants one for the controls (tests, and the chip's control
run): each must break the agreement with the program.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder import _attention, _rms_norm, _swiglu

FAULTS = (None, "one_pass_fewer", "pre_norm_only", "unnormed_state")


def _layer(x, layer, f, sandwich):
    eps = f["rms_norm_eps"]
    a = _attention(_rms_norm(x, layer["attn_norm"], eps), layer, f)
    if sandwich:
        a = _rms_norm(a, layer["attn_post_norm"], eps)
    x = x + a
    y = _swiglu(_rms_norm(x, layer["ffn_norm"], eps),
                layer["w1"], layer["w3"], layer["w2"])
    if sandwich:
        y = _rms_norm(y, layer["ffn_post_norm"], eps)
    return x + y


def _head(h, lm_head, exit_gate, targets):
    """Per token: NLL of ``targets`` under ``h W_head``, and the gate."""
    logp = jax.nn.log_softmax(h @ lm_head, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    gate = jax.nn.sigmoid(h @ exit_gate["w"] + exit_gate["b"])
    return nll, gate


def next_token_loss(params, tokens, fields, *, fault=None):
    """The loss of ``tokens`` (B, S + 1) as the docstring above has it."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    f = fields
    passes = f["total_ut_steps"] - (fault == "one_pass_fewer")
    layer_fn = jax.checkpoint(
        lambda x, layer: (_layer(x, layer, f, fault != "pre_norm_only"), None))
    head_fn = jax.checkpoint(_head)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def one_pass(x, _):
            x, _ = jax.lax.scan(layer_fn, x, p["layers"])
            h = _rms_norm(x, p["final_norm"], f["rms_norm_eps"])
            nll, gate = head_fn(h, p["lm_head"], p["exit_gate"], targets)
            return (x if fault == "unnormed_state" else h), (nll, gate)

        # (T, B, S) each: NLL_t and lambda_t of every token after pass t
        _, (nll, gate) = jax.lax.scan(
            one_pass, p["tok_embed"][inputs], None, length=passes)
        # prod_{j<t} (1 - lambda_j) for t = 1..T, then p(t)
        stay = jnp.concatenate(
            [jnp.ones_like(gate[:1]), jnp.cumprod(1.0 - gate[:-1], axis=0)])
        prob = jnp.concatenate([gate[:-1] * stay[:-1], stay[-1:]])
        entropy = -jnp.sum(prob * jnp.log(prob), axis=0)
        expected = jnp.sum(prob * nll, axis=0)
        return jnp.mean(expected - f["exit_entropy_beta"] * entropy)


def loss_and_grad_norm(params, tokens, fields, *, fault=None):
    """(loss, global L2 norm of its gradient over every parameter)."""
    # differentiate with respect to the float32 copy: a gradient taken
    # through the cast would be rounded back to the stored type
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    loss, grads = jax.value_and_grad(next_token_loss)(
        params, tokens, fields, fault=fault)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    return loss, norm
