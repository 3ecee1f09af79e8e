"""Looped (weight-shared, recurrent-depth) decoder, TPU-first.

One stack of Llama-class layers run ``n_passes`` times with the same
weights ("Scaling Latent Reasoning via Looped Language Models", the
Ouro models). What it adds to models/llama.py, whose blocks it composes
as models/moe.py does:

- **sandwich norm**: a layer norms each branch again before it joins the
  residual, ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(SwiGLU(N3(a)))``
  (``attn_post_norm`` / ``ffn_post_norm`` in the layer tree);
- **the loop**: ``h_t = N_f(Stack(h_{t-1}))``, the normed state is what the
  next pass takes; a ``lax.scan`` over passes around the layer scan, so
  the HLO holds one pass whatever ``n_passes`` is;
- **an exit head after every pass**: ``logits_t = h_t W_head`` and an exit
  gate ``lambda_t = sigmoid(w_g . h_t + b_g)`` per token;
- **a loss over the exit distribution** ``p(t) = lambda_t prod_{j<t}(1 -
  lambda_j)``, ``p(T) = prod_{j<T}(1 - lambda_j)``: the mean over tokens of
  ``sum_t p(t) NLL_t - beta H(p)`` (the paper's stage-I objective).

Memory is what shapes the code. A microbatch keeps ``n_passes x n_layers``
layer applications alive for the backward pass, so the layers are fully
rematerialised by default (``remat_policy`` None: one (B, S, D) input a
layer application, and the flash kernel's output and log-sum-exp that
every policy keeps, about one (B, S, D) more, so that the backward pass
does not run the forward kernel again), and each pass's head runs under
``jax.checkpoint`` so
that only ``h_t`` survives it, not ``n_passes`` f32 logit blocks. The
shared weights' cotangents are summed over the passes in float32
(``_summed_in_f32``).
"""

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama as _llama


@dataclass(frozen=True)
class LoopedConfig(_llama.LlamaConfig):
    # every layer application is kept for the backward pass n_passes times
    # over: save nothing by default but the flash kernel's output and
    # log-sum-exp, which every policy keeps ("dots" is 12x the bytes a layer)
    remat_policy: Optional[str] = None
    n_passes: int = 4
    # weight of the exit distribution's entropy in the loss
    exit_entropy_beta: float = 0.05

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LoopedConfig":
        """CI-sized config."""
        return LoopedConfig(
            vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat=False,
            n_passes=3,
        )


def param_logical_axes(config: LoopedConfig) -> Dict:
    """The llama tree's axes plus the two post-norms a layer and the gate."""
    axes = _llama.param_logical_axes(config)
    axes["layers"]["attn_post_norm"] = ("layers", "norm")
    axes["layers"]["ffn_post_norm"] = ("layers", "norm")
    axes["exit_gate"] = {"w": ("norm",), "b": ()}
    return axes


def init_params(config: LoopedConfig, key) -> Dict:
    """The llama tree + ``attn_post_norm`` / ``ffn_post_norm`` a layer +
    ``exit_gate: {w: (dim,), b: ()}``. The head gets a key of its own:
    ``llama.init_params`` draws embedding and head from one."""
    c = config
    base_key, head_key, gate_key = jax.random.split(key, 3)
    params = _llama.init_params(c, base_key)
    params["lm_head"] = _llama.dense_init(
        head_key, (c.dim, c.vocab_size), c.dim, c.dtype)
    for name in ("attn_post_norm", "ffn_post_norm"):
        params["layers"][name] = jnp.ones((c.n_layers, c.dim), dtype=c.dtype)
    params["exit_gate"] = {
        "w": _llama.dense_init(gate_key, (c.dim,), c.dim, c.dtype),
        "b": jnp.zeros((), dtype=c.dtype),
    }
    return params


def _summed_in_f32(shared):
    """``shared`` as the pass loop closes over it. Autodiff sums a
    closed-over value's cotangents over the scan's iterations in that
    value's own type: bf16 weights would get a bf16 sum of ``n_passes``
    gradients. So the loop closes over float32 copies and casts back
    inside the pass (:func:`_as_stored`): the sum is float32, rounded to
    the stored type once, as every other gradient leaf is. The round trip
    is exact, and XLA folds it away in the forward pass."""
    return jax.tree.map(lambda w: w.astype(jnp.float32), shared)


def _as_stored(shared32, shared):
    return jax.tree.map(lambda w, like: w.astype(like.dtype),
                        shared32, shared)


def _exit_head(h, head, gate, targets, mesh):
    """One pass's head on the normed state ``h`` (B, S, D): per-token NLL
    of ``targets`` under ``h W_head`` and the exit gate's logit, both f32
    (B, S). Under ``jax.checkpoint`` in the loop: the (B, S, vocab) f32
    logits are made again in the backward pass, never kept."""
    with jax.named_scope("loop_head"):
        nll = _llama.head_nll(h, head, targets, mesh)
    with jax.named_scope("loop_gate"):
        logit = jnp.einsum(
            "bsd,d->bs", h.astype(jnp.float32), gate["w"].astype(jnp.float32),
        ) + gate["b"].astype(jnp.float32)
    return nll, logit


def loss_and_stats(params, tokens, config: LoopedConfig,
                   mesh=None) -> Tuple[jax.Array, Dict]:
    """(loss, stats) of ``tokens`` (B, S + 1): the loss over the exit
    distribution, and ``pass_nll`` (n_passes,), ``exit_mass`` (n_passes,)
    and ``exit_entropy`` (): means over the tokens, f32."""
    c = config
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    shared = {k: params[k]
              for k in ("layers", "final_norm", "lm_head", "exit_gate")}
    shared32 = _summed_in_f32(shared)
    head = jax.checkpoint(
        functools.partial(_exit_head, mesh=mesh), prevent_cse=False)

    def one_pass(carry, t):
        h, log_stay, loss, plogp = carry
        w = _as_stored(shared32, shared)
        with jax.named_scope("loop_stack"):
            h, _ = _llama.decoder_stack(h, w["layers"], c, positions, mesh)
            h = _llama.rms_norm(h, w["final_norm"], c.norm_eps)
        nll, logit = head(h, w["lm_head"], w["exit_gate"], targets)
        # p(t) = lambda_t * prod_{j<t}(1 - lambda_j), in logs; the last
        # pass takes all that is left, so its gate's output is unused
        last = t == c.n_passes - 1
        log_p = log_stay + jnp.where(last, 0.0, jax.nn.log_sigmoid(logit))
        p = jnp.exp(log_p)
        carry = (h, log_stay + jax.nn.log_sigmoid(-logit),
                 loss + p * nll, plogp + p * log_p)
        return carry, (nll.mean(), p.mean())

    zeros = jnp.zeros((B, S), jnp.float32)
    (_, _, loss, plogp), (pass_nll, exit_mass) = jax.lax.scan(
        one_pass, (params["tok_embed"][inputs], zeros, zeros, zeros),
        jnp.arange(c.n_passes),
    )
    entropy = -plogp.mean()
    stats = {"pass_nll": pass_nll, "exit_mass": exit_mass,
             "exit_entropy": entropy}
    return loss.mean() - c.exit_entropy_beta * entropy, stats


def next_token_loss(params, tokens, config: LoopedConfig, mesh=None):
    """The scalar loss of :func:`loss_and_stats`."""
    return loss_and_stats(params, tokens, config, mesh)[0]


def make_loss_fn(config: LoopedConfig, mesh=None, with_stats: bool = False):
    """``loss_fn(params, microbatch)`` for ``ElasticTrainer``: the scalar
    loss, or with ``with_stats`` the pair ``(loss, stats)``, which the
    trainer averages over the microbatches into ``TrainStepResult.stats``.
    ``span_attrs`` rides on the function for the ``train.step`` span."""
    fn = loss_and_stats if with_stats else next_token_loss

    def loss_fn(params, microbatch):
        return fn(params, microbatch, config, mesh)

    loss_fn.span_attrs = {"passes": config.n_passes}
    return loss_fn


def publish_stats(stats: Dict, registry=None) -> None:
    """Registry gauges from stats the caller has *already* read back to
    the host (``jax.device_get(result.stats)`` where it reads the loss):
    ``dlrover_loop_exit_mass{pass}``, ``dlrover_loop_pass_nll{pass}``,
    ``dlrover_loop_exit_entropy``. Never called on the step path."""
    from dlrover_tpu.observability.registry import get_registry

    reg = registry or get_registry()
    mass = reg.gauge("dlrover_loop_exit_mass",
                     "Mean exit probability of each pass", ("pass",))
    nll = reg.gauge("dlrover_loop_pass_nll",
                    "Mean next-token NLL under each pass's head", ("pass",))
    # one label value a pass: bounded by the config's n_passes
    for t, (m, n) in enumerate(
            zip(stats["exit_mass"], stats["pass_nll"]), start=1):
        mass.labels(t).set(float(m))
        nll.labels(t).set(float(n))
    reg.gauge("dlrover_loop_exit_entropy",
              "Mean entropy of the exit distribution").set(
                  float(stats["exit_entropy"]))


def num_params(config: LoopedConfig) -> int:
    """Independent of ``n_passes``: the passes share every weight."""
    c = config
    return _llama.num_params(c) + 2 * c.n_layers * c.dim + c.dim + 1
