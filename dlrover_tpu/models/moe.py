"""Mixtral-class sparse Mixture-of-Experts decoder, TPU-first.

The reference delegates MoE entirely to Megatron/DeepSpeed (SURVEY.md §2.7:
EP "absent — delegated to frameworks"); a from-scratch TPU stack owns it.
Design for the MXU:

- **the experts compute the rows that were routed and no others**: the
  (token, choice) pairs of a microbatch are sorted by expert, their rows
  gathered into one buffer of static size, and each projection is one
  grouped matmul over it whose ``group_sizes`` say which rows belong to
  which expert. Rows past the last kept pair cost no MXU time, and where
  the chip holds a share of the router's experts only the kept pairs'
  rows are moved into the buffer and back into their tokens. (Until
  PR 30 routing was two dense einsums against a (tokens, experts,
  capacity) one-hot, which computes every slot an expert might fill:
  four times the useful rows where no token may be dropped);
- **capacity is a mask, not a shape**: a pair beyond its expert's
  capacity in its routing group is sorted last, counted in no group and
  adds nothing to its token, which then falls through the residual
  connection (standard Switch behavior). One path for every
  ``capacity_factor``;
- **every expert on every chip, sliced by columns**: the three expert
  leaves shard their ``ffn_dim`` over the ``ep`` and ``tp`` mesh axes
  together (the ``expert_mlp`` logical axis, parallel/sharding.py
  DEFAULT_RULES) and no other dimension but ``embed``. Under a mesh the
  sorted block runs in a ``shard_map`` (a Pallas call has no
  partitioning rule): every chip of the group holds the same rows, sorts
  all pairs into all experts' groups and computes every routed pair over
  its own columns, and the one all-reduce that sums SwiGLU's partial
  down projections joins them. The work a chip does is the same
  whatever the routing: with whole experts on chips the group waits for
  the chip whose experts drew the most pairs (PERF.md §6, PR 33);
- **a chip's share of many experts**: the router scores
  ``router_experts`` experts (its published width) and the chip holds
  ``n_experts`` of them, from ``first_expert`` on. Only pairs routed to
  a held expert are sorted into groups and computed; what the others
  add lies on other chips of the expert-parallel group and is left out
  here (one chip runs no exchange). ``router_experts`` None holds them
  all;
- **two routers**, both in f32: ``"softmax"``, Mixtral's top-k of the
  softmax with renormalized gates and a Switch-style load-balancing loss
  per routing group; ``"sigmoid"``, DeepSeek-V3's (arXiv:2412.19437
  §2.1.2): sigmoid scores, the top k of scores plus a selection bias
  ``router_bias`` that chooses and never gates, gates renormalized over
  the k and times ``routed_scaling``, the sequence-wise balance loss per
  routing group (one sequence by default), and the bias moved against
  each expert's load once a step (:func:`_ffn`);
- **a shared SwiGLU expert** (``shared_ffn_dim``) every token meets,
  added once beside the routed experts; **leading dense layers**
  (``n_dense_layers`` of SwiGLU ``dense_ffn_dim``) before the expert
  layers, a stack of their own through the same ``llama.decoder_stack``;
- attention/norms/RoPE are the Llama blocks (models/llama.py) unchanged —
  ring/Ulysses long-context paths compose with MoE layers — or, where
  ``mla`` is set, latent attention (models/mla.py);
- scanned layers, bf16 params, remat: same compile-time story as llama.

Checkpoint shards fall out of the ``NamedSharding`` on each leaf — the
engine needs no MoE-specific code (ckpt shard = mesh coords incl. ep).
"""

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from dlrover_tpu.common.constants import MetricLabel
from dlrover_tpu.models import llama as _llama
from dlrover_tpu.models import mla as _mla
from dlrover_tpu.observability.registry import get_registry
from dlrover_tpu.parallel.sharding import DEFAULT_RULES, valid_spec_for


@dataclass(frozen=True)
class MoEConfig(_llama.AttentionConfigMixin):
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336          # per-expert FFN width (Mixtral 8x7B)
    n_experts: int = 8            # the experts this chip holds
    top_k: int = 2
    # the router's width, where it scores more experts than are held
    # here (None: n_experts), and the first held expert's index
    router_experts: Optional[int] = None
    first_expert: int = 0
    router_score: str = "softmax"  # or "sigmoid" (module docstring)
    routed_scaling: float = 1.0    # the sigmoid router's gate factor
    shared_ffn_dim: int = 0        # the shared expert's width (0: none)
    n_dense_layers: int = 0        # leading dense layers of n_layers
    dense_ffn_dim: int = 0
    mla: Optional[_mla.MLAShape] = None  # latent attention's widths
    capacity_factor: float = 1.25  # expert slots = g/E · top_k · this
    # routing group size (GShard num_groups dual): capacity and the
    # auxiliary loss are reckoned within fixed-size groups of tokens, so
    # what a token may be dropped for does not depend on the rest of the
    # batch. None = one sequence per group (g = S), the standard choice.
    route_group_size: Optional[int] = None
    # the balance loss's weight: the softmax router's Switch-style term
    # averaged over the layers, or the sigmoid router's sequence-wise
    # term summed over them (as DeepSeek-V3 adds each layer's)
    router_aux_weight: float = 0.01
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # same semantics as LlamaConfig: "dots" | None
    remat_policy: Optional[str] = "dots"
    # same semantics as LlamaConfig: None | "ring" | "ulysses"
    sp_attention: Optional[str] = None
    use_ring_attention: bool = False  # legacy alias for sp_attention="ring"
    use_flash_attention: Optional[bool] = None

    @property
    def router_width(self) -> int:
        return self.router_experts or self.n_experts

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def mixtral8x7b() -> "MoEConfig":
        """Mixtral-8x7B shapes — 46.7B params, 12.9B active."""
        return MoEConfig()

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MoEConfig":
        """CI-sized config: 4 experts, top-2."""
        return MoEConfig(
            vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2, ffn_dim=96, n_experts=4, top_k=2,
            max_seq_len=128, remat=False,
        )


def _attention_axes(config: MoEConfig) -> Dict:
    if config.mla is None:
        return _llama.attention_param_axes()
    return _mla.param_axes()


def param_logical_axes(config: MoEConfig) -> Dict:
    """Logical sharding axes per param (parallel/sharding.py rules;
    ``expert_mlp`` → the ep and tp mesh axes together)."""
    c = config
    layers = {
        **_attention_axes(c),
        "ffn_norm": ("layers", "norm"),
        "router": ("layers", "embed", None),
        "w1": ("layers", None, "embed", "expert_mlp"),
        "w3": ("layers", None, "embed", "expert_mlp"),
        "w2": ("layers", None, "expert_mlp", "embed"),
    }
    if c.router_score == "sigmoid":
        layers["router_bias"] = ("layers", None)
    if c.shared_ffn_dim:
        layers.update(shared_w1=("layers", "embed", "mlp"),
                      shared_w3=("layers", "embed", "mlp"),
                      shared_w2=("layers", "mlp", "embed"))
    axes = {
        "tok_embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }
    if c.n_dense_layers:
        axes["dense_layers"] = {
            **_attention_axes(c),
            "ffn_norm": ("layers", "norm"),
            "w1": ("layers", "embed", "mlp"),
            "w3": ("layers", "embed", "mlp"),
            "w2": ("layers", "mlp", "embed"),
        }
    return axes


def _init_attention(config: MoEConfig, key, n_layers: int) -> Dict:
    if config.mla is None:
        return _llama.init_attention_params(config, key, n_layers)
    return _mla.init_params(config, key, n_layers)


def _swiglu_leaves(key, n_layers, dim, width, dtype, prefix=""):
    keys = jax.random.split(key, 3)
    dense = _llama.dense_init
    return {
        prefix + "w1": dense(keys[0], (n_layers, dim, width), dim, dtype),
        prefix + "w3": dense(keys[1], (n_layers, dim, width), dim, dtype),
        prefix + "w2": dense(keys[2], (n_layers, width, dim), width, dtype),
    }


def init_params(config: MoEConfig, key) -> Dict:
    c = config
    keys = jax.random.split(key, 7)
    dt = c.dtype
    dense = _llama.dense_init
    L, E = c.n_expert_layers, c.n_experts
    layers = {
        **_init_attention(c, keys[1], L),
        "ffn_norm": jnp.ones((L, c.dim), dtype=dt),
        # router stays f32: tiny, and routing decisions are precision-
        # sensitive (standard MoE practice)
        "router": jax.random.normal(
            keys[2], (L, c.dim, c.router_width),
            dtype=jnp.float32) * (c.dim ** -0.5),
        "w1": dense(keys[3], (L, E, c.dim, c.ffn_dim), c.dim, dt),
        "w3": dense(keys[4], (L, E, c.dim, c.ffn_dim), c.dim, dt),
        "w2": dense(keys[5], (L, E, c.ffn_dim, c.dim), c.ffn_dim, dt),
    }
    # the leaves only other kinds of model have draw from keys folded
    # out of ``key``, so that a Mixtral's draw is what it always was
    if c.router_score == "sigmoid":
        layers["router_bias"] = jnp.zeros((L, c.router_width), jnp.float32)
    if c.shared_ffn_dim:
        layers.update(_swiglu_leaves(jax.random.fold_in(key, 7), L, c.dim,
                                     c.shared_ffn_dim, dt, "shared_"))
    params = {
        "tok_embed": dense(keys[0], (c.vocab_size, c.dim), c.dim, dt),
        "layers": layers,
        "final_norm": jnp.ones((c.dim,), dtype=dt),
        "lm_head": dense(keys[6], (c.dim, c.vocab_size), c.dim, dt),
    }
    if c.n_dense_layers:
        n = c.n_dense_layers
        params["dense_layers"] = {
            **_init_attention(c, jax.random.fold_in(key, 8), n),
            "ffn_norm": jnp.ones((n, c.dim), dtype=dt),
            **_swiglu_leaves(jax.random.fold_in(key, 9), n, c.dim,
                             c.dense_ffn_dim, dt),
        }
    return params


def _group_size(config: MoEConfig, batch: int, seq: int) -> int:
    """Routing group size: config override or one sequence per group."""
    g = config.route_group_size or seq
    if (batch * seq) % g != 0:
        raise ValueError(
            f"route_group_size {g} must divide token count {batch * seq}"
        )
    return g


def expert_capacity(config: MoEConfig, batch: int, seq: int) -> int:
    """Static per-expert token slots *per routing group*."""
    c = config
    g = _group_size(c, batch, seq)
    cap = int(g * c.top_k * c.capacity_factor / c.router_width)
    return max(c.top_k, cap)


def _route(x_grouped, router, config: MoEConfig, capacity: int,
           bias=None):
    """Top-k routing with capacity → the pairs' experts, gates and mask.

    x_grouped: (G, g, D) — G routing groups of g tokens; capacity is
    per-expert *per group*. Returns, each (G, g, k): the expert of every
    (token, choice) pair (of the router's whole width), its f32 gate, and
    ``keep``, false where the pair's expert was full in its group; and
    the balance term. Choice-major priority within a group: every
    token's first choice claims capacity before any token's second
    choice (GShard order). ``bias`` (E,) is the sigmoid router's
    selection bias.
    """
    c = config
    G, g = x_grouped.shape[0], x_grouped.shape[1]
    E, k = c.router_width, c.top_k
    logits = jnp.einsum(
        "gtd,de->gte", x_grouped.astype(jnp.float32), router
    )
    if c.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)                   # (G, g, E) f32
        # the bias takes part in choosing, not in the gates
        _, topi = jax.lax.top_k(scores + bias, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        gates = c.routed_scaling * topv / (
            topv.sum(-1, keepdims=True) + 1e-20)
        # the sequence-wise term's affinities: scores over their sum
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)           # (G, g, E) f32
        topv, topi = jax.lax.top_k(probs, k)              # (G, g, k)
        gates = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    masks = jax.nn.one_hot(topi, E, dtype=jnp.float32)    # (G, g, k, E)
    cm = masks.transpose(0, 2, 1, 3)                      # (G, k, g, E)
    positions = (
        jnp.cumsum(cm.reshape(G, k * g, E), axis=1).reshape(G, k, g, E) - 1.0
    )
    pos_in_expert = (positions * cm).sum(-1)              # (G, k, g)
    keep = (pos_in_expert < capacity).transpose(0, 2, 1)  # (G, g, k)

    # load-balancing loss over ALL k choices (ST-MoE/Mixtral style): a
    # router dumping second choices on one expert is penalized too.
    # E · Σ_e (choice fraction · mean router prob), averaged over groups.
    # Under the sigmoid router this is DeepSeek-V3's sequence-wise term
    # where a group is a sequence: f_e = E / (k·T) · picks, P_e the mean
    # normalized score
    frac = masks.mean(axis=(1, 2))                        # (G, E)
    aux = E * jnp.mean(jnp.sum(frac * probs.mean(axis=1), axis=-1))
    return topi, gates, keep, aux


# megablox tiles: the most rows, contraction and columns a tile takes,
# chosen by measurement at the expert cell's shapes (8,192 live rows in
# two to eight groups, 4096 x 3584 and 3584 x 4096; PERF.md §6, PR 33)
_GMM_TILING = (512, 1024, 1024)


def _fit(dim: int, most: int) -> int:
    """The widest tile of whole 128-lane registers, ``most`` at most, that
    divides ``dim``: megablox masks a remainder tile of the contraction on
    every visit. ``most`` where none does."""
    if dim <= most:
        return dim
    return next((t for t in range(most, 0, -128) if dim % t == 0), most)


def _tiles(k: int, n: int):
    """Tiles of a grouped ``(m, k) @ (k, n)``."""
    tm, tk, tn = _GMM_TILING
    return tm, _fit(k, tk), _fit(n, tn)


@jax.custom_vjp
def _gmm(rows, w, group_sizes):
    """The Pallas grouped matmul (megablox) and its two gradients, each a
    kernel of the same family. One rule for the function and its forward
    pass, so that tracing makes each kernel once (megablox's own
    ``ops.gmm`` traces two of every forward kernel under remat: set-up
    seconds, PERF.md §6, PR 30)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(
        rows, w, group_sizes, rows.dtype, _tiles(w.shape[1], w.shape[2]))


def _gmm_fwd(rows, w, group_sizes):
    return _gmm(rows, w, group_sizes), (rows, w, group_sizes)


def _gmm_bwd(residuals, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    rows, w, group_sizes = residuals
    k, n = w.shape[1:]
    # the gradient of the rows contracts over the columns of w
    d_rows = gmm(
        g, w, group_sizes, rows.dtype, _tiles(n, k), transpose_rhs=True)
    d_w = tgmm(
        rows.swapaxes(0, 1), g, group_sizes, w.dtype, _tiles(k, n))
    return d_rows, d_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped_matmul(rows, w, group_sizes):
    """``rows[start_e:start_e + group_sizes[e]] @ w[e]`` for every group,
    the groups back to back from row 0. rows (M, K), w (E, K, N) → (M, N).
    Rows past the last group are not computed: what comes back there is
    undefined and the caller's to mask. The Pallas kernel on TPU, XLA's
    own lowering elsewhere."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(rows, w, group_sizes)
    m = rows.shape[0]
    rows = jnp.pad(rows, ((0, -m % _GMM_TILING[0]), (0, 0)))  # decode's few
    return _gmm(rows, w, group_sizes)[:m]


# what a remat policy that saves dots saves of the experts: a kernel call
# is no dot to ``jax.checkpoint``, so the three projections are named.
# The backward pass needs each again (the down projection's output for
# the gradient of the gates): unsaved they are three of twelve grouped
# matmuls a microbatch (PERF.md §6, PR 30)
# Every policy, None too, also keeps the flash kernel's output and
# log-sum-exp (``llama._remat_policy``: B·H·S·D bf16 + B·H·S f32 a layer
# application)
_SAVED = ("moe_gate", "moe_up", "moe_down")


def _remat_policy(config):
    """The llama policy, with the experts' named projections saved
    wherever it saves dots."""
    policy = _llama._remat_policy(config)
    if config.remat_policy is None:
        return policy
    return jax.checkpoint_policies.save_from_both_policies(
        policy, jax.checkpoint_policies.save_only_these_names(*_SAVED))


def _expert_ffn(rows, group_sizes, w1, w3, w2):
    """SwiGLU over the sorted buffer: rows (M, D) grouped by expert, the
    first ``group_sizes.sum()`` of them live. Dead rows are zeroed going
    in and coming out, so nothing they hold reaches a token or a weight
    gradient."""
    live = (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]
    rows = jnp.where(live, rows, 0)
    return jnp.where(live, _grouped_ffn(rows, group_sizes, w1, w3, w2), 0)


def _grouped_ffn(rows, group_sizes, w1, w3, w2):
    """SwiGLU's three grouped matmuls over the sorted buffer, whose dead
    rows the caller zeroes going in and coming out."""
    gate = checkpoint_name(_grouped_matmul(rows, w1, group_sizes), _SAVED[0])
    up = checkpoint_name(_grouped_matmul(rows, w3, group_sizes), _SAVED[1])
    return checkpoint_name(
        _grouped_matmul(jax.nn.silu(gate) * up, w2, group_sizes), _SAVED[2])


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is known: the gradient
    is a gather too, where autodiff would scatter-add."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# the live rows of the sorted buffer move this many at a time (at most):
# one tile of a 1-D int32 array on the chip, and a width that no layer
# reads as its own (latent attention's 512 would put these loops' ops in
# ``mla.latent_ms``)
_CHUNK = 1024


def _over_live(n, rows: int, body, init):
    """``body(start, chunk, carry)`` over the chunks of a ``rows``-row
    buffer that hold its first ``n`` rows: a loop of ``ceil(n / chunk)``
    trips, so the rows past ``n`` cost none. The chunk divides ``rows``,
    so that no chunk's slice is moved back inside the buffer."""
    chunk = math.gcd(rows, _CHUNK)
    trips = (n + chunk - 1) // chunk
    return jax.lax.fori_loop(
        0, trips, lambda c, carry: body(c * chunk, chunk, carry), init)


def _live(n, start, chunk):
    return (start + jnp.arange(chunk)) < n


def _gather_live(table, order, n):
    """The sorted buffer (M, D), M = len(order): row i < n is
    ``table[order[i] mod T]``, every other row zero. table (T, D)."""
    T, D = table.shape
    M = order.shape[0]

    def body(start, chunk, buf):
        o = jax.lax.dynamic_slice(order, (start,), (chunk,))
        rows = jnp.where(_live(n, start, chunk)[:, None], table[o % T], 0)
        return jax.lax.dynamic_update_slice(buf, rows, (start, 0))

    return _over_live(n, M, body, jnp.zeros((M, D), table.dtype))


def _add_live(rows, order, n, T: int, scale=None):
    """The token rows (T, D) in f32: row t the sum of the live rows
    ``rows[i]``, i < n, whose pair ``order[i]`` is of token t (times
    ``scale[order[i]]`` where given). What a dead row holds reaches
    nothing."""
    M, D = rows.shape

    def body(start, chunk, y):
        o = jax.lax.dynamic_slice(order, (start,), (chunk,))
        r = jax.lax.dynamic_slice(rows, (start, 0), (chunk, D)).astype(
            jnp.float32)
        if scale is not None:
            r = scale[o][:, None] * r
        return y.at[o % T].add(
            jnp.where(_live(n, start, chunk)[:, None], r, 0))

    return _over_live(n, M, body, jnp.zeros((T, D), jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _into_rows(x, order, n, T: int):
    """The sorted buffer from the token rows x (T, D), moving the live
    rows alone: row i < n is ``x[order[i] mod T]`` (pairs choice-major,
    ``j·T + t``), the rest zero. Its gradient adds the live rows back
    into their tokens."""
    return _gather_live(x, order, n)


def _into_rows_fwd(x, order, n, T):
    return _gather_live(x, order, n), (order, n)


def _into_rows_bwd(T, residuals, g):
    order, n = residuals
    return _add_live(g, order, n, T).astype(g.dtype), None, None


_into_rows.defvjp(_into_rows_fwd, _into_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _out_of_rows(out, order, gates, n, T: int):
    """What the live rows of the expert buffer out (M, D) add to their T
    tokens, in f32: ``y[order[i] mod T] += gates[order[i]] · out[i]`` for
    i < n, gates (M,) f32 in pair order. Its gradients are the same moves
    the other way, over the live rows again: the buffer's, a gather of
    the tokens' rows times the gates; the gates', each live row's dot
    with its token's row, zero for a dead pair."""
    return _add_live(out, order, n, T, gates)


def _out_of_rows_fwd(out, order, gates, n, T):
    return _add_live(out, order, n, T, gates), (out, order, gates, n)


def _out_of_rows_bwd(T, residuals, g):
    out, order, gates, n = residuals
    M, D = out.shape

    def body(start, chunk, carry):
        d_out, d_gates = carry
        o = jax.lax.dynamic_slice(order, (start,), (chunk,))
        live = _live(n, start, chunk)
        g_rows = g[o % T]
        rows = jax.lax.dynamic_slice(out, (start, 0), (chunk, D))
        d_rows = jnp.where(live[:, None], gates[o][:, None] * g_rows, 0)
        dots = (rows.astype(jnp.float32) * g_rows).sum(-1)
        return (jax.lax.dynamic_update_slice(
                    d_out, d_rows.astype(out.dtype), (start, 0)),
                d_gates.at[jnp.where(live, o, M)].set(
                    dots, mode="drop", unique_indices=True))

    d_out, d_gates = _over_live(n, M, body, (
        jnp.zeros_like(out), jnp.zeros_like(gates)))
    return d_out, None, d_gates, None


_out_of_rows.defvjp(_out_of_rows_fwd, _out_of_rows_bwd)


def _sort_by_expert(expert, keep, n_experts: int):
    """Order of the pairs by expert, the dropped ones last; and how many
    each expert keeps."""
    key = jnp.where(keep, expert, n_experts)
    order = jnp.argsort(key, stable=True)
    group_sizes = (key[:, None] == jnp.arange(n_experts)).sum(
        0, dtype=jnp.int32)
    return order, group_sizes


def _routed_rows(x, expert, keep, gates, w1, w3, w2, live_only=False):
    """What the experts add to every token, over the columns of
    ``ffn_dim`` that w1, w3, w2 hold: all of them, or under a mesh this
    chip's. x (1, B, S, D), this chip's copy of its tokens; expert, keep,
    gates (B, S, k). Returns the shape of x: this chip's term of the sum
    over the chips that slice the columns.

    ``live_only`` moves only the live rows into the sorted buffer and out
    of it, straight from and into the token rows (:func:`_into_rows`,
    :func:`_out_of_rows`): for a chip that holds a share of the router's
    experts, whose groups keep a small part of the ``T·k`` pairs. Without
    it every pair's row is gathered in and out, which costs the same
    where every pair is kept and the loop over live chunks would add its
    trips."""
    k, D = expert.shape[-1], x.shape[-1]
    # pairs choice-major, pair j·T + t: a choice's rows are one block, so
    # no array is laid out (T, k, D) with k among the tiled dimensions
    by_choice = lambda a: a.reshape(-1, k).T
    order, group_sizes = _sort_by_expert(
        by_choice(expert).reshape(-1), by_choice(keep).reshape(-1),
        w1.shape[0])
    _count_permute(live_only)
    if live_only:
        xf, n = x.reshape(-1, D), group_sizes.sum()
        with jax.named_scope("moe_permute"):
            rows = _into_rows(xf, order, n, xf.shape[0])
        out = _grouped_ffn(rows, group_sizes, w1, w3, w2)
        with jax.named_scope("moe_permute"):
            y = _out_of_rows(out, order, by_choice(gates).reshape(-1), n,
                             xf.shape[0])
        return y.astype(x.dtype).reshape(x.shape)
    with jax.named_scope("moe_permute"):
        inverse = jnp.argsort(order)
        rows = _permute(jnp.tile(x.reshape(-1, D), (k, 1)), order, inverse)
    out = _expert_ffn(rows, group_sizes, w1, w3, w2)
    with jax.named_scope("moe_permute"):
        out = _permute(out, inverse, order).reshape(k, -1, D)
        y = (out.astype(jnp.float32) * by_choice(gates)[..., None]).sum(0)
    return y.astype(x.dtype).reshape(x.shape)


def _count_permute(live_only: bool) -> None:
    """Count a traced call of the permutation by its path in the
    registry."""
    calls = get_registry().counter(
        "dlrover_moe_permute_calls_total",
        "Traced calls of the experts' permutation into the sorted buffer "
        "and out of it, by path: live (the live rows alone, a chip that "
        "holds a share of the router's experts) or whole (every pair's "
        "row)",
        labelnames=("path",),
    )
    path = (MetricLabel.PERMUTE_PATH_LIVE if live_only
            else MetricLabel.PERMUTE_PATH_WHOLE)
    calls.labels(path=path).inc()  # noqa: DLR013 — a PERMUTE_PATH_* constant


def _moe_ffn(x, layer, config: MoEConfig, mesh=None):
    """Sparse expert FFN. x: (B, S, D) → (B, S, D), balance term."""
    out, report = _ffn(x, layer, config, mesh)
    return out, report["aux"]


def _ffn(x, layer, config: MoEConfig, mesh=None):
    """The expert layer's FFN branch (``llama.decoder_layer``'s ``ffn``):
    x (B, S, D) → (out, report). ``report["aux"]`` is the router's
    balance term; under the sigmoid router also ``load`` (E,), the share
    of the microbatch's pairs that chose each of the router's experts,
    and ``pull``, a term of value zero whose gradient with respect to
    the selection bias is ``load - 1 / E``: the optimizer moves the bias
    against each expert's load once a step, outside the gradient of the
    loss (DeepSeek-V3's auxiliary-loss-free balancing, by the optimizer's
    step rather than a fixed one)."""
    c = config
    B, S, D = x.shape
    capacity = expert_capacity(c, B, S)
    g = _group_size(c, B, S)
    pairs = (B, S, c.top_k)
    bias = layer.get("router_bias")
    with jax.named_scope("moe_router"):
        expert, gates, keep, aux = _route(
            x.reshape(B * S // g, g, D), layer["router"], c, capacity, bias)
    report = {"aux": aux}
    if bias is not None:
        load = jax.nn.one_hot(expert, c.router_width).mean(axis=(0, 1, 2))
        err = jax.lax.stop_gradient(load - 1.0 / c.router_width)
        report.update(load=load, pull=jnp.sum(
            (bias - jax.lax.stop_gradient(bias)) * err))
    share = c.router_width != c.n_experts
    if share:
        # this chip's share: the pairs of other chips' experts are kept
        # by none of its groups, and only the kept pairs' rows move
        held = expert - c.first_expert
        keep = keep & (held >= 0) & (held < c.n_experts)
        expert = held
    experts, terms = functools.partial(_routed_rows, live_only=share), 1
    if mesh is not None:
        # a Pallas call has no partitioning rule, so under a mesh the
        # block is manual over all of it: tokens stay where the batch and
        # sequence axes put them, every expert's columns where
        # ``expert_mlp`` puts them. The rows go in as one copy, and the
        # terms come out one, for each chip whose columns differ, so that
        # the sum below and its transpose in the backward pass are
        # all-reduces that GSPMD inserts
        tokens = valid_spec_for(mesh, (B, S), ("batch", "seq"))
        over = tuple(
            a for a in DEFAULT_RULES["expert_mlp"] if a in mesh.shape)
        terms = math.prod(mesh.shape[a] for a in over)
        if c.ffn_dim % terms:
            # ``valid_spec_for`` would have replicated the expert leaves
            # on every chip: a job that no longer fits, not a layout
            raise ValueError(
                f"ffn_dim {c.ffn_dim} is not divisible by the {terms} "
                f"chips of mesh axes {over} that slice every expert's "
                "columns")
        up = P(None, None, over)
        experts = jax.shard_map(
            experts, mesh=mesh,
            in_specs=(P(over, *tokens), P(*tokens), P(*tokens), P(*tokens),
                      up, up, P(None, over, None)),
            out_specs=P(over, *tokens), check_vma=False,
        )
    with jax.named_scope("moe_experts"):
        out = experts(
            jnp.broadcast_to(x, (terms, B, S, D)),
            *(a.reshape(pairs) for a in (expert, keep, gates)),
            layer["w1"], layer["w3"], layer["w2"],
        ).sum(0)
    if c.shared_ffn_dim:
        with jax.named_scope("moe_shared"):
            out = out + _llama._mlp(x, {
                "w1": layer["shared_w1"], "w3": layer["shared_w3"],
                "w2": layer["shared_w2"]})
    return out, report


def _hidden_states(params: Dict, tokens, config: MoEConfig, mesh=None):
    """tokens (B, S) int32 → (the normed last hidden states (B, S, D),
    the expert layers' reports stacked on a leading layer axis)."""
    c = config
    B, S = tokens.shape
    x = params["tok_embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    attention = _llama._attention if c.mla is None else _mla.attention
    policy = _remat_policy(c)
    if c.n_dense_layers:
        x, _ = _llama.decoder_stack(
            x, params["dense_layers"], c, positions, mesh,
            layer=functools.partial(
                _llama.decoder_layer, attention=attention),
            policy=policy)
    x, reports = _llama.decoder_stack(
        x, params["layers"], c, positions, mesh,
        layer=functools.partial(
            _llama.decoder_layer, attention=attention, ffn=_ffn),
        policy=policy)
    x = _llama.rms_norm(x, params["final_norm"], c.norm_eps)
    return x, reports


def _balance(reports, config: MoEConfig):
    """The balance terms the loss adds: the softmax router's mean over
    the layers, the sigmoid router's sum, and the bias's pull."""
    if config.router_score != "sigmoid":
        return config.router_aux_weight * reports["aux"].mean()
    return (config.router_aux_weight * reports["aux"].sum()
            + reports["pull"].sum())


def forward(
    params: Dict,
    tokens,
    config: MoEConfig,
    mesh=None,
) -> Tuple[Any, Any]:
    """tokens (B, S) int32 → (logits (B, S, vocab) f32, the router's
    balance term averaged over the expert layers)."""
    x, reports = _hidden_states(params, tokens, config, mesh)
    return _llama.lm_head(x, params["lm_head"]), reports["aux"].mean()


@functools.partial(jax.jit, static_argnames=("config", "mesh"))
def loss_and_stats(params, tokens, config: MoEConfig, mesh=None):
    """(loss, stats) of ``tokens`` (B, S + 1): the causal LM loss plus
    the router's balance terms; ``stats["expert_load"]`` (expert layers,
    router width), each expert's share of the pairs, under the sigmoid
    router (else no stats). Jitted so that a process traces and
    differentiates the model once, however many programs hold the loss
    (a check of the gradient, then the train step: a second or two of
    set-up with the experts' kernels)."""
    x, reports = _hidden_states(params, tokens[:, :-1], config, mesh)
    nll = _llama.head_nll(x, params["lm_head"], tokens[:, 1:], mesh)
    stats = {"expert_load": reports["load"]} if "load" in reports else {}
    return nll.mean() + _balance(reports, config), stats


def next_token_loss(params, tokens, config: MoEConfig, mesh=None):
    """The scalar loss of :func:`loss_and_stats`."""
    return loss_and_stats(params, tokens, config, mesh)[0]


def make_loss_fn(config: MoEConfig, mesh=None):
    """``loss_fn(params, microbatch)`` → the scalar loss, for
    ``ElasticTrainer`` and for a check of the gradient alike. Under the
    sigmoid router it carries ``with_stats``, the ``(loss, stats)`` form
    the trainer's step takes instead, and ``stats_gauges``, which the
    trainer calls once with a function that reads the last step's stats
    back (:func:`stats_gauges`)."""

    def loss_fn(params, microbatch):
        return next_token_loss(params, microbatch, config, mesh)

    if config.router_score == "sigmoid":
        loss_fn.with_stats = lambda params, microbatch: loss_and_stats(
            params, microbatch, config, mesh)
        loss_fn.stats_gauges = functools.partial(stats_gauges, config=config)
    return loss_fn


def stats_gauges(read, config: MoEConfig, registry=None) -> None:
    """Registry gauges computed when the registry is read, from ``read()``,
    the last step's stats on the host (None before a step: NaN), over the
    expert layers: ``dlrover_moe_held_pair_share``, the share of the
    routed pairs that chose an expert this chip holds (held / router
    width where the routing is even), and ``dlrover_moe_held_load_ratio``,
    the hottest held expert's pairs over the mean expert's; each layer's
    averaged over the layers. Nothing on the step path reads them."""
    import numpy as np

    reg = registry or get_registry()
    first, n = config.first_expert, config.n_experts

    def over_layers(of):
        def value():
            stats = read()
            if stats is None:
                return float("nan")
            load = np.asarray(stats["expert_load"], np.float64)   # (L, E)
            return float(of(load[:, first:first + n], load).mean())
        return value

    reg.gauge("dlrover_moe_held_pair_share",
              "Share of the routed pairs whose expert this chip holds, "
              "mean over the expert layers, at the last step").set_function(
        over_layers(lambda held, load: held.sum(-1)))
    reg.gauge("dlrover_moe_held_load_ratio",
              "The hottest held expert's pairs over the mean expert's, "
              "mean over the expert layers, at the last step").set_function(
        over_layers(lambda held, load: held.max(-1) / load.mean(-1)))


def num_params(config: MoEConfig) -> Tuple[int, int]:
    """(total, active-per-token) parameter counts."""
    c = config
    q_dim, kv_dim = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    attn = 2 * c.dim + c.dim * q_dim + 2 * c.dim * kv_dim + q_dim * c.dim
    expert = 3 * c.dim * c.ffn_dim
    router = c.dim * c.n_experts
    shared = c.vocab_size * c.dim * 2 + c.dim
    total = shared + c.n_layers * (attn + router + c.n_experts * expert)
    active = shared + c.n_layers * (attn + router + c.top_k * expert)
    return total, active
