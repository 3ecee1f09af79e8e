"""Mixtral-class sparse Mixture-of-Experts decoder, TPU-first.

The reference delegates MoE entirely to Megatron/DeepSpeed (SURVEY.md §2.7:
EP "absent — delegated to frameworks"); a from-scratch TPU stack owns it.
Design for the MXU:

- **the experts compute the rows that were routed and no others**: the
  (token, choice) pairs of a microbatch are sorted by expert, their rows
  gathered into one buffer of static size, and each projection is one
  grouped matmul over it whose ``group_sizes`` say which rows belong to
  which expert. Rows past the last kept pair cost no MXU time. (Until
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
- **top-k routing with renormalized gates** (Mixtral) + Switch-style
  load-balancing auxiliary loss per routing group, both in f32;
- attention/norms/RoPE are the Llama blocks (models/llama.py) unchanged —
  ring/Ulysses long-context paths compose with MoE layers;
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

from dlrover_tpu.models import llama as _llama
from dlrover_tpu.parallel.sharding import DEFAULT_RULES, valid_spec_for


@dataclass(frozen=True)
class MoEConfig(_llama.AttentionConfigMixin):
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336          # per-expert FFN width (Mixtral 8x7B)
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25  # expert slots = g/E · top_k · this
    # routing group size (GShard num_groups dual): capacity and the
    # auxiliary loss are reckoned within fixed-size groups of tokens, so
    # what a token may be dropped for does not depend on the rest of the
    # batch. None = one sequence per group (g = S), the standard choice.
    route_group_size: Optional[int] = None
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


def param_logical_axes(config: MoEConfig) -> Dict:
    """Logical sharding axes per param (parallel/sharding.py rules;
    ``expert_mlp`` → the ep and tp mesh axes together)."""
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": {
            **_llama.attention_param_axes(),
            "ffn_norm": ("layers", "norm"),
            "router": ("layers", "embed", None),
            "w1": ("layers", None, "embed", "expert_mlp"),
            "w3": ("layers", None, "embed", "expert_mlp"),
            "w2": ("layers", None, "expert_mlp", "embed"),
        },
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: MoEConfig, key) -> Dict:
    c = config
    keys = jax.random.split(key, 7)
    dt = c.dtype
    dense = _llama.dense_init
    L, E = c.n_layers, c.n_experts
    return {
        "tok_embed": dense(keys[0], (c.vocab_size, c.dim), c.dim, dt),
        "layers": {
            **_llama.init_attention_params(c, keys[1]),
            "ffn_norm": jnp.ones((L, c.dim), dtype=dt),
            # router stays f32: tiny, and routing decisions are precision-
            # sensitive (standard MoE practice)
            "router": jax.random.normal(
                keys[2], (L, c.dim, E), dtype=jnp.float32) * (c.dim ** -0.5),
            "w1": dense(keys[3], (L, E, c.dim, c.ffn_dim), c.dim, dt),
            "w3": dense(keys[4], (L, E, c.dim, c.ffn_dim), c.dim, dt),
            "w2": dense(keys[5], (L, E, c.ffn_dim, c.dim), c.ffn_dim, dt),
        },
        "final_norm": jnp.ones((c.dim,), dtype=dt),
        "lm_head": dense(keys[6], (c.dim, c.vocab_size), c.dim, dt),
    }


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
    cap = int(g * c.top_k * c.capacity_factor / c.n_experts)
    return max(c.top_k, cap)


def _route(x_grouped, router, config: MoEConfig, capacity: int):
    """Top-k routing with capacity → the pairs' experts, gates and mask.

    x_grouped: (G, g, D) — G routing groups of g tokens; capacity is
    per-expert *per group*. Returns, each (G, g, k): the expert of every
    (token, choice) pair, its renormalized f32 gate, and ``keep``, false
    where the pair's expert was full in its group; and the aux scalar.
    Choice-major priority within a group: every token's first choice
    claims capacity before any token's second choice (GShard order).
    """
    c = config
    G, g = x_grouped.shape[0], x_grouped.shape[1]
    E, k = c.n_experts, c.top_k
    logits = jnp.einsum(
        "gtd,de->gte", x_grouped.astype(jnp.float32), router
    )
    probs = jax.nn.softmax(logits, axis=-1)               # (G, g, E) f32
    topv, topi = jax.lax.top_k(probs, k)                  # (G, g, k)
    gates = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)  # renorm

    masks = jax.nn.one_hot(topi, E, dtype=jnp.float32)    # (G, g, k, E)
    cm = masks.transpose(0, 2, 1, 3)                      # (G, k, g, E)
    positions = (
        jnp.cumsum(cm.reshape(G, k * g, E), axis=1).reshape(G, k, g, E) - 1.0
    )
    pos_in_expert = (positions * cm).sum(-1)              # (G, k, g)
    keep = (pos_in_expert < capacity).transpose(0, 2, 1)  # (G, g, k)

    # load-balancing loss over ALL k choices (ST-MoE/Mixtral style): a
    # router dumping second choices on one expert is penalized too.
    # E · Σ_e (choice fraction · mean router prob), averaged over groups
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
    gate = checkpoint_name(_grouped_matmul(rows, w1, group_sizes), _SAVED[0])
    up = checkpoint_name(_grouped_matmul(rows, w3, group_sizes), _SAVED[1])
    down = checkpoint_name(
        _grouped_matmul(jax.nn.silu(gate) * up, w2, group_sizes), _SAVED[2])
    return jnp.where(live, down, 0)


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


def _sort_by_expert(expert, keep, n_experts: int):
    """Order of the pairs by expert, the dropped ones last; and how many
    each expert keeps."""
    key = jnp.where(keep, expert, n_experts)
    order = jnp.argsort(key, stable=True)
    group_sizes = (key[:, None] == jnp.arange(n_experts)).sum(
        0, dtype=jnp.int32)
    return order, group_sizes


def _routed_rows(x, expert, keep, gates, w1, w3, w2):
    """What the experts add to every token, over the columns of
    ``ffn_dim`` that w1, w3, w2 hold: all of them, or under a mesh this
    chip's. x (1, B, S, D), this chip's copy of its tokens; expert, keep,
    gates (B, S, k). Returns the shape of x: this chip's term of the sum
    over the chips that slice the columns."""
    k, D = expert.shape[-1], x.shape[-1]
    # pairs choice-major, pair j·T + t: a choice's rows are one block, so
    # no array is laid out (T, k, D) with k among the tiled dimensions
    by_choice = lambda a: a.reshape(-1, k).T
    order, group_sizes = _sort_by_expert(
        by_choice(expert).reshape(-1), by_choice(keep).reshape(-1),
        w1.shape[0])
    inverse = jnp.argsort(order)
    rows = _permute(jnp.tile(x.reshape(-1, D), (k, 1)), order, inverse)
    out = _expert_ffn(rows, group_sizes, w1, w3, w2)
    out = _permute(out, inverse, order).reshape(k, -1, D)
    y = (out.astype(jnp.float32) * by_choice(gates)[..., None]).sum(0)
    return y.astype(x.dtype).reshape(x.shape)


def _moe_ffn(x, layer, config: MoEConfig, mesh=None):
    """Sparse expert FFN. x: (B, S, D) → (B, S, D), aux scalar."""
    c = config
    B, S, D = x.shape
    capacity = expert_capacity(c, B, S)
    g = _group_size(c, B, S)
    pairs = (B, S, c.top_k)
    expert, gates, keep, aux = _route(
        x.reshape(B * S // g, g, D), layer["router"], c, capacity)
    experts, terms = _routed_rows, 1
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
            _routed_rows, mesh=mesh,
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
    return out, aux


def _hidden_states(params: Dict, tokens, config: MoEConfig, mesh=None):
    """tokens (B, S) int32 → (the normed last hidden states (B, S, D),
    aux loss scalar)."""
    c = config
    B, S = tokens.shape
    x = params["tok_embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def layer_fn(carry, layer):
        h, aux_sum = carry
        h = h + _llama.attention_block(
            _llama.rms_norm(h, layer["attn_norm"], c.norm_eps),
            layer, c, positions, mesh,
        )
        ffn_out, aux = _moe_ffn(
            _llama.rms_norm(h, layer["ffn_norm"], c.norm_eps), layer, c,
            mesh,
        )
        return (h + ffn_out, aux_sum + aux), None

    scan_fn = layer_fn
    if c.remat:
        scan_fn = jax.checkpoint(
            layer_fn, prevent_cse=False,
            policy=_remat_policy(c),
        )
    (x, aux_sum), _ = jax.lax.scan(
        scan_fn, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    x = _llama.rms_norm(x, params["final_norm"], c.norm_eps)
    return x, aux_sum / c.n_layers


def forward(
    params: Dict,
    tokens,
    config: MoEConfig,
    mesh=None,
) -> Tuple[Any, Any]:
    """tokens (B, S) int32 → (logits (B, S, vocab) f32, aux loss scalar)."""
    x, aux = _hidden_states(params, tokens, config, mesh)
    return _llama.lm_head(x, params["lm_head"]), aux


@functools.partial(jax.jit, static_argnames=("config", "mesh"))
def next_token_loss(params, tokens, config: MoEConfig, mesh=None):
    """Causal LM loss + router load-balancing aux term. Jitted so that a
    process traces and differentiates the model once, however many
    programs hold the loss (a check of the gradient, then the train
    step: a second or two of set-up with the experts' kernels)."""
    x, aux = _hidden_states(params, tokens[:, :-1], config, mesh)
    nll = _llama.head_nll(x, params["lm_head"], tokens[:, 1:], mesh)
    return nll.mean() + config.router_aux_weight * aux


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
