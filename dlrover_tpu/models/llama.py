"""Llama-class decoder-only transformer, TPU-first.

The flagship model of the framework (the reference orchestrates external
Llama trainers — BASELINE.json's driver workload is Llama-7B). Design
choices for the MXU/XLA:

- **pure-functional params pytree** (no framework classes): shardings ride
  on the arrays, flash-checkpoint and pjit see plain leaves;
- **scanned layers**: per-layer params are stacked on a leading axis and the
  decoder runs as one ``lax.scan`` — O(1) HLO size in depth, the standard
  TPU compile-time win;
- **bf16 params/activations, f32 logits+softmax**: MXU-native;
- **GQA** (n_kv_heads ≤ n_heads), RoPE, RMSNorm, SwiGLU — Llama-2/3 shapes;
- **remat** per layer (``jax.checkpoint``) to trade FLOPs for HBM;
- attention is pluggable: dense causal for short S, ring attention over the
  ``sp`` mesh axis for long context (parallel/ring_attention.py).

Logical sharding axes per param are in :func:`param_logical_axes`; combined
with parallel/sharding.py rules this yields fsdp/tp sharded params without
touching model code.
"""

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.common.log import log_once
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.ring_attention import (
    full_causal_attention,
    ring_attention,
    sharded_flash_attention,
)
from dlrover_tpu.parallel.sharding import valid_spec_for, vocab_split
from dlrover_tpu.parallel.ulysses import ulysses_attention


class AttentionConfigMixin:
    """Shared attention-config surface for decoder configs (LlamaConfig,
    moe.MoEConfig): the sp-strategy legacy-alias fold and head_dim. One copy
    so sp semantics can't drift between model families."""

    @property
    def sp_strategy(self) -> Optional[str]:
        """Effective sp strategy after the legacy-alias fold."""
        if self.sp_attention is not None:
            return self.sp_attention
        return "ring" if self.use_ring_attention else None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


@dataclass(frozen=True)
class LlamaConfig(AttentionConfigMixin):
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # remat policy: "dots" saves matmul outputs and recomputes only the
    # cheap elementwise/attention-softmax work in backward (~5% FLOPs
    # overhead vs ~33% for full per-layer remat); None = save nothing
    remat_policy: Optional[str] = "dots"
    # long-context strategy applied when the sp mesh axis is >1:
    # None = no sequence-parallel attention;
    # "ring" = K/V ppermute ring (unbounded S, sp hops);
    # "ulysses" = head-scatter all-to-all (full S per device; 4 a2a calls
    #   per attention — q/k/v in, output out — k/v legs unrepeated in GQA)
    sp_attention: Optional[str] = None
    # legacy alias: True ≡ sp_attention="ring" (when sp_attention is None)
    use_ring_attention: bool = False
    # None = auto: fused pallas flash kernel on TPU, dense math elsewhere
    use_flash_attention: Optional[bool] = None

    @staticmethod
    def llama7b() -> "LlamaConfig":
        """Llama-2-7B shapes (MHA: 32 kv heads) — 6.74B params."""
        return LlamaConfig(n_kv_heads=32)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        """CI-sized config."""
        return LlamaConfig(
            vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat=False,
        )


def attention_param_axes() -> Dict:
    """Per-layer attention-block logical axes — shared by every model
    family that reuses the Llama attention blocks (e.g. models/moe.py)."""
    return {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
    }


def param_logical_axes(config: LlamaConfig) -> Dict:
    """Logical sharding axes per param (see parallel/sharding.py rules)."""
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": {
            **attention_param_axes(),
            "ffn_norm": ("layers", "norm"),
            "w1": ("layers", "embed", "mlp"),
            "w3": ("layers", "embed", "mlp"),
            "w2": ("layers", "mlp", "embed"),
        },
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def dense_init(key, shape, fan_in, dtype):
    """He-style dense init shared across model families."""
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def init_attention_params(config, key) -> Dict:
    """Stacked (L, …) attention-block params for any config exposing
    n_layers/dim/n_heads/n_kv_heads/head_dim/dtype."""
    c = config
    keys = jax.random.split(key, 4)
    L = c.n_layers
    q_dim = c.n_heads * c.head_dim
    kv_dim = c.n_kv_heads * c.head_dim
    return {
        "attn_norm": jnp.ones((L, c.dim), dtype=c.dtype),
        "wq": dense_init(keys[0], (L, c.dim, q_dim), c.dim, c.dtype),
        "wk": dense_init(keys[1], (L, c.dim, kv_dim), c.dim, c.dtype),
        "wv": dense_init(keys[2], (L, c.dim, kv_dim), c.dim, c.dtype),
        "wo": dense_init(keys[3], (L, q_dim, c.dim), q_dim, c.dtype),
    }


def init_params(config: LlamaConfig, key) -> Dict:
    """He-style init, params in config.dtype (bf16)."""
    c = config
    keys = jax.random.split(key, 5)
    dt = c.dtype
    L = c.n_layers
    return {
        "tok_embed": dense_init(keys[0], (c.vocab_size, c.dim), c.dim, dt),
        "layers": {
            **init_attention_params(c, keys[1]),
            "ffn_norm": jnp.ones((L, c.dim), dtype=dt),
            "w1": dense_init(keys[2], (L, c.dim, c.ffn_dim), c.dim, dt),
            "w3": dense_init(keys[3], (L, c.dim, c.ffn_dim), c.dim, dt),
            "w2": dense_init(keys[4], (L, c.ffn_dim, c.dim), c.ffn_dim, dt),
        },
        "final_norm": jnp.ones((c.dim,), dtype=dt),
        "lm_head": dense_init(keys[0], (c.dim, c.vocab_size), c.dim, dt),
    }


def _rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight


def _rope(x, positions, theta: float):
    """Rotary embedding. x: (B, S, H, D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _flash_shardable(mesh, batch: int) -> bool:
    """Whether the short-context flash layout (batch over dp/fsdp, heads
    where :func:`head_split` puts them, sequence resident) divides the
    mesh evenly."""
    dp = (mesh.shape.get("dcn", 1) * mesh.shape.get("dp", 1)
          * mesh.shape.get("fsdp", 1))
    sp = mesh.shape.get("sp", 1)
    return sp == 1 and batch % dp == 0


def _attention(x, layer, config: LlamaConfig, positions, mesh):
    c = config
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, layer["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, layer["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, layer["wv"])
    q = q.reshape(B, S, c.n_heads, c.head_dim)
    k = k.reshape(B, S, c.n_kv_heads, c.head_dim)
    v = v.reshape(B, S, c.n_kv_heads, c.head_dim)
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # (B,H,S,D)
    strategy = c.sp_strategy
    if strategy not in (None, "ring", "ulysses"):
        raise ValueError(
            f"unknown sp_attention {strategy!r}; expected None, 'ring' or "
            "'ulysses'"
        )
    use_flash = c.use_flash_attention
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
        if not use_flash:
            log_once(
                "attention: dense XLA path (use_flash_attention=None and "
                "default backend is %r, not tpu)", jax.default_backend(),
            )
    use_sp = (
        strategy is not None and mesh is not None
        and mesh.shape.get("sp", 1) > 1
    )
    # GQA: repeat kv heads to match q heads — except on the Ulysses path,
    # which scatters unrepeated K/V (1/rep the all-to-all bytes) and
    # broadcasts heads device-locally after
    rep = c.n_heads // c.n_kv_heads
    if not (use_sp and strategy == "ulysses"):
        from dlrover_tpu.ops.flash_attention import repeat_kv

        k, v = repeat_kv(k, v, rep)
    if use_sp:
        # honor an explicit kernel opt-out in the sp paths too
        if strategy == "ulysses":
            out = ulysses_attention(
                q, k, v, mesh, use_pallas=c.use_flash_attention
            )
        else:
            out = ring_attention(
                q, k, v, mesh, use_pallas=c.use_flash_attention
            )
    elif use_flash and mesh is None:
        out = flash_attention(q, k, v, causal=True)
    elif use_flash and _flash_shardable(mesh, B):
        out = sharded_flash_attention(q, k, v, mesh)
    else:
        if use_flash:
            log_once(
                "attention: flash kernel wanted but mesh %s does not divide "
                "batch=%s — dense XLA path", str(dict(mesh.shape)), B,
            )
        out = full_causal_attention(q, k, v)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, c.n_heads * c.head_dim)
    return jnp.einsum("bsh,hd->bsd", out, layer["wo"])


# public names for model families composing these blocks (models/moe.py)
attention_block = _attention
rms_norm = _rms_norm


def _remat_policy(config):
    """Map the config's remat_policy name to a jax.checkpoint policy."""
    name = getattr(config, "remat_policy", None)
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name is None:
        return None
    raise ValueError(f"unknown remat_policy {name!r}")


def _mlp(x, layer):
    gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, layer["w1"]))
    up = jnp.einsum("bsd,df->bsf", x, layer["w3"])
    return jnp.einsum("bsf,fd->bsd", gate * up, layer["w2"])


def decoder_layer(h, layer, config, positions, mesh):
    """One decoder layer on ``h`` (B, S, D). ``positions`` None takes them
    from ``h``'s own shape (inside a pipeline stage that is the local
    shard). A layer tree that holds ``attn_post_norm`` / ``ffn_post_norm``
    gets each branch normed again before it joins the residual (sandwich
    norm, models/looped.py)."""
    c = config
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1])[None, :], h.shape[:2]
        )
    branch = _attention(
        _rms_norm(h, layer["attn_norm"], c.norm_eps),
        layer, c, positions, mesh,
    )
    if "attn_post_norm" in layer:
        branch = _rms_norm(branch, layer["attn_post_norm"], c.norm_eps)
    h = h + branch
    branch = _mlp(_rms_norm(h, layer["ffn_norm"], c.norm_eps), layer)
    if "ffn_post_norm" in layer:
        branch = _rms_norm(branch, layer["ffn_post_norm"], c.norm_eps)
    return h + branch


def decoder_stack(x, layers, config, positions, mesh):
    """The stacked layers as one ``lax.scan``, each layer under
    ``jax.checkpoint`` where the config asks for remat. A function of its
    own so that a caller may run the same weights more than once
    (models/looped.py) or stage by stage (``forward_pp``)."""

    def layer_fn(h, layer):
        return decoder_layer(h, layer, config, positions, mesh), None

    scan_fn = layer_fn
    if config.remat:
        scan_fn = jax.checkpoint(
            layer_fn, prevent_cse=False, policy=_remat_policy(config),
        )
    x, _ = jax.lax.scan(scan_fn, x, layers)
    return x


def lm_head(x, weight):
    """(B, S, D) x (D, vocab) -> f32 logits."""
    return jnp.einsum(
        "bsd,dv->bsv", x, weight, preferred_element_type=jnp.float32,
    )


def hidden_states(params: Dict, tokens, config: LlamaConfig, mesh=None):
    """tokens (B, S) int32 → the normed last hidden states (B, S, D)."""
    c = config
    B, S = tokens.shape
    x = params["tok_embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = decoder_stack(x, params["layers"], c, positions, mesh)
    return _rms_norm(x, params["final_norm"], c.norm_eps)


def forward(
    params: Dict,
    tokens,
    config: LlamaConfig,
    mesh=None,
):
    """tokens (B, S) int32 → logits (B, S, vocab) f32."""
    return lm_head(
        hidden_states(params, tokens, config, mesh), params["lm_head"])


def token_nll(logits, targets):
    """Per-token NLL via logsumexp − gathered-logit: mathematically
    identical to log_softmax + gather, but never materializes the full
    (B, S, V) log-probability tensor — at vocab 32k/seq 2048 that
    intermediate is ~1 GB of pure HBM traffic per pass. Measured on one
    v5e: −3% step time (+1.7 MFU points) on the bench model."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def cross_entropy(logits, targets):
    """Mean NLL over every token (:func:`token_nll`)."""
    return token_nll(logits, targets).mean()


def _nll_on_shard(x, weight, targets, over):
    """:func:`token_nll` on one chip of the ``over`` group, which holds
    the columns ``weight`` (D, V / n) of the head and x (1, B, S, D), its
    copy of the group's hidden states: f32 logits of its own columns, the
    log-sum-exp from the group's maximum and its summed exponentials, the
    target's logit from the one chip whose columns hold it."""
    logits = lm_head(x[0], weight)                       # (B, S, V / n)
    top = jax.lax.pmax(
        jax.lax.stop_gradient(logits.max(axis=-1)), over)
    lse = top + jnp.log(jax.lax.psum(
        jnp.exp(logits - top[..., None]).sum(axis=-1), over))
    columns = weight.shape[1]
    local = targets - jax.lax.axis_index(over) * columns
    mine = (local >= 0) & (local < columns)
    tgt = jnp.take_along_axis(
        logits, jnp.clip(local, 0, columns - 1)[..., None], axis=-1)[..., 0]
    return lse - jax.lax.psum(jnp.where(mine, tgt, 0.0), over)


def head_nll(x, weight, targets, mesh=None):
    """Per-token NLL (B, S) f32 of ``targets`` under the output head:
    ``token_nll(lm_head(x, weight), targets)``. Where the mesh spreads
    the vocabulary over chips (:func:`vocab_split`) each of them makes
    the logits of its own columns only, and the (B, S, vocab) logits exist
    nowhere: the group exchanges two f32 statistics a token for the
    log-sum-exp and one for the target's logit, and the sum of the
    hidden states' gradient over its chips (the Megatron-LM
    vocabulary-parallel cross-entropy, in this file's precision)."""
    over, chips = vocab_split(mesh, weight.shape[1])
    if chips == 1:
        return token_nll(lm_head(x, weight), targets)
    # manual over the whole mesh, as the experts and the flash kernel
    # are: tokens stay where the batch and sequence axes put them. The
    # hidden states go in as one copy for each chip of the group, so that
    # the sum of their gradients is an all-reduce that GSPMD inserts
    tokens = valid_spec_for(mesh, targets.shape, ("batch", "seq"))
    on_shards = jax.shard_map(
        functools.partial(_nll_on_shard, over=over), mesh=mesh,
        in_specs=(P(over, *tokens), P(None, over), P(*tokens)),
        out_specs=P(*tokens), check_vma=False,
    )
    return on_shards(
        jnp.broadcast_to(x, (chips, *x.shape)), weight, targets)


def next_token_loss(params, tokens, config: LlamaConfig, mesh=None):
    """Causal LM loss: predict tokens[1:] from tokens[:-1]."""
    x = hidden_states(params, tokens[:, :-1], config, mesh)
    return head_nll(x, params["lm_head"], tokens[:, 1:], mesh).mean()


def hidden_states_pp(
    params: Dict,
    tokens,
    config: LlamaConfig,
    mesh,
    n_microbatches: int = 0,
):
    """:func:`hidden_states` pipeline-parallel over the mesh's ``pp`` axis
    (parallel/pipeline.py — shard_map + ppermute GPipe schedule).

    Stage layout: the cheap, replicable ends (embedding lookup, final
    norm + lm_head) run outside the pipeline on every pp rank — only the
    transformer blocks, where the FLOPs and parameters are, get staged.
    That keeps the pipelined state a single uniform ``(b, S, D)``
    activation (no int-token first hop, no special first/last stage) at
    the cost of replicating <1% of compute. Backward is autodiff through
    the schedule. Defaults M = 4·pp for a <20% fill/drain bubble.
    """
    from dlrover_tpu.parallel.pipeline import (
        microbatch,
        pipeline_apply,
        stack_stages,
        unmicrobatch,
    )

    c = config
    S_pp = mesh.shape["pp"]
    if S_pp <= 1:
        return hidden_states(params, tokens, config, mesh)
    B, S = tokens.shape
    M = n_microbatches
    if not M:
        # largest divisor of B not exceeding 4·pp (bubble target) — an
        # arbitrary min(B, 4·pp) need not divide B
        M = 1
        for d in range(min(B, 4 * S_pp), 0, -1):
            if B % d == 0:
                M = d
                break
    x = params["tok_embed"][tokens]

    def stage_fn(layer_group, h):
        # positions from the *local* activation shape: inside the pipeline
        # body the batch dim is the per-(dp,fsdp)-rank shard, not B/M
        return decoder_stack(h, layer_group, c, None, None)

    stages = stack_stages(params["layers"], S_pp)
    ym = pipeline_apply(
        stage_fn, stages, microbatch(x, M), mesh,
        axis="pp", checkpoint_ticks=not c.remat,
        batch_axes=("dcn", "dp", "fsdp"),
    )
    return _rms_norm(unmicrobatch(ym), params["final_norm"], c.norm_eps)


def forward_pp(params: Dict, tokens, config: LlamaConfig, mesh,
               n_microbatches: int = 0):
    """tokens (B, S) int32 → logits (B, S, vocab) f32, the layers staged
    over ``pp`` (:func:`hidden_states_pp`)."""
    return lm_head(
        hidden_states_pp(params, tokens, config, mesh, n_microbatches),
        params["lm_head"])


def next_token_loss_pp(params, tokens, config: LlamaConfig, mesh,
                       n_microbatches: int = 0):
    """Causal LM loss through the pipeline-parallel forward."""
    x = hidden_states_pp(params, tokens[:, :-1], config, mesh,
                         n_microbatches)
    return head_nll(x, params["lm_head"], tokens[:, 1:], mesh).mean()


def num_params(config: LlamaConfig) -> int:
    c = config
    q_dim, kv_dim = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    per_layer = (
        2 * c.dim  # norms
        + c.dim * q_dim + 2 * c.dim * kv_dim + q_dim * c.dim  # attn
        + 3 * c.dim * c.ffn_dim  # w1, w3: (dim, ffn); w2: (ffn, dim)
    )
    return (
        c.vocab_size * c.dim
        + c.n_layers * per_layer
        + c.dim
        + c.dim * c.vocab_size
    )
