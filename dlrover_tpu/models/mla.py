"""Multi-head latent attention (MLA) for training, TPU-first.

DeepSeek-V2's attention (arXiv:2405.04434 §2.1), as DeepSeek-V3 and the
Moonlight models run it with no query compression (``q_lora_rank``
null). For hidden states x (B, S, D):

- queries ``q = x W_q``: H heads of ``qk_nope_dim + qk_rope_dim``;
- the latent ``[c, k_r] = x W_kva``: ``kv_lora_rank`` latent columns and
  one ``qk_rope_dim``-wide rope key that every head shares; ``c`` is
  RMS-normed (``kv_norm``);
- ``[k_nope, v] = c W_kvb``: H heads of ``qk_nope_dim + v_head_dim``;
- RoPE rotates ``q``'s last ``qk_rope_dim`` columns and ``k_r`` only;
- causal softmax attention of ``[q_nope, q_rope]`` over
  ``[k_nope, k_r]`` (one width, ``qk_nope_dim + qk_rope_dim``) with values
  of their own width ``v_head_dim``, scale ``(qk_nope + qk_rope) ** -0.5``,
  then ``o W_o``.

The attention itself is ``llama.attend``: the same flash kernels (which
take a value width of their own), the same ``shard_map`` and head split
as the GQA block. The down-projection, the latent norm, the
up-projection and the RoPE split and concatenation run under
``jax.named_scope("mla_latent")``.

Leaves (stacked on a leading layer axis): ``attn_norm``, ``wq``,
``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``. Decode through a latent cache
is not built: ``models/decode.py`` and ``serving/`` run GQA models only.
"""

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama as _llama


@dataclass(frozen=True)
class MLAShape:
    """The widths of a latent attention block (the published config's
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``) and the latent norm's epsilon."""

    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    latent_eps: float = 1e-6

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def param_axes() -> Dict:
    """Per-layer logical axes (parallel/sharding.py rules): the heads'
    columns of ``wq`` and ``wkv_b`` and rows of ``wo`` where GQA's are,
    the latent replicated."""
    return {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads"),
        "wkv_a": ("layers", "embed", "latent"),
        "kv_norm": ("layers", "norm"),
        "wkv_b": ("layers", "latent", "heads"),
        "wo": ("layers", "heads", "embed"),
    }


def init_params(config, key, n_layers: int) -> Dict:
    """Stacked (n_layers, …) leaves for a config exposing dim, n_heads,
    dtype and ``mla`` (:class:`MLAShape`); He-style, norms at one."""
    c, m = config, config.mla
    keys = jax.random.split(key, 4)
    L, D, H, dt = n_layers, c.dim, c.n_heads, c.dtype
    dense = _llama.dense_init
    latent = m.kv_lora_rank
    return {
        "attn_norm": jnp.ones((L, D), dtype=dt),
        "wq": dense(keys[0], (L, D, H * m.qk_dim), D, dt),
        "wkv_a": dense(keys[1], (L, D, latent + m.qk_rope_dim), D, dt),
        "kv_norm": jnp.ones((L, latent), dtype=dt),
        "wkv_b": dense(keys[2], (L, latent, H * (m.qk_nope_dim
                                                 + m.v_head_dim)),
                       latent, dt),
        "wo": dense(keys[3], (L, H * m.v_head_dim, D), H * m.v_head_dim, dt),
    }


def attention(x, layer, config, positions, mesh):
    """The latent attention block on normed hidden states x (B, S, D);
    ``llama.decoder_layer``'s ``attention``."""
    c, m = config, config.mla
    B, S, _ = x.shape
    H, nope, rope = c.n_heads, m.qk_nope_dim, m.qk_rope_dim
    q = jnp.einsum("bsd,dh->bsh", x, layer["wq"]).reshape(B, S, H, m.qk_dim)
    with jax.named_scope("mla_latent"):
        kv_a = jnp.einsum("bsd,dr->bsr", x, layer["wkv_a"])
        latent = _llama.rms_norm(
            kv_a[..., :m.kv_lora_rank], layer["kv_norm"], m.latent_eps)
        k_rope = _llama._rope(
            kv_a[..., None, m.kv_lora_rank:], positions, c.rope_theta)
        kv = jnp.einsum("bsr,rh->bsh", latent, layer["wkv_b"]).reshape(
            B, S, H, nope + m.v_head_dim)
        q = jnp.concatenate(
            [q[..., :nope], _llama._rope(q[..., nope:], positions,
                                         c.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))],
            axis=-1)
        v = kv[..., nope:]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # (B,H,S,·)
    out = _llama.attend(q, k, v, c, mesh)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * m.v_head_dim)
    return jnp.einsum("bsh,hd->bsd", out, layer["wo"])
