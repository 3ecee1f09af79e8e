"""DeepFM/DLRM-class recommender, TPU-first.

The reference's CI system tests train a Criteo DeepFM through the stack
(examples/tensorflow/criteo_deeprec/deepfm.py: 13 continuous `I*` + 26
categorical `C*` columns, 16-dim embeddings, deep tower [1024, 256, 32],
final tower [128, 64], FM second-order term) on parameter servers with
partitioned embedding variables. This is the TPU-native redesign of that
workload family — PS-partitioned `EmbeddingVariable`s become mesh-sharded
dense tables:

- **one stacked embedding table** ``(F·B, D)``: every categorical field
  hashes into its own ``B``-row stripe of a single tensor, so lookups are
  one static-shape gather per batch — no per-field Python loop, no ragged
  shapes, XLA fuses the 26 lookups into one;
- **row-sharded over the mesh** via the ``vocab`` logical axis (the same
  rule the LM token embedding uses): GSPMD turns the gather into a
  one-hot-matmul / all-to-all on its own, which is exactly how TPU
  embedding lookups want to run when tables exceed one chip's HBM — the
  TPU answer to the reference's `fixed_size_partitioner(ps_num)`;
- **FM second-order term** computed as 0.5·((Σe)² − Σe²) — O(F·D) instead
  of the naive O(F²·D) pairwise sum, all elementwise → fused by XLA;
- dense/bottom features go through the same towers as the reference; the
  whole forward is a handful of matmuls, MXU-shaped.

Elasticity/checkpointing need nothing model-specific: params are a pytree
with logical axes (`param_logical_axes`), so the Flash Checkpoint engine
shards the table exactly as it shards attention weights.
"""

from dataclasses import dataclass
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import dense_init

# Criteo schema used by the reference system tests
N_DENSE = 13
N_SPARSE = 26


@dataclass(frozen=True)
class DLRMConfig:
    n_dense: int = N_DENSE
    n_sparse: int = N_SPARSE
    hash_buckets: int = 100_000       # rows per categorical field
    embed_dim: int = 16
    deep_hidden: Sequence[int] = (1024, 256, 32)
    final_hidden: Sequence[int] = (128, 64)
    dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny() -> "DLRMConfig":
        """CI-sized config."""
        return DLRMConfig(
            hash_buckets=64, embed_dim=8,
            deep_hidden=(32, 16), final_hidden=(16,),
        )

    @property
    def table_rows(self) -> int:
        return self.n_sparse * self.hash_buckets


def param_logical_axes(config: DLRMConfig) -> Dict:
    """Logical sharding axes (parallel/sharding.py rules).

    The table's row axis maps to ``vocab`` (→ ep and tp) — the mesh-sharded
    stand-in for the reference's PS partitioner; MLP widths map to
    ``mlp``/``embed`` like the LM FFNs so fsdp/tp lay them out the same
    way.
    """
    def mlp_axes(hidden):
        return [
            {"w": ("embed", "mlp"), "b": ("mlp",)} for _ in hidden
        ]

    return {
        "table": ("vocab", None),
        "deep": mlp_axes(config.deep_hidden),
        "final": mlp_axes(config.final_hidden),
        "out": {"w": ("embed", None), "b": (None,)},
    }


def _init_mlp(key, in_dim: int, hidden: Sequence[int], dtype) -> Tuple[list, int]:
    layers = []
    for width in hidden:
        key, k = jax.random.split(key)
        layers.append({
            "w": dense_init(k, (in_dim, width), in_dim, dtype),
            "b": jnp.zeros((width,), dtype=dtype),
        })
        in_dim = width
    return layers, in_dim


def init_params(config: DLRMConfig, key) -> Dict:
    c = config
    k_table, k_deep, k_final, k_out = jax.random.split(key, 4)
    # deep tower input: embeddings of every sparse field + dense features
    deep_in = c.n_sparse * c.embed_dim + c.n_dense
    deep, deep_out = _init_mlp(k_deep, deep_in, c.deep_hidden, c.dtype)
    # final tower sees deep output + FM scalar-per-dim term + dense
    final_in = deep_out + c.embed_dim + c.n_dense
    final, final_out = _init_mlp(k_final, final_in, c.final_hidden, c.dtype)
    return {
        # embeddings stay f32: sparse-updated rows accumulate tiny
        # gradients (standard recommender practice)
        "table": jax.random.normal(
            k_table, (c.table_rows, c.embed_dim), dtype=jnp.float32
        ) * (c.embed_dim ** -0.5),
        "deep": deep,
        "final": final,
        "out": {
            "w": dense_init(k_out, (final_out, 1), final_out, c.dtype),
            "b": jnp.zeros((1,), dtype=c.dtype),
        },
    }


def hash_features(raw: jnp.ndarray, config: DLRMConfig) -> jnp.ndarray:
    """Map raw categorical ids (B, F) int — arbitrary range — into the
    stacked table's row space: field f occupies rows [f·B, (f+1)·B).

    An avalanche mixer (murmur3 finalizer) stands in for the reference's
    string-hashing feature column; collisions are the standard
    hashed-embedding trade. A bare multiplicative hash mod 2^k would keep
    only the low bits (ids differing by a multiple of the bucket count
    would always collide) — the xor-shift rounds mix the high bits in
    before the modulo.
    """
    c = config
    h = raw.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    h = h % jnp.uint32(c.hash_buckets)
    offsets = (jnp.arange(c.n_sparse, dtype=jnp.uint32) * c.hash_buckets)
    return (h + offsets[None, :]).astype(jnp.int32)


def _mlp(x, layers, act=jax.nn.relu):
    for layer in layers:
        x = act(x @ layer["w"] + layer["b"])
    return x


def forward(params: Dict, dense: jnp.ndarray, sparse_ids: jnp.ndarray,
            config: DLRMConfig) -> jnp.ndarray:
    """dense (B, 13) f32, sparse_ids (B, 26) int32 hashed rows → logits (B,).

    DeepFM: ``logit = final([deep(e ⊕ x), fm(e), x])`` with the FM
    second-order interaction term computed by the sum-square trick.
    """
    c = config
    rows = hash_features(sparse_ids, c)                       # (B, F)
    emb = jnp.take(params["table"], rows, axis=0)             # (B, F, D) f32
    emb = emb.astype(c.dtype)
    dense = dense.astype(c.dtype)

    # FM 2nd order: Σ_{i<j} e_i ∘ e_j = 0.5·((Σe)² − Σe²)  → (B, D)
    s = emb.sum(axis=1)
    fm = 0.5 * (s * s - (emb * emb).sum(axis=1))

    deep_in = jnp.concatenate(
        [emb.reshape(emb.shape[0], -1), dense], axis=-1
    )
    deep = _mlp(deep_in, params["deep"])
    final_in = jnp.concatenate([deep, fm, dense], axis=-1)
    final = _mlp(final_in, params["final"])
    logits = final @ params["out"]["w"] + params["out"]["b"]
    return logits[:, 0].astype(jnp.float32)


def bce_loss(params: Dict, batch: Dict, config: DLRMConfig) -> jnp.ndarray:
    """Binary cross-entropy with logits over a batch dict
    {"dense": (B, 13), "sparse": (B, 26), "label": (B,)}."""
    logits = forward(params, batch["dense"], batch["sparse"], config)
    labels = batch["label"].astype(jnp.float32)
    # numerically-stable BCE-with-logits
    loss = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )
    return loss.mean()


def batch_auc(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Exact in-batch AUC (probability a positive scores above a negative)
    via rank statistics — O(B log B), jit-friendly, no thresholds."""
    order = jnp.argsort(logits)
    ranks = jnp.empty_like(order).at[order].set(jnp.arange(logits.shape[0]))
    labels = labels.astype(jnp.float32)
    n_pos = labels.sum()
    n_neg = labels.shape[0] - n_pos
    pos_rank_sum = (ranks.astype(jnp.float32) * labels).sum()
    auc = (pos_rank_sum - n_pos * (n_pos - 1) / 2) / jnp.maximum(
        n_pos * n_neg, 1.0
    )
    return jnp.where((n_pos > 0) & (n_neg > 0), auc, 0.5)


def num_params(config: DLRMConfig) -> int:
    c = config
    n = c.table_rows * c.embed_dim
    in_dim = c.n_sparse * c.embed_dim + c.n_dense
    for w in c.deep_hidden:
        n += in_dim * w + w
        in_dim = w
    fin = in_dim + c.embed_dim + c.n_dense
    for w in c.final_hidden:
        n += fin * w + w
        fin = w
    return n + fin + 1


def synthetic_criteo_batch(key, batch: int, config: DLRMConfig) -> Dict:
    """Criteo-shaped synthetic batch with a learnable signal (labels
    correlate with a random linear probe of the features) — what the
    system test trains on in place of the 4.5 GB criteo download."""
    c = config
    k1, k2, k3 = jax.random.split(key, 3)
    dense = jax.random.normal(k1, (batch, c.n_dense), dtype=jnp.float32)
    sparse = jax.random.randint(
        k2, (batch, c.n_sparse), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    )
    signal = dense[:, 0] + 0.5 * dense[:, 1] - 0.25 * dense[:, 2]
    noise = jax.random.normal(k3, (batch,), dtype=jnp.float32)
    label = (signal + 0.5 * noise > 0).astype(jnp.int32)
    return {"dense": dense, "sparse": sparse, "label": label}
