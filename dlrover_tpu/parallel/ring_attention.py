"""Ring attention: causal attention over a sequence-sharded mesh axis.

The reference has NO long-context layer (SURVEY.md §5.7) — it launches
Megatron jobs that bring their own. A TPU-native stack owns it. This is the
blockwise/ring formulation (Liu et al., Ring Attention; Milakov & Gimelshein
online softmax): the sequence axis is sharded over mesh axis ``sp``; each
device keeps its Q block resident and the K/V blocks rotate around the ring
via ``ppermute`` (nearest-neighbor ICI traffic — the cheapest collective a
TPU has), while a numerically-stable online softmax folds each visiting
block into the running (max, denom, numerator) accumulators in f32.

Causality with a ring: sequence blocks are contiguous chunks in ring order,
so a whole visiting block is either fully attendable (its chunk precedes
ours), fully masked (it follows ours), or the diagonal chunk (ours) which
uses the triangular mask. The fully-masked steps still rotate K/V (the ring
must stay in lockstep) but contribute nothing.

Exposed as ``ring_attention(q, k, v, mesh)`` — a drop-in for full attention
when S is sharded — plus ``_ring_attention_local`` for direct shard_map use.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common.log import log_once
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.sharding import clamp_spec, head_split


def _block_attend(q, k, v, mask, m, l, o, scale):
    """Fold one K/V block into the online-softmax accumulators.

    q: (B, H, Sq, D); k/v: (B, H, Sk, D); mask: (Sq, Sk) bool (True=keep);
    m: (B, H, Sq) running max; l: (B, H, Sq) running denom;
    o: (B, H, Sq, D) running numerator. All accumulators f32.
    """
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard: a fully-masked row keeps m=-inf; exp(-inf - -inf) would be NaN
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask[None, None, :, :], p, 0.0)
    correction = jnp.where(
        jnp.isneginf(m), 0.0, jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
    )
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, scale: float):
    """Per-device ring attention body (inside shard_map).

    q/k/v: (B, H, S_local, D) — the local sequence chunk; chunks are laid
    out contiguously in ring order (chunk r of the global sequence lives on
    ring position r).
    """
    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    qf = q.astype(jnp.float32)

    rows = jnp.arange(s_local)
    cols = jnp.arange(s_local)
    tri = rows[:, None] >= cols[None, :]  # causal within a chunk
    full = jnp.ones((s_local, s_local), dtype=bool)
    empty = jnp.zeros((s_local, s_local), dtype=bool)

    m0 = jnp.full(q.shape[:3], -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros(q.shape[:3], dtype=jnp.float32)
    o0 = jnp.zeros(qf.shape, dtype=jnp.float32)

    def step(i, carry):
        m, l, o, k_blk, v_blk = carry
        # after i rotations the visiting block started on ring position
        # (my_idx - i) mod sp  — ppermute sends to (j+1) % sp each step
        src = (my_idx - i) % sp
        mask = jnp.where(
            src == my_idx, tri, jnp.where(src < my_idx, full, empty)
        )
        m, l, o = _block_attend(qf, k_blk, v_blk, mask, m, l, o, scale)
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_next, v_next

    m, l, o, _, _ = jax.lax.fori_loop(0, sp, step, (m0, l0, o0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (none in causal LM)
    return (o / l[..., None]).astype(q.dtype)


def _merge_partials(o1, lse1, o2, lse2):
    """Numerically-stable merge of two normalized attention partials.

    o: (B, H, S, D) f32; lse: (B, H, S) f32 (-1e30 ≈ -inf for empty).
    Standard logsumexp combine — differentiable, so grads flow back into
    each partial's flash kernel via its lse cotangent.
    """
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= -1e29, 0.0, m)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    denom = w1 + w2
    safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / safe[..., None]
    lse = jnp.where(denom == 0.0, -1e30, m_safe + jnp.log(safe))
    return o, lse


def _ring_flash_local(
    q, k, v, axis_name: str, scale: float, block_q: int, block_k: int,
):
    """Ring attention with the pallas flash kernel as the inner block op.

    Same ring schedule as :func:`_ring_attention_local`, but each visiting
    block runs the fused flash kernel (causal for the diagonal chunk, dense
    for past chunks) and partials merge by logsumexp — the blockwise
    formulation of Ring Attention with a hardware inner loop.
    """
    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    flash = functools.partial(
        flash_attention, scale=scale, block_q=block_q, block_k=block_k,
        return_lse=True,
    )
    # step 0: the diagonal chunk (our own K/V) with the triangular mask
    o0, lse0 = flash(q, k, v, causal=True)
    o0 = o0.astype(jnp.float32)

    def step(i, carry):
        o, lse, k_blk, v_blk = carry
        # rotate first: after i steps the visiting block is ring chunk
        # (my_idx - i) mod sp
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        src = (my_idx - i) % sp

        def attend(o, lse, k_blk, v_blk):
            o_b, lse_b = flash(q, k_blk, v_blk, causal=False)
            return _merge_partials(o, lse, o_b.astype(jnp.float32), lse_b)

        # chunks after ours contribute nothing (causal); cond keeps the
        # collective schedule identical on every device (ppermute above)
        o, lse = jax.lax.cond(
            src < my_idx,
            attend,
            lambda o, lse, k_blk, v_blk: (o, lse),
            o, lse, k_blk, v_blk,
        )
        return o, lse, k_blk, v_blk

    o, lse, _, _ = jax.lax.fori_loop(1, sp, step, (o0, lse0, k, v))
    return o.astype(q.dtype)


def ring_attention(
    q, k, v,
    mesh: Mesh,
    sp_axis: str = "sp",
    batch_spec=None,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    block_q: int = 512,
    block_k: int = 1024,
):
    """Causal attention with the sequence axis sharded over ``sp_axis``.

    q/k/v: (B, H, S, D) jax.Arrays (S sharded over sp). Returns same shape/
    sharding. Inside jit, composes with the surrounding GSPMD program via
    shard_map. ``use_pallas`` selects the fused flash inner kernel
    (default: on TPU backends).
    """
    if batch_spec is None:
        # library default, clamped to the mesh's axes; an explicit caller
        # spec is passed through verbatim so typos still fail loudly
        batch_spec = clamp_spec(mesh, P(
            ("dcn", "dp", "fsdp"), head_split(mesh, q.shape[1])[0], "sp",
            None))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
        if not use_pallas:
            log_once(
                "ring attention: dense inner block, not the flash kernel "
                "(default backend is %r, not tpu)", jax.default_backend(),
            )
    if use_pallas:
        fn = functools.partial(
            _ring_flash_local, axis_name=sp_axis, scale=scale,
            block_q=block_q, block_k=block_k,
        )
    else:
        fn = functools.partial(
            _ring_attention_local, axis_name=sp_axis, scale=scale
        )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(q, k, v)


def sharded_flash_attention(
    q, k, v,
    mesh: Mesh,
    batch_spec=None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
):
    """Causal flash attention with batch sharded over dp/fsdp and heads
    over the ``heads`` rule's axes, ep and tp (sequence resident per
    device — the short-context layout).

    pallas_call has no GSPMD partitioning rule, so calling the kernel on
    sharded arrays inside jit would force replication; shard_map pins the
    per-device block the kernel sees. Callers must ensure the batch dim
    divides the data axes (see models/llama.py:_attention); heads the
    rule's axes do not divide stay whole (:func:`head_split`).
    """
    if batch_spec is None:
        batch_spec = clamp_spec(mesh, P(
            ("dcn", "dp", "fsdp"), head_split(mesh, q.shape[1])[0], None,
            None))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    fn = functools.partial(
        flash_attention, causal=True, scale=scale,
        block_q=block_q, block_k=block_k,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(q, k, v)


def full_causal_attention(q, k, v, scale: Optional[float] = None):
    """Reference dense causal attention (B, H, S, D) — the correctness
    oracle for ring attention and the single-device fallback."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = q.shape[2]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", probs.astype(v.dtype), v
    ).astype(q.dtype)
