"""Ulysses sequence parallelism: all-to-all head-scatter attention.

The second long-context strategy next to ring attention (the reference has
neither in core — SURVEY.md §5.7 — it delegates to Megatron/DeepSpeed;
DeepSpeed-Ulysses is the pattern this re-creates TPU-natively). Where ring
attention keeps Q resident and rotates K/V around the ``sp`` ring, Ulysses
re-shards *once* per attention call:

1. inputs arrive sequence-sharded: each device holds (B, H, S/sp, D);
2. one ``all_to_all`` per operand over ``sp`` splits the head axis and
   gathers the sequence axis → (B, H/sp, S, D): every device now sees the
   FULL sequence for a 1/sp slice of the heads;
3. plain (flash) causal attention runs per head group — no masking
   gymnastics, any attention kernel drops in unchanged;
4. a mirror ``all_to_all`` restores the sequence-sharded layout.

Traffic: four all-to-alls per call (q, k, v in; output out), each moving
the operand's local bytes once (XLA lowers them onto ICI as balanced
point-to-point traffic). GQA keeps K/V *unrepeated* through the transform
— heads broadcast only after the scatter — so the k/v legs move 1/rep the
bytes of the q leg. Versus ring's sp ppermute hops the total volume is
comparable, but Ulysses materializes the full sequence per device, so S is
bounded by HBM; ring streams K/V and is not. Head counts must divide:
(H / heads) % sp == 0 for q, and for unrepeated GQA also
(H_kv / heads) % sp, where ``heads`` is the chips the heads' rule splits
them over (ep x tp).

Chunk order: ``all_to_all(tiled=True)`` concatenates received blocks in
ring-index order, which is global sequence order (contiguous chunks laid
out over ``sp``), so causal masks stay correct with no re-indexing.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common.log import log_once
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.sharding import clamp_spec, head_split


def _ulysses_local(q, k, v, axis_name: str, scale: float, use_pallas: bool,
                   block_q: int, block_k: int):
    """Per-device Ulysses body (inside shard_map).

    q: (B, Hq_local, S_local, D); k/v: (B, Hkv_local, S_local, D) with
    Hkv_local ≤ Hq_local (GQA: repeated to match *after* the head scatter,
    so the k/v all-to-alls move unduplicated bytes).
    """
    # (B, H, S/sp, D) -> (B, H/sp, S, D): scatter heads, gather sequence
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name,
        split_axis=1, concat_axis=2, tiled=True,
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    from dlrover_tpu.ops.flash_attention import repeat_kv

    kg, vg = repeat_kv(kg, vg, qg.shape[1] // kg.shape[1])
    if use_pallas:
        out = flash_attention(
            qg, kg, vg, causal=True, scale=scale,
            block_q=block_q, block_k=block_k,
        )
    else:
        s = qg.shape[2]
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", qg, kg, preferred_element_type=jnp.float32
        ) * scale
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bhkd->bhqd", probs.astype(vg.dtype), vg
        ).astype(qg.dtype)
    # (B, H/sp, S, D) -> (B, H, S/sp, D): mirror transform
    return jax.lax.all_to_all(
        out, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def _local_heads(mesh: Mesh, spec, n_heads: int) -> int:
    """Per-device head count under ``spec``'s head entry (index 1)."""
    entry = spec[1] if len(spec) > 1 else None
    if entry is None:
        return n_heads
    axes = entry if isinstance(entry, tuple) else (entry,)
    denom = 1
    for a in axes:
        denom *= mesh.shape.get(a, 1)
    return n_heads // denom


def ulysses_attention(
    q, k, v,
    mesh: Mesh,
    sp_axis: str = "sp",
    batch_spec=None,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    block_q: int = 512,
    block_k: int = 1024,
):
    """Causal attention with S sharded over ``sp_axis``, computed by
    head-scatter/seq-gather all-to-all (DeepSpeed-Ulysses style).

    q: (B, H, S, D); k/v: (B, H_kv, S, D) with H_kv dividing H (GQA —
    repeated internally after the scatter). S sharded over sp, heads
    optionally over ``batch_spec``'s head axes, B over dp/fsdp. Returns
    q's shape/sharding. Per-device head counts (for q AND kv) must be
    divisible by the sp axis size.
    """
    if batch_spec is None:
        # library default, clamped to the mesh's axes; explicit caller
        # specs pass through verbatim so typos still fail loudly
        # split as the unrepeated K/V heads allow: they divide the
        # query heads (GQA)
        batch_spec = clamp_spec(mesh, P(
            ("dcn", "dp", "fsdp"), head_split(mesh, k.shape[1])[0], "sp",
            None))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
        if not use_pallas:
            log_once(
                "ulysses attention: dense inner block, not the flash kernel "
                "(default backend is %r, not tpu)", jax.default_backend(),
            )
    sp = mesh.shape.get(sp_axis, 1)
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"q heads ({q.shape[1]}) must be a multiple of kv heads "
            f"({k.shape[1]})"
        )
    for name, t in (("q", q), ("kv", k)):
        h_local = _local_heads(mesh, batch_spec, t.shape[1])
        if h_local % sp != 0:
            raise ValueError(
                f"Ulysses needs per-device {name} heads ({h_local}) "
                f"divisible by sp ({sp}); use ring_attention for "
                "head-poor long-context configs"
            )
    fn = functools.partial(
        _ulysses_local, axis_name=sp_axis, scale=scale,
        use_pallas=use_pallas, block_q=block_q, block_k=block_k,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(q, k, v)
