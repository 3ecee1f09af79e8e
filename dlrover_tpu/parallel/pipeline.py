"""Pipeline parallelism over the ``pp`` mesh axis — GPipe-style microbatch
pipelining, TPU-first.

Reference accounting: DLRover only *accounts* for PP via Megatron checkpoint
shard math (flash_checkpoint/megatron_engine.py:53–55); the schedule itself
lives in Megatron. A from-scratch TPU stack needs its own, built the XLA
way rather than Megatron's way:

- **No per-stage processes / p2p sends.** All stages live in one jitted
  SPMD program: ``shard_map`` over the ``pp`` axis holds stage ``i``'s
  layer group on pipeline rank ``i``; activations move ring-wise with
  ``lax.ppermute`` (ICI neighbor hops — the mesh layout puts ``pp``
  outermost where inter-stage traffic is smallest, mesh.py:13).
- **The schedule is a ``lax.scan`` over ticks.** ``T = M + S - 1`` ticks
  stream ``M`` microbatches through ``S`` stages (GPipe fill/drain; bubble
  fraction ``(S-1)/T``). Static shapes, no data-dependent control flow —
  one compile.
- **Backward is autodiff, not hand scheduling.** ``ppermute`` transposes to
  the reverse permute and ``scan`` reverses, so differentiating the
  pipelined forward *is* the reverse pipeline schedule; per-tick
  ``jax.checkpoint`` keeps live memory at one activation per stage instead
  of T of them.
"""

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stages(tree: Any, n_stages: int) -> Any:
    """Reshape depth-stacked per-layer params ``(L, ...)`` into pipeline
    stage groups ``(S, L/S, ...)`` (contiguous layer ranges per stage)."""

    def _split(leaf):
        L = leaf.shape[0]
        if L % n_stages != 0:
            raise ValueError(
                f"{L} layers not divisible into {n_stages} pipeline stages"
            )
        return leaf.reshape((n_stages, L // n_stages) + leaf.shape[1:])

    return jax.tree.map(_split, tree)


def unstack_stages(tree: Any) -> Any:
    """Inverse of :func:`stack_stages` — back to ``(L, ...)``."""
    return jax.tree.map(
        lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), tree
    )


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    microbatches: jnp.ndarray,
    mesh,
    axis: str = "pp",
    checkpoint_ticks: bool = True,
    batch_axes=None,
):
    """Run ``M`` microbatches through ``S = mesh.shape[axis]`` stages.

    ``stage_params``: pytree whose leaves have leading dim ``S`` (one slice
    per stage — see :func:`stack_stages`). ``microbatches``: ``(M, B, ...)``
    activations, shape-uniform across stages. Returns ``(M, B, ...)``
    outputs of the last stage. Fully differentiable.

    ``batch_axes``: mesh axis name(s) sharding the per-microbatch batch dim
    (dim 1), e.g. ``("dp", "fsdp")``. Without it every rank of those axes
    would process the full global batch redundantly — pass it whenever the
    pp mesh also carries data axes. Stage params stay replicated across
    non-pp axes in this schedule (pp×fsdp weight sharding needs per-leaf
    specs — future work).
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    T = M + S - 1
    if batch_axes is not None:
        present = tuple(
            a for a in (
                (batch_axes,) if isinstance(batch_axes, str) else batch_axes
            ) if mesh.shape.get(a, 1) > 1
        )
        total = 1
        for a in present:
            total *= mesh.shape[a]
        # fall back to replicated batch when the per-microbatch batch dim
        # can't be evenly sharded (correctness over the dp speedup)
        if not present or microbatches.shape[1] % total != 0:
            batch_axes = None
        else:
            batch_axes = present
    x_spec = P(None, batch_axes) if batch_axes else P()
    fn = jax.checkpoint(stage_fn) if checkpoint_ticks else stage_fn

    def body(params_sharded, x):
        # local leaves arrive as (1, ...) slices of the stage dim
        params_local = jax.tree.map(lambda p: p[0], params_sharded)
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(x[0])
        ybuf = jnp.zeros_like(x)  # written only on the last stage

        def tick(carry, t):
            state, ybuf = carry
            # neighbor hop: stage i's previous output arrives at stage i+1
            prev = jax.lax.ppermute(
                state, axis, [(i, i + 1) for i in range(S - 1)]
            )
            feed = jax.lax.dynamic_index_in_dim(
                x, jnp.minimum(t, M - 1), 0, keepdims=False
            )
            inp = jnp.where(idx == 0, feed, prev)
            out = fn(params_local, inp)
            # drain: last stage emits microbatch t-(S-1) at tick t
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            live = jnp.logical_and(idx == S - 1, t >= S - 1)
            slot = jax.lax.dynamic_index_in_dim(
                ybuf, widx, 0, keepdims=False
            )
            ybuf = jax.lax.dynamic_update_index_in_dim(
                ybuf, jnp.where(live, out, slot), widx, 0
            )
            return (out, ybuf), None

        (_, ybuf), _ = jax.lax.scan(
            tick, (state, ybuf), jnp.arange(T)
        )
        return ybuf[None]  # (1, M, ...) per stage → (S, M, ...) stacked

    # jit here (inlined under an outer jit) — per-tick jax.checkpoint
    # inside shard_map is trace-only
    out_spec = (
        P(axis, None, batch_axes) if batch_axes else P(axis)
    )
    out = jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=out_spec,
        check_vma=False,
    ))(stage_params, microbatches)
    return out[-1]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe fill/drain overhead — pick M >= 4*S to keep it under 20%."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def microbatch(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """(B, ...) → (n, B/n, ...)"""
    if x.shape[0] % n != 0:
        raise ValueError(f"batch {x.shape[0]} not divisible by {n}")
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


def unmicrobatch(x: jnp.ndarray) -> jnp.ndarray:
    """(n, b, ...) → (n*b, ...)"""
    return x.reshape((-1,) + x.shape[2:])


__all__ = [
    "pipeline_apply",
    "stack_stages",
    "unstack_stages",
    "bubble_fraction",
    "microbatch",
    "unmicrobatch",
]
