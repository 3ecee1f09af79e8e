"""Blockwise (flash) attention as a TPU Pallas kernel, forward + backward.

The reference (cyh-ant/dlrover) ships no attention kernel — it orchestrates
Megatron/DeepSpeed jobs that bring their own (SURVEY.md §5.7). A TPU-native
stack owns its compute path, so this module supplies the fused attention
kernel the models layer and the ring-attention long-context layer build on.

Design (MXU/VMEM-first):

- Grid ``(B, H, num_q_blocks, num_k_blocks)`` with the K dimension
  innermost: TPU grids execute sequentially on a core, so the online-softmax
  accumulators (running max ``m``, denominator ``l``, unnormalized output
  ``acc``) live in VMEM scratch and carry across K-block steps — no HBM
  round-trips inside a Q row.
- Each step is one ``(block_q, d) @ (d, block_k)`` MXU matmul in f32 plus
  VPU elementwise (exp / mask / rescale); inputs stay bf16, accumulation
  f32 (``preferred_element_type``).
- Every grid step falls in one of three classes by its block's place
  against the causal diagonal and the padded tails (``flash_block_plan``
  counts them): a *skip* step lies wholly in the causal future and runs
  no body, and its index maps point at a block the pipeline already
  holds or fetches next, so it issues no DMA either; a *full* step lies
  wholly on or below the diagonal and inside both true lengths, and runs
  the score chain with no iota, compare or ``where``; a *masked* step
  crosses the diagonal or holds padded rows or columns, and builds and
  applies the mask. The classes follow from the program ids and the
  static shapes only; a length mask exists only where padding does.
- Row statistics (``m``/``l``/``lse``) are kept lane-replicated with shape
  ``(block_q, 128)`` — the VMEM-tileable layout for per-row scalars (same
  scheme as XLA's reference kernels).
- The kernel also returns the per-row log-sum-exp, which makes partial
  results mergeable: ring attention combines per-ring-step partials with a
  stable logsumexp merge (see parallel/ring_attention.py), and the backward
  pass recomputes probabilities from ``lse`` instead of storing them.
- Backward is two kernels — dq (grid K-innermost, dq accumulates in
  scratch) and dk/dv (grid Q-innermost) — the standard recomputation
  formulation: ``ds = p * (dp - delta)`` with
  ``delta = rowsum(do * o) - dlse`` (the ``dlse`` term supports cotangents
  flowing into the returned lse from the ring merge).
- The forward rule names ``o`` and ``lse`` (``FLASH_RESIDUALS``) and every
  remat policy of ``models/`` keeps them: a rematerialised layer's
  backward pass reads them instead of running the forward kernel again.
  That costs ``B·H·S·D`` in the input dtype plus ``B·H·S`` f32 a call:
  one output a layer application; under ring attention one a ring step,
  ``sp`` outputs of ``S / sp`` rows a layer on each device, as much as one
  output of the whole sequence.

On non-TPU backends (CPU tests) the kernels run in pallas interpret mode.
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common.constants import ConfigKey, env_int
from dlrover_tpu.common.log import log_once
from dlrover_tpu.observability.registry import get_registry

NEG_INF = float(-1e30)  # avoid -inf arithmetic inside the kernel
LANES = 128  # lane width for replicated row statistics
# ``checkpoint_name``s of the forward kernel's output and log-sum-exp, the
# backward kernels' residuals that only the forward kernel can make
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend != "tpu":
        log_once(
            "pallas kernels run in INTERPRET mode: default backend is %r, "
            "not tpu", backend,
        )
        return True
    return False


def _vmem_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def repeat_kv(k, v, rep: int):
    """Broadcast grouped-query K/V heads up to the query head count for
    kernels that take one KV timeline per query head. Head axis is 1
    ((B, KV, S, D) → (B, KV*rep, S, D)); the ONE shared site for the
    GQA repeat convention (llama attention, ulysses, decode prefill)."""
    if rep <= 1:
        return k, v
    return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


# ---------------------------------------------------------------------------
# the causal block plan
# ---------------------------------------------------------------------------


class BlockPlan(NamedTuple):
    """Grid steps of one (row, head) of a flash call, by what each does."""

    skip: int    # wholly in the causal future: no body, no fetch
    full: int    # wholly attended, no padding: the body without a mask
    masked: int  # crosses the diagonal or holds padding: the masked body


class _Blocks(NamedTuple):
    """A call's blocking: block sizes, true lengths and the mask rule.
    ``step`` and ``mask`` take Python ints (the plan) and program ids (the
    kernels and their index maps) alike; a condition that the shapes
    settle is left out, never traced."""

    bq: int
    bk: int
    q_len: int
    kv_len: int
    causal: bool

    @property
    def nq(self) -> int:
        return -(-self.q_len // self.bq)

    @property
    def nk(self) -> int:
        return -(-self.kv_len // self.bk)

    def step(self, iq, ik):
        """``(live, full)`` of the step that pairs query block ``iq`` with
        key block ``ik``: live where some row may attend some column of
        it (causal is top-left, column <= row, which ``Sq != Sk`` callers
        rely on), full where every row may attend every column and none
        is padding. ``True`` where the shapes settle it."""
        live = full = True
        if self.causal:
            live = ik * self.bk <= iq * self.bq + self.bq - 1
            full = ik * self.bk + self.bk - 1 <= iq * self.bq
        if self.kv_len % self.bk:
            full = full & ((ik + 1) * self.bk <= self.kv_len)
        if self.q_len % self.bq:
            full = full & ((iq + 1) * self.bq <= self.q_len)
        return live, full

    def mask(self, iq, ik):
        """The ``(bq, bk)`` mask of a masked step: one term for each
        condition ``step`` holds."""
        shape = (self.bq, self.bk)
        rows = iq * self.bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = ik * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        terms = []
        if self.causal:
            terms.append(cols <= rows)
        if self.kv_len % self.bk:
            terms.append(cols < self.kv_len)
        if self.q_len % self.bq:
            terms.append(rows < self.q_len)
        return functools.reduce(jnp.logical_and, terms)

    def row_step(self, iq, ik):
        """``(iq, ik)`` of the blocks that step ``(iq, ik)`` of the forward
        and dq grids fetches: its own where live. Skip steps end a row,
        and take the next row's first blocks, so the pipeline fetches them
        under the row's last live step and nothing after; on the last row,
        which no row follows, they keep the row's last live blocks."""
        live, _ = self.step(iq, ik)
        if live is True:
            return iq, ik
        last_row = iq == self.nq - 1
        last_live = (iq * self.bq + self.bq - 1) // self.bk
        return (jnp.where(live | last_row, iq, iq + 1),
                jnp.where(live, ik, jnp.where(last_row, last_live, 0)))

    def column_q(self, ik, iq):
        """The query block that step ``(ik, iq)`` of the dkv kernel fetches
        (its q, do, lse and delta): its own where live. Skip steps start a
        key block's column, and take the column's first live block (the
        last block, where no row attends the column): the pipeline fetches
        it under the previous column's last step, and nothing after."""
        live, _ = self.step(iq, ik)
        if live is True:
            return iq
        first = jnp.minimum(ik * self.bk // self.bq, self.nq - 1)
        return jnp.where(live, iq, first)

    def plan(self) -> BlockPlan:
        steps = [self.step(iq, ik)
                 for iq in range(self.nq) for ik in range(self.nk)]
        live = sum(live for live, _ in steps)
        full = sum(full for _, full in steps)
        return BlockPlan(len(steps) - live, full, live - full)


def _blocks(Sq: int, Sk: int, block_q: int, block_k: int,
            causal: bool) -> _Blocks:
    """The blocking a call runs with: the requested blocks, clamped to the
    sequence lengths rounded up to a sublane tile (8)."""
    return _Blocks(min(block_q, _round_up(Sq, 8)),
                   min(block_k, _round_up(Sk, 8)), Sq, Sk, causal)


def flash_block_plan(Sq: int, Sk: int, block_q: int, block_k: int,
                     causal: bool) -> BlockPlan:
    """How many grid steps of one (row, head) of a flash call over ``Sq``
    queries and ``Sk`` keys skip, run unmasked and run masked, at the
    blocks the call is given. All three kernels follow it: a step has one
    class whichever grid order runs it. At the cells' shapes (4,096, blocks
    512 x 1024, causal) it is 12 skip, 12 full and 8 masked of 32."""
    return _blocks(Sq, Sk, block_q, block_k, causal).plan()


def _count_steps(kernel: str, plan: BlockPlan, rows_x_heads: int) -> None:
    """Add a traced call's grid steps to the registry, by class."""
    steps = get_registry().counter(
        "dlrover_flash_grid_steps_total",
        "Grid steps of the flash kernels traced in this process, by kernel "
        "and by what the step does (skip, full, masked)",
        labelnames=("kernel", "block"),
    )
    for block, n in plan._asdict().items():
        steps.labels(kernel=kernel, block=block).inc(n * rows_x_heads)


def _run_live(body, blocks: _Blocks, plan: BlockPlan, iq, ik) -> None:
    """``body(mask)`` on the live steps: ``mask`` None on a full step, the
    block's mask on a masked one, built there only; a skip step runs
    nothing. A class the plan does not hold is not traced."""
    live, full = blocks.step(iq, ik)
    if full is True:  # not causal, nothing padded: every step is full
        body(None)
        return
    if plan.full:
        pl.when(full)(lambda: body(None))
    if plan.masked:
        pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
            lambda: body(blocks.mask(iq, ik)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, blocks: _Blocks, plan: BlockPlan,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _attend(mask):
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]  # (block_q, LANES), lane-replicated
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, :1])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_live(_attend, blocks, plan, iq, ik)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l == 0.0, NEG_INF, m_scr[:] + jnp.log(safe_l)
        )


def _fwd(
    q, k, v, *, scale, causal, block_q, block_k, interpret,
) -> Tuple[jax.Array, jax.Array]:
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    blocks = _blocks(Sq, Sk, block_q, block_k, causal)
    bq, bk, nq, nk = blocks.bq, blocks.bk, blocks.nq, blocks.nk
    q_pad = nq * bq - Sq
    k_pad = nk * bk - Sk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0))) if q_pad else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0))) if k_pad else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0))) if k_pad else v
    plan = blocks.plan()
    _count_steps("flash_fwd", plan, B * H)

    def q_map(b, h, i, j):
        return (b, h, blocks.row_step(i, j)[0], 0)

    def kv_map(b, h, i, j):
        return (b, h, blocks.row_step(i, j)[1], 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, blocks=blocks, plan=plan,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            _vmem_spec((1, 1, bq, D), q_map),
            _vmem_spec((1, 1, bk, D), kv_map),
            _vmem_spec((1, 1, bk, Dv), kv_map),
        ],
        out_specs=[
            _vmem_spec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * bq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, nq * bq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :, :Sq], lse[:, :, :Sq, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale: float, blocks: _Blocks, plan: BlockPlan,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accum(mask):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, :1])
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_live(_accum, blocks, plan, iq, ik)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, blocks: _Blocks, plan: BlockPlan,
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accum(mask):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, :1])
        # dk += ds^T @ q * scale
        dk_scr[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_live(_accum, blocks, plan, iq, ik)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    q, k, v, o, lse, do, dlse, *, scale, causal, block_q, block_k, interpret,
):
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    blocks = _blocks(Sq, Sk, block_q, block_k, causal)
    bq, bk, nq, nk = blocks.bq, blocks.bk, blocks.nq, blocks.nk
    q_pad = nq * bq - Sq
    k_pad = nk * bk - Sk

    # delta_i = rowsum(do_i * o_i) - dlse_i  (f32, one fused
    # elementwise+reduce at the jnp level — not worth a kernel)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ) - dlse.astype(jnp.float32)

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, q_pad), (0, 0))) if q_pad else x

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, k_pad), (0, 0))) if k_pad else x

    def rows_to_lanes(x, fill=0.0):
        """(B,H,Sq) f32 → (B,H,Sq+pad,LANES) lane-replicated."""
        if q_pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, q_pad)), constant_values=fill)
        return jnp.broadcast_to(x[..., None], x.shape + (LANES,))

    qp, dop = padq(q), padq(do)
    kp, vp = padk(k), padk(v)
    lsep = rows_to_lanes(lse, fill=NEG_INF)
    deltap = rows_to_lanes(delta)
    plan = blocks.plan()
    _count_steps("flash_bwd_dq", plan, B * H)
    _count_steps("flash_bwd_dkv", plan, B * H)

    def row_map(b, h, i, j):
        return (b, h, blocks.row_step(i, j)[0], 0)

    def kv_map(b, h, i, j):
        return (b, h, blocks.row_step(i, j)[1], 0)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, blocks=blocks, plan=plan,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            _vmem_spec((1, 1, bq, D), row_map),
            _vmem_spec((1, 1, bk, D), kv_map),
            _vmem_spec((1, 1, bk, Dv), kv_map),
            _vmem_spec((1, 1, bq, Dv), row_map),
            _vmem_spec((1, 1, bq, LANES), row_map),
            _vmem_spec((1, 1, bq, LANES), row_map),
        ],
        out_specs=_vmem_spec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    def column_map(b, h, j, i):
        return (b, h, blocks.column_q(j, i), 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, blocks=blocks, plan=plan,
        ),
        grid=(B, H, nk, nq),
        in_specs=[
            _vmem_spec((1, 1, bq, D), column_map),
            _vmem_spec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, bk, Dv), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, bq, Dv), column_map),
            _vmem_spec((1, 1, bq, LANES), column_map),
            _vmem_spec((1, 1, bq, LANES), column_map),
        ],
        out_specs=[
            _vmem_spec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            _vmem_spec((1, 1, bk, Dv), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nk * bk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, nk * bk, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :Sq], dk[:, :, :Sk], dv[:, :, :Sk]


# ---------------------------------------------------------------------------
# public API (custom_vjp so ring-merge lse cotangents flow)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _fwd(
        q, k, v, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    # named, so that a remat policy can keep the two residuals only the
    # kernel makes (q, k, v a replay remakes from the projections); outside
    # ``jax.checkpoint`` a name does nothing
    o = checkpoint_name(o, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    do, dlse = g
    # the backward kernels' working set (5 dots/block, 2-3 f32 scratch
    # accumulators) tiles differently from the forward's — let the bwd
    # blocks be tuned independently (read at trace time)
    bq = env_int(ConfigKey.FLASH_BWD_BLOCK_Q, 0) or block_q
    bk = env_int(ConfigKey.FLASH_BWD_BLOCK_K, 0) or block_k
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, dlse, scale=scale, causal=causal,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    return_lse: bool = False,
    interpret: Optional[bool] = None,
):
    """Fused blockwise attention. q/k: (B, H, S, D), v: (B, H, S, Dv);
    GQA callers repeat KV heads first (XLA fuses the broadcast into the
    block loads). The value width may differ from the query/key width
    (latent attention: 192-wide queries and keys over 128-wide values,
    models/mla.py); the scale defaults to ``D ** -0.5`` and the output
    and the value gradient follow ``Dv``.

    Default blocks are empirically tuned on v5e (fwd+bwd at B4 H16 S2048
    D128: 512×1024 is 3.3× the fused-dense XLA path and within 10% of the
    best measured combo; 128×128 was 6× slower — grid-overhead-bound).
    Blocks are clamped to the sequence length, so short-S callers are
    unaffected.

    Returns ``o`` (B, H, Sq, Dv), plus the per-row logsumexp (B, H, Sq) f32
    when ``return_lse`` — the handle ring attention uses to merge partials.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _default_interpret()
    o, lse = _flash(
        q, k, v, float(scale), bool(causal), int(block_q), int(block_k),
        bool(interpret),
    )
    return (o, lse) if return_lse else o


# ---------------------------------------------------------------------------
# decode (single-token) attention against a KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, *rest,
    scale: float, block_k: int, g_blk: int, rows: int, quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    pos = pos_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _attend():
        # whole-block loads over the FUSED (batch x kv-head) axis: one
        # DMA fetches the K/V block for every batch row and head at
        # once, dequantized once, and the per-group matmuls run as ONE
        # batched dot_general. (History: a python unroll over heads was
        # 16 separate matmuls and measured slower than XLA's einsum; a
        # grid axis over batch (the r3 shape) paid per-grid-step
        # overhead B times per block — fusing batch into the block cut
        # the grid from B*nk to ~nk steps per call.) The cache is
        # head-major (models/decode.py init_kv_cache), so blocks arrive
        # already batched — no in-VMEM transpose.
        g, rws = g_blk, rows
        # int8 blocks: only the s8->f32 CONVERT touches every (row, d)
        # element — the per-vector scales fold into the (rows x block_k)
        # score/probability planes instead (ks into the QK columns, vs
        # into p before the AV matmul), which is head_dim x fewer VPU
        # multiplies than scaling the K/V blocks themselves. HBM still
        # saw only int8 values + one f32 scale per vector.
        kt = k_ref[:].astype(jnp.float32)           # (g_blk, block_k, d)
        vt = v_ref[:].astype(jnp.float32)
        q = q_ref[:].astype(jnp.float32)            # (g_blk, rows, d)
        s = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (g_blk, rows, block_k)
        if quantized:
            s = s * ks_ref[:][:, None, :]
        colmask = (
            j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block_k), 2
            )
        ) <= pos
        s = jnp.where(colmask, s, NEG_INF)
        m_prev = m_scr[:].reshape(g, rws, LANES)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)           # lane-replicated
        p = jnp.where(colmask, jnp.exp(s - m_new[:, :, :1]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = (
            l_scr[:].reshape(g, rws, LANES) * alpha
            + jnp.sum(p, axis=-1, keepdims=True)
        ).reshape(g * rws, LANES)
        m_scr[:] = m_new.reshape(g * rws, LANES)
        d = acc_scr.shape[-1]
        pv = p * vs_ref[:][:, None, :] if quantized else p
        acc_scr[:] = (
            acc_scr[:].reshape(g, rws, d) * alpha[:, :, :1]
            + jax.lax.dot_general(
                pv, vt, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        ).reshape(g * rws, d)

    # blocks fully past ``pos`` do no work (their index map also clamps,
    # so the pipeline re-targets an already-fetched block — ~no bandwidth)
    pl.when(j * block_k <= pos)(_attend)

    @pl.when(j == nk - 1)
    def _finish():
        d = acc_scr.shape[-1]
        l = l_scr[:].reshape(g_blk, rows, LANES)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (
            acc_scr[:].reshape(g_blk, rows, d) / safe_l[:, :, :1]
        ).astype(o_ref.dtype)


def flash_decode_attention(
    q, k, v, pos,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,
    k_scale=None,
    v_scale=None,
):
    """Single-token attention against a KV cache, fused.

    q: (B, KV, G, Dh) — the current token's query heads grouped by KV
    head (G = H // KV, the GQA group). k/v: (B, KV, T, Dh) — the cache in
    its head-major layout (blocks arrive batched by head, each read once
    for ALL of that head's queries). ``pos``: scalar int32 — only
    cache slots ``[0, pos]`` attend, and K blocks beyond ``pos`` are
    skipped at ~zero bandwidth via a scalar-prefetch-clamped index map.
    T must divide by ``block_k`` (callers round the cache length up at
    creation).

    With ``k_scale``/``v_scale`` (B, KV, T) f32, k/v are int8 and are
    dequantized inside the kernel (per-vector absmax scales) — HBM
    traffic for the cache is halved vs bf16, which is the whole game for
    the bandwidth-bound decode step. An XLA-level dequant can't deliver
    that: it materializes the bf16 copy first (models/decode.py history).

    Returns (B, KV, G, Dh).
    """
    B, KV, G, Dh = q.shape
    T = k.shape[2]  # head-major cache: (B, KV, T, Dh)
    if T % block_k != 0:
        raise ValueError(f"cache length {T} not divisible by {block_k}")
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale given without v_scale")
    if scale is None:
        scale = Dh ** -0.5
    if interpret is None:
        interpret = _default_interpret()
    rows = _round_up(G, 8)
    if rows != G:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - G), (0, 0)))
    # batch and kv-head fuse into ONE leading axis (free reshapes): a
    # grid axis over batch made the pipeline pay per-grid-step overhead
    # B times per K block — fused blocks make each DMA B*KV-wide and cut
    # the grid to ~nk steps. bf16 blocks are 2x int8 bytes, so they use
    # half the K width to hold the same VMEM footprint.
    fused = B * KV
    qf = q.reshape(fused, rows, Dh)
    kf = k.reshape(fused, T, Dh)
    vf = v.reshape(fused, T, Dh)
    bk = block_k if quantized else max(128, block_k // 2)
    if T % bk != 0:
        # the halved bf16 width must still tile the cache — fall back to
        # the caller-validated divisor rather than silently dropping the
        # T % bk tail slots from attention
        bk = block_k
    # largest row-chunk of the fused axis whose K/V blocks stay ~<=1 MB
    # each: k+v double-buffered is 4 of these in flight, plus scales/q/
    # out/scratch, against the ~16 MB scoped-VMEM limit (2 MB blocks
    # measured 17.45M > 16M on v5e). Sized from the cache dtype's real
    # itemsize, and chosen as the largest DIVISOR of the fused axis (not
    # repeated halving, which strands odd factors over the limit).
    limit = max(8, (1024 * 1024) // (bk * Dh * k.dtype.itemsize))
    g_blk = max(
        d for d in range(1, fused + 1) if fused % d == 0 and d <= limit
    )
    ng = fused // g_blk
    nk = T // bk
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, scale=float(scale), block_k=int(bk),
        g_blk=g_blk, rows=rows, quantized=quantized,
    )

    def _clamped(i, j, pos_ref):
        return (i, jnp.minimum(j, pos_ref[0] // bk), 0)

    def _clamped2(i, j, pos_ref):
        return (i, jnp.minimum(j, pos_ref[0] // bk))

    in_specs = [
        _vmem_spec((g_blk, rows, Dh), lambda i, j, p: (i, 0, 0)),
        _vmem_spec((g_blk, bk, Dh), _clamped),
        _vmem_spec((g_blk, bk, Dh), _clamped),
    ]
    operands = [qf, kf, vf]
    if quantized:
        in_specs += [
            _vmem_spec((g_blk, bk), _clamped2),
            _vmem_spec((g_blk, bk), _clamped2),
        ]
        operands += [
            jnp.asarray(k_scale, jnp.float32).reshape(fused, T),
            jnp.asarray(v_scale, jnp.float32).reshape(fused, T),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ng, nk),
        in_specs=in_specs,
        out_specs=[
            _vmem_spec((g_blk, rows, Dh), lambda i, j, p: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g_blk * rows, LANES), jnp.float32),
            pltpu.VMEM((g_blk * rows, LANES), jnp.float32),
            pltpu.VMEM((g_blk * rows, Dh), jnp.float32),
        ],
    )
    out_dtype = q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((fused, rows, Dh), out_dtype)],
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, *operands)[0]
    return out.reshape(B, KV, rows, Dh)[:, :, :G]
