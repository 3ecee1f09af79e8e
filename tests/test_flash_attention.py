"""Pallas flash attention vs the dense oracle (interpret mode on CPU).

Mirrors the reference's correctness-oracle pattern (SURVEY.md §4): every
fused path is checked against straight-line math. Covers forward, backward
(through custom_vjp incl. the lse cotangent), GQA shapes, non-multiple
sequence lengths (padding), and the pallas ring-attention path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.ring_attention import (
    _merge_partials,
    full_causal_attention,
    ring_attention,
)


def _rand_qkv(B=2, H=3, S=64, D=32, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), dtype=dtype)
        for i in range(3)
    )


def _dense_full(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


class TestFlashForward:
    def test_causal_matches_dense(self):
        q, k, v = _rand_qkv()
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        o_ref = full_causal_attention(q, k, v)
        np.testing.assert_allclose(o, o_ref, atol=2e-5)

    def test_full_matches_dense(self):
        q, k, v = _rand_qkv()
        o = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        np.testing.assert_allclose(o, _dense_full(q, k, v), atol=2e-5)

    def test_ragged_seq_len_padding(self):
        # S=56 is not a multiple of the 32-block: exercises pad+mask
        q, k, v = _rand_qkv(S=56)
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        np.testing.assert_allclose(
            o, full_causal_attention(q, k, v), atol=2e-5
        )

    def test_lse_matches_logsumexp(self):
        q, k, v = _rand_qkv()
        scale = q.shape[-1] ** -0.5
        _, lse = flash_attention(
            q, k, v, causal=False, block_q=32, block_k=32, return_lse=True
        )
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        np.testing.assert_allclose(
            lse, jax.nn.logsumexp(s, axis=-1), atol=2e-5
        )

    def test_cross_attention_shapes(self):
        # Sq != Sk (the shape ring attention feeds the non-diagonal steps)
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (2, 2, 32, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 48, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 48, 16))
        o = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
        np.testing.assert_allclose(o, _dense_full(q, k, v), atol=2e-5)


class TestFlashBackward:
    def test_grads_match_dense(self):
        q, k, v = _rand_qkv()

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
            return (o**2).sum()

        def loss_ref(q, k, v):
            return (full_causal_attention(q, k, v) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_lse_cotangent(self):
        # grads flowing only through the returned lse (the ring-merge path)
        q, k, v = _rand_qkv(S=32)

        def loss_flash(q, k, v):
            _, lse = flash_attention(
                q, k, v, causal=False, block_q=16, block_k=16,
                return_lse=True,
            )
            return (lse**2).sum()

        def loss_ref(q, k, v):
            scale = q.shape[-1] ** -0.5
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            return (jax.nn.logsumexp(s, axis=-1) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4)


class TestMergePartials:
    def test_merge_two_halves_equals_whole(self):
        q, k, v = _rand_qkv(S=64)
        half = 32
        o1, lse1 = flash_attention(
            q, k[:, :, :half], v[:, :, :half], causal=False,
            block_q=32, block_k=32, return_lse=True,
        )
        o2, lse2 = flash_attention(
            q, k[:, :, half:], v[:, :, half:], causal=False,
            block_q=32, block_k=32, return_lse=True,
        )
        o, _ = _merge_partials(
            o1.astype(jnp.float32), lse1, o2.astype(jnp.float32), lse2
        )
        np.testing.assert_allclose(o, _dense_full(q, k, v), atol=2e-5)


class TestLlamaFlashWiring:
    """The model-level flash branch (auto-off on CPU CI) forced on."""

    def test_forward_matches_dense_path(self):
        from dlrover_tpu.models import llama

        c_flash = llama.LlamaConfig.tiny()
        c_flash = type(c_flash)(
            **{**c_flash.__dict__, "use_flash_attention": True}
        )
        c_dense = type(c_flash)(
            **{**c_flash.__dict__, "use_flash_attention": False}
        )
        params = llama.init_params(c_flash, jax.random.PRNGKey(0))
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (2, 48), 0, c_flash.vocab_size
        )
        lf = llama.forward(params, toks, c_flash)
        ld = llama.forward(params, toks, c_dense)
        # flash accumulates p@v in f32 while the dense path rounds probs to
        # bf16, so logits legitimately diverge at bf16 resolution × depth
        np.testing.assert_allclose(lf, ld, atol=1e-1)

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 cpu devices")
    def test_sharded_forward_matches_dense_path(self):
        from jax.sharding import Mesh

        from dlrover_tpu.models import llama

        mesh = Mesh(
            np.array(jax.devices()[:4]).reshape(1, 2, 2, 1),
            ("dp", "fsdp", "tp", "sp"),
        )
        c_flash = llama.LlamaConfig.tiny()
        c_flash = type(c_flash)(
            **{**c_flash.__dict__, "use_flash_attention": True}
        )
        c_dense = type(c_flash)(
            **{**c_flash.__dict__, "use_flash_attention": False}
        )
        params = llama.init_params(c_flash, jax.random.PRNGKey(0))
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (4, 48), 0, c_flash.vocab_size
        )
        with mesh:
            lf = jax.jit(
                lambda p, t: llama.forward(p, t, c_flash, mesh)
            )(params, toks)
        ld = llama.forward(params, toks, c_dense)
        np.testing.assert_allclose(
            np.asarray(lf), np.asarray(ld), atol=1e-1
        )


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 cpu devices")
class TestRingFlash:
    def _mesh(self, sp):
        from jax.sharding import Mesh

        devices = np.array(jax.devices()[:sp]).reshape(1, 1, 1, sp)
        return Mesh(devices, ("dp", "fsdp", "tp", "sp"))

    def test_ring_flash_matches_dense(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sp = 4
        mesh = self._mesh(sp)
        q, k, v = _rand_qkv(B=2, H=2, S=64, D=16)
        spec = P(("dp", "fsdp"), "tp", "sp", None)
        qs, ks, vs = (
            jax.device_put(t, NamedSharding(mesh, spec)) for t in (q, k, v)
        )
        o = ring_attention(qs, ks, vs, mesh, use_pallas=True, block_q=16,
                           block_k=16)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(full_causal_attention(q, k, v)),
            atol=2e-5,
        )

    def test_ring_flash_grads_match_dense(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sp = 4
        mesh = self._mesh(sp)
        q, k, v = _rand_qkv(B=1, H=2, S=32, D=16)
        spec = P(("dp", "fsdp"), "tp", "sp", None)
        qs, ks, vs = (
            jax.device_put(t, NamedSharding(mesh, spec)) for t in (q, k, v)
        )

        def loss_ring(q, k, v):
            o = ring_attention(
                q, k, v, mesh, use_pallas=True, block_q=8, block_k=8
            )
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_ref(q, k, v):
            return (full_causal_attention(q, k, v) ** 2).sum()

        gf = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), b, atol=1e-4)


class TestFlashDecode:
    def test_matches_masked_oracle_across_positions(self):
        from dlrover_tpu.ops.flash_attention import flash_decode_attention

        B, KV, G, Dh, T = 2, 4, 2, 16, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh), jnp.float32)
        k = jax.random.normal(ks[1], (B, KV, T, Dh), jnp.float32)
        v = jax.random.normal(ks[2], (B, KV, T, Dh), jnp.float32)
        scale = Dh ** -0.5
        for pos in (0, 7, 31, 37, 63):
            out = flash_decode_attention(q, k, v, pos, block_k=16)
            s = jnp.einsum("bkgd,bktd->bkgt", q, k) * scale
            mask = jnp.arange(T)[None, None, None, :] <= pos
            s = jnp.where(mask, s, -1e30)
            ref = jnp.einsum(
                "bkgt,bktd->bkgd", jax.nn.softmax(s, -1), v
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5,
                err_msg=f"pos={pos}",
            )

    def test_bf16_block_halving_never_drops_tail_slots(self):
        """The bf16 path halves the K block width for VMEM; if the
        halved width doesn't tile the cache it must fall back to the
        caller-validated block_k — not floor nk and silently drop the
        tail slots from attention (regression: T=192 block_k=16 made
        bk=128, nk=1, and keys 128..191 never attended)."""
        from dlrover_tpu.ops.flash_attention import flash_decode_attention

        B, KV, G, Dh, T = 1, 2, 2, 16, 192
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh), jnp.float32)
        k = jax.random.normal(ks[1], (B, KV, T, Dh), jnp.float32)
        v = jax.random.normal(ks[2], (B, KV, T, Dh), jnp.float32)
        pos = 150  # attends into the would-be-dropped tail
        out = flash_decode_attention(q, k, v, pos, block_k=16)
        scale = Dh ** -0.5
        s = jnp.einsum("bkgd,bktd->bkgt", q, k) * scale
        mask = jnp.arange(T)[None, None, None, :] <= pos
        s = jnp.where(mask, s, -1e30)
        ref = jnp.einsum("bkgt,bktd->bkgd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_rejects_indivisible_cache(self):
        from dlrover_tpu.ops.flash_attention import flash_decode_attention

        q = jnp.zeros((1, 2, 2, 16))
        k = v = jnp.zeros((1, 2, 60, 16))
        with pytest.raises(ValueError, match="not divisible"):
            flash_decode_attention(q, k, v, 0, block_k=16)

    def test_int8_fused_dequant_matches_dequantized_oracle(self):
        """The in-kernel dequant path must agree with attending over the
        explicitly dequantized cache (the XLA fallback path)."""
        from dlrover_tpu.ops.flash_attention import flash_decode_attention

        B, KV, G, Dh, T = 2, 2, 4, 16, 48
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh), jnp.float32)
        kf = jax.random.normal(ks[1], (B, KV, T, Dh), jnp.float32)
        vf = jax.random.normal(ks[2], (B, KV, T, Dh), jnp.float32)

        def quant(x):
            s = jnp.max(jnp.abs(x), axis=-1) / 127.0
            s = jnp.maximum(s, 1e-9)
            return (
                jnp.clip(jnp.round(x / s[..., None]), -127, 127)
                .astype(jnp.int8),
                s,
            )

        kq, ksc = quant(kf)
        vq, vsc = quant(vf)
        kd = kq.astype(jnp.float32) * ksc[..., None]
        vd = vq.astype(jnp.float32) * vsc[..., None]
        scale = Dh ** -0.5
        for pos in (0, 17, 47):
            out = flash_decode_attention(
                q, kq, vq, pos, block_k=16, k_scale=ksc, v_scale=vsc
            )
            s = jnp.einsum("bkgd,bktd->bkgt", q, kd) * scale
            mask = jnp.arange(T)[None, None, None, :] <= pos
            s = jnp.where(mask, s, -1e30)
            ref = jnp.einsum("bkgt,bktd->bkgd", jax.nn.softmax(s, -1), vd)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5,
                err_msg=f"pos={pos}",
            )


# ---------------------------------------------------------------------------
# the causal block plan: skip / full / masked grid steps
# ---------------------------------------------------------------------------


def _plan_by_mask(Sq, Sk, bq, bk, causal):
    """The plan counted from the dense mask, block by block: a block no
    pair of which may attend is skipped, one every pair of which may and
    that holds no padding is full, any other is masked."""
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    rows = np.arange(nq * bq)[:, None]
    cols = np.arange(nk * bk)[None, :]
    attend = (cols < Sk) & (rows < Sq)
    if causal:
        attend &= cols <= rows
    skip = full = 0
    for i in range(nq):
        for j in range(nk):
            block = attend[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            causal_part = (cols <= rows)[i * bq:(i + 1) * bq,
                                         j * bk:(j + 1) * bk]
            skip += bool(causal and not causal_part.any())
            full += bool(block.all())
    return skip, full, nq * nk - skip - full


@pytest.mark.parametrize("shape,expected", [
    pytest.param((4096, 4096, 512, 1024, True), (12, 12, 8), id="cells"),
    pytest.param((4096, 4096, 512, 1024, False), (0, 32, 0),
                 id="non-causal"),
    pytest.param((56, 56, 32, 32, True), (1, 0, 3), id="padded"),
    pytest.param((56, 56, 32, 32, False), (0, 1, 3),
                 id="padded-non-causal"),
    pytest.param((32, 64, 16, 16, True), (5, 1, 2), id="sq-lt-sk"),
    pytest.param((64, 32, 16, 16, True), (1, 5, 2), id="sq-gt-sk"),
    pytest.param((128, 128, 64, 32, True), (2, 2, 4), id="bq-gt-bk"),
    pytest.param((128, 128, 32, 64, True), (2, 2, 4), id="bq-lt-bk"),
])
def test_flash_block_plan(shape, expected):
    from dlrover_tpu.ops.flash_attention import BlockPlan, flash_block_plan

    plan = flash_block_plan(*shape)
    assert plan == BlockPlan(*expected)
    Sq, Sk, block_q, block_k, causal = shape
    bq = min(block_q, -(-Sq // 8) * 8)
    bk = min(block_k, -(-Sk // 8) * 8)
    assert tuple(plan) == _plan_by_mask(Sq, Sk, bq, bk, causal)


def _dense_attention(q, k, v, causal):
    """Output and log-sum-exp by straight-line math; causal is top-left
    (key <= query position), as the kernels have it for Sq != Sk."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v)
    return o, lse


_CLASS_SHAPES = [
    # Sq, Sk, block_q, block_k, causal
    pytest.param(128, 128, 32, 64, True, id="32x64-causal"),
    pytest.param(128, 128, 64, 32, True, id="64x32-causal"),
    pytest.param(128, 128, 32, 64, False, id="32x64-full"),
    pytest.param(128, 128, 64, 32, False, id="64x32-full"),
    pytest.param(88, 88, 32, 64, True, id="32x64-padded-causal"),
    pytest.param(88, 88, 64, 32, True, id="64x32-padded-causal"),
    pytest.param(88, 88, 32, 64, False, id="32x64-padded-full"),
    pytest.param(48, 96, 32, 64, True, id="sq-lt-sk-causal"),
    pytest.param(96, 48, 64, 32, True, id="sq-gt-sk-causal"),
    pytest.param(48, 96, 32, 64, False, id="sq-lt-sk-full"),
]


def _rand_qk_v(Sq, Sk, B=1, H=2, D=16, seed=0):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, H, Sq, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, Sk, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, Sk, D))
    return q, k, v


@pytest.mark.parametrize("Sq,Sk,block_q,block_k,causal", _CLASS_SHAPES)
def test_block_classes_match_dense(Sq, Sk, block_q, block_k, causal):
    """Forward, log-sum-exp and all three gradients against the dense
    oracle, at unequal blocks both ways, padded, and Sq != Sk: every
    class of step (skip, full, masked) takes part somewhere."""
    q, k, v = _rand_qk_v(Sq, Sk)
    key = jax.random.PRNGKey(7)
    w_o = jax.random.normal(key, q.shape)
    w_l = jax.random.normal(jax.random.fold_in(key, 1), q.shape[:3])

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, return_lse=True)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return (o * w_o).sum() + (lse * w_l).sum()
        return f

    o, lse = flash(q, k, v)
    o_ref, lse_ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: _dense_attention(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=f"d{name}")


def test_grid_step_counter_after_one_traced_call():
    """Tracing one call adds its grid, rows x heads x the plan, to the
    registry's count of steps by kernel and class: the forward's when the
    forward is traced, all three kernels' when its gradient is."""
    from dlrover_tpu.observability.registry import get_registry
    from dlrover_tpu.ops.flash_attention import BlockPlan, flash_block_plan

    kernels = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

    def read():
        steps = get_registry().counter(
            "dlrover_flash_grid_steps_total", labelnames=("kernel", "block"))
        return {(kernel, block): steps.labels(kernel=kernel,
                                              block=block).value
                for kernel in kernels for block in BlockPlan._fields}

    B, H, S = 2, 3, 128
    q, k, v = _rand_qkv(B=B, H=H, S=S, D=16)

    def attend(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=64)

    plan = flash_block_plan(S, S, 32, 64, True)
    assert plan == BlockPlan(skip=2, full=2, masked=4)
    before = read()
    jax.jit(attend).lower(q, k, v)
    after = read()
    for (kernel, block), n in after.items():
        want = B * H * getattr(plan, block) if kernel == "flash_fwd" else 0
        assert n - before[(kernel, block)] == want, (kernel, block)
    jax.jit(jax.grad(lambda q, k, v: attend(q, k, v).sum(),
                     argnums=(0, 1, 2))).lower(q, k, v)
    again = read()
    for key, n in again.items():
        assert n - after[key] == B * H * getattr(plan, key[1]), key


def _masked_everywhere_fwd(q, k, v, *, causal, block_q, block_k):
    """The forward as it was before the block classes, kept as the
    reference: every computed step builds the mask from two iotas and
    applies it with two ``where``s, and skipped steps still fetch their
    K/V blocks (index maps unclamped)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    neg_inf, lanes = -1e30, 128
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(block_q, -(-Sq // 8) * 8)
    bk = min(block_k, -(-Sk // 8) * 8)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    scale = D ** -0.5

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        iq, ik = pl.program_id(2), pl.program_id(3)

        @pl.when(ik == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, neg_inf)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

        def _attend():
            s = jax.lax.dot_general(
                q_ref[0, 0].astype(jnp.float32),
                k_ref[0, 0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            mask = cols < Sk
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            s = jnp.where(mask, s, neg_inf)
            m_prev = m_scr[:]
            l_prev = l_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[:] = m_new
            acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        if causal:
            pl.when(ik * bk <= iq * bq + bq - 1)(_attend)
        else:
            _attend()

        @pl.when(ik == nk - 1)
        def _finish():
            l = l_scr[:]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0] = (acc_scr[:] / safe_l[:, :1]).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.where(
                l == 0.0, neg_inf, m_scr[:] + jnp.log(safe_l))

    def pad(x, n):
        return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))

    def spec(rows, width, index_map):
        return pl.BlockSpec((1, 1, rows, width), index_map,
                            memory_space=pltpu.VMEM)

    by_q = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    by_k = lambda b, h, i, j: (b, h, j, 0)  # noqa: E731
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[spec(bq, D, by_q), spec(bk, D, by_k), spec(bk, D, by_k)],
        out_specs=[spec(bq, D, by_q), spec(bq, lanes, by_q)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, nq * bq, lanes), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, lanes), jnp.float32),
            pltpu.VMEM((bq, lanes), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=True,
    )(pad(q, nq * bq), pad(k, nk * bk), pad(v, nk * bk))
    return o[:, :, :Sq], lse[:, :, :Sq, 0]


@pytest.mark.parametrize("Sq,Sk,block_q,block_k,causal,dtype", [
    pytest.param(256, 256, 32, 64, True, jnp.bfloat16, id="causal-bf16"),
    pytest.param(88, 88, 64, 32, True, jnp.float32, id="padded-causal"),
    pytest.param(48, 96, 32, 64, False, jnp.float32, id="sq-lt-sk-full"),
])
def test_block_classes_are_bit_exact(Sq, Sk, block_q, block_k, causal,
                                     dtype):
    """Dropping an all-true mask and clamping the skip steps' index maps
    change what is fetched and built, not what is computed: ``o`` and
    ``lse`` equal the masked-everywhere forward's bit for bit."""
    q, k, v = (x.astype(dtype) for x in _rand_qk_v(Sq, Sk, B=2, H=2))
    o, lse = flash_attention(q, k, v, causal=causal, block_q=block_q,
                             block_k=block_k, return_lse=True,
                             interpret=True)
    o_ref, lse_ref = _masked_everywhere_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_ref))


@pytest.mark.parametrize("Sq,Sk,block_q,block_k,causal", [
    pytest.param(4096, 4096, 512, 1024, True, id="cells"),
    *_CLASS_SHAPES,
    pytest.param(32, 64, 16, 16, True, id="sq-lt-sk-whole-columns"),
])
def test_skip_steps_fetch_nothing(Sq, Sk, block_q, block_k, causal):
    """Walk each grid in its order over two (row, head) sweeps and count
    the steps at which an input's block index changes, i.e. the DMAs the
    pipeline issues: with the skip steps in, exactly as many as the live
    steps alone ask for, for every input of all three kernels. And each
    such DMA inside a sweep is issued under a live step (the pipeline
    starts step t's copies at the start of step t - 1), never under a
    skip step with no body to hide it."""
    import importlib

    # the package's ``flash_attention`` is the function; the module by name
    fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
    blocks = fa._blocks(Sq, Sk, block_q, block_k, causal)
    nq, nk = blocks.nq, blocks.nk

    def fetches(steps, index):
        seen = [(sweep, int(index(iq, ik))) for sweep, iq, ik in steps]
        return sum(a != b for a, b in zip(seen, seen[1:])) + 1

    def live(steps):
        return [s for s in steps if blocks.step(s[1], s[2])[0]]

    def exposed(steps, index):
        seen = [(sweep, int(index(iq, ik))) for sweep, iq, ik in steps]
        return [steps[t] for t in range(1, len(steps))
                if seen[t] != seen[t - 1] and seen[t][0] == seen[t - 1][0]
                and not blocks.step(*steps[t - 1][1:])[0]]

    rows = [(s, iq, ik) for s in range(2) for iq in range(nq)
            for ik in range(nk)]
    columns = [(s, iq, ik) for s in range(2) for ik in range(nk)
               for iq in range(nq)]
    for side in (0, 1):  # the queries' blocks, the keys' blocks
        index = lambda iq, ik: blocks.row_step(iq, ik)[side]  # noqa: E731
        assert fetches(rows, index) \
            == fetches(live(rows), lambda iq, ik: (iq, ik)[side]), side
        assert not exposed(rows, index), side
    index = lambda iq, ik: blocks.column_q(ik, iq)  # noqa: E731
    assert fetches(columns, index) \
        == fetches(live(columns), lambda iq, ik: iq)
    assert not exposed(columns, index)
    if blocks.plan().skip:
        # an unclamped map, as the kernels had, fetches on skip steps
        assert fetches(rows, lambda iq, ik: ik) \
            > fetches(live(rows), lambda iq, ik: ik)


# -- a value width of its own (latent attention: 192-wide queries and keys
# over 128-wide values, scaled down here to 48 over 32) ----------------------

_VALUE_WIDTH_SHAPES = [
    # Sq, Sk, block_q, block_k, causal
    pytest.param(128, 128, 32, 64, True, id="32x64-causal"),
    pytest.param(128, 128, 64, 32, False, id="64x32-full"),
    pytest.param(88, 88, 32, 64, True, id="32x64-padded-causal"),
    pytest.param(48, 96, 32, 64, True, id="sq-lt-sk-causal"),
]


@pytest.mark.parametrize("Sq,Sk,block_q,block_k,causal", _VALUE_WIDTH_SHAPES)
def test_values_of_another_width_match_dense(Sq, Sk, block_q, block_k,
                                              causal):
    """Values narrower than queries and keys (3 : 2, as 192 : 128):
    output, log-sum-exp and all three gradients against the dense oracle,
    at the default scale of the query/key width; the output and the value
    gradient are as wide as the values."""
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (1, 2, Sq, 48))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, Sk, 48))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, Sk, 32))
    w_o = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, Sq, 32))
    w_l = jax.random.normal(jax.random.fold_in(key, 4), (1, 2, Sq))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, return_lse=True)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return (o * w_o).sum() + (lse * w_l).sum()
        return f

    o, lse = flash(q, k, v)
    o_ref, lse_ref = _dense_attention(q, k, v, causal)
    assert o.shape == (1, 2, Sq, 32)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: _dense_attention(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        assert a.shape == {"q": q, "k": k, "v": v}[name].shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=f"d{name}")


# sha256 of the jaxpr (kernels' bodies, grids, block and scratch shapes,
# index maps; no source location) of the kernels' forward and backward at
# the cells' shapes, as they stood before the value width was made a
# width of its own. Where the widths are equal the kernels must be those,
# call for call: a deliberate change of the kernels changes these too
_EQUAL_WIDTH_JAXPR = {
    32: "7ea7de6785db7196e76b24888f59be011ed557f519235d494eff2a04c0a52319",
    16: "181edd2b3ba5b07f17ad8bcf16afdc1517aaa1861bae745e4cb2c870ef22c3a6",
}


@pytest.mark.parametrize("heads", sorted(_EQUAL_WIDTH_JAXPR))
def test_equal_widths_trace_the_kernels_as_before(heads):
    """At one width for q, k and v (the dense and looped cells' attention:
    [1, heads, 4096, 128], the default blocks) the forward and backward
    kernels trace to the very jaxpr they traced to before they took a
    value width of their own."""
    import hashlib

    shape = jax.ShapeDtypeStruct((1, heads, 4096, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2)))(shape, shape, shape))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        _EQUAL_WIDTH_JAXPR[heads])
