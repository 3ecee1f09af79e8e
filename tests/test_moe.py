"""MoE model + expert-parallel tests on the virtual 8-device mesh."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama, moe
from dlrover_tpu.parallel import sharding
from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
from dlrover_tpu.parallel.sharding import shard_tree


def _tiny(dtype=jnp.float32, **kw):
    base = moe.MoEConfig.tiny().__dict__
    base.update(dtype=dtype, **kw)
    return moe.MoEConfig(**base)


def _old_route(x_grouped, router, config, capacity):
    """The one-hot dispatch/combine routing ``models/moe.py`` had until
    PR 30, kept as the reference the sorted path is held to."""
    c = config
    G, g = x_grouped.shape[0], x_grouped.shape[1]
    E, k = c.n_experts, c.top_k
    logits = jnp.einsum(
        "gtd,de->gte", x_grouped.astype(jnp.float32), router
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)
    masks = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    cm = masks.transpose(0, 2, 1, 3)
    positions = (
        jnp.cumsum(cm.reshape(G, k * g, E), axis=1).reshape(G, k, g, E) - 1.0
    )
    keep = (positions < capacity) * cm
    pos_in_expert = (positions * cm).sum(-1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)
    oh = keep[..., None] * slot[:, :, :, None, :]         # (G, k, g, E, C)
    dispatch = oh.sum(1)
    gates_km = gates.transpose(0, 2, 1)
    combine = (oh * gates_km[..., None, None]).sum(1)
    frac = masks.mean(axis=(1, 2))
    aux = E * jnp.mean(jnp.sum(frac * probs.mean(axis=1), axis=-1))
    return dispatch, combine, aux


def _old_moe_ffn(x, layer, config):
    """The three einsums over (group, expert, slot) of before PR 30."""
    c = config
    B, S, D = x.shape
    capacity = moe.expert_capacity(c, B, S)
    g = moe._group_size(c, B, S)
    x_grouped = x.reshape(B * S // g, g, D)
    dispatch, combine, aux = _old_route(
        x_grouped, layer["router"], c, capacity)
    expert_in = jnp.einsum(
        "gtec,gtd->gecd", dispatch.astype(x.dtype), x_grouped
    )
    gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, layer["w1"]))
    up = jnp.einsum("gecd,edf->gecf", expert_in, layer["w3"])
    expert_out = jnp.einsum("gecf,efd->gecd", gate * up, layer["w2"])
    out = jnp.einsum(
        "gtec,gecd->gtd", combine.astype(x.dtype), expert_out
    )
    return out.reshape(B, S, D), aux


def _layer(c, key):
    """One layer's router and expert leaves, float32."""
    ks = jax.random.split(key, 4)
    E, D, F = c.n_experts, c.dim, c.ffn_dim
    return {
        "router": jax.random.normal(ks[0], (D, E)) * D ** -0.5,
        "w1": jax.random.normal(ks[1], (E, D, F)) * D ** -0.5,
        "w3": jax.random.normal(ks[2], (E, D, F)) * D ** -0.5,
        "w2": jax.random.normal(ks[3], (E, F, D)) * F ** -0.5,
    }


def _routed(c, G, g, router=None):
    """The pairs of G groups of g tokens: expert, gates, keep (each
    (G, g, k)), aux, and the capacity they were held to."""
    x = jax.random.normal(jax.random.PRNGKey(0), (G, g, c.dim))
    if router is None:
        router = jax.random.normal(
            jax.random.PRNGKey(1), (c.dim, c.n_experts))
    cap = moe.expert_capacity(c, G, g)
    return moe._route(x, router, c, cap) + (cap,)


class TestRouting:
    def test_kept_pairs_and_gate_mass(self):
        c = _tiny()
        G, g = 2, 32
        expert, gates, keep, aux, cap = _routed(c, G, g)
        assert expert.shape == gates.shape == keep.shape == (G, g, c.top_k)
        assert keep.dtype == jnp.bool_
        # each token is kept at most top_k times, by distinct experts
        assert int(keep.sum(-1).max()) <= c.top_k
        assert bool((expert[..., 0] != expert[..., 1]).all())
        # each expert of a group keeps at most capacity pairs
        per_expert = (
            jax.nn.one_hot(expert, c.n_experts) * keep[..., None]
        ).sum(axis=(1, 2))
        assert float(per_expert.max()) <= cap
        # the gates of a fully kept token sum to 1
        full = np.asarray(keep.all(-1))
        np.testing.assert_allclose(
            np.asarray(gates.sum(-1))[full], 1.0, atol=1e-5
        )
        assert float(aux) > 0.0

    def test_capacity_drops_overflow(self):
        c = _tiny(capacity_factor=0.25)
        g = 64
        router = jnp.zeros((c.dim, c.n_experts))  # uniform: argmax ties
        expert, _, keep, _, cap = _routed(c, 1, g, router)
        # ties send every pair to two experts: both fill, the rest drop
        assert int(keep.sum()) == 2 * cap < g * c.top_k
        order, group_sizes = moe._sort_by_expert(
            expert.reshape(-1), keep.reshape(-1), c.n_experts)
        assert int(group_sizes.sum()) == int(keep.sum())
        assert int(group_sizes.max()) <= cap
        # kept pairs come first, by expert; dropped pairs last
        kept_sorted = np.asarray(keep.reshape(-1)[order])
        assert kept_sorted[: 2 * cap].all() and not kept_sorted[2 * cap:].any()
        experts_sorted = np.asarray(expert.reshape(-1)[order])[: 2 * cap]
        assert (np.diff(experts_sorted) >= 0).all()

    def test_group_size_bounds_capacity(self):
        # capacity depends on the group size, not the total token count
        c = _tiny(route_group_size=32)
        assert moe.expert_capacity(c, 8, 128) == moe.expert_capacity(c, 1, 32)
        with pytest.raises(ValueError, match="divide"):
            moe.expert_capacity(c, 1, 33)

    def test_dead_rows_of_the_sorted_buffer_reach_nothing(self):
        # rows past group_sizes.sum() belong to no expert: whatever they
        # hold, the block's output is finite and zero there
        c = _tiny()
        layer = _layer(c, jax.random.PRNGKey(2))
        group_sizes = jnp.array([5, 0, 9, 3], jnp.int32)
        rows = jax.random.normal(jax.random.PRNGKey(3), (40, c.dim))
        poisoned = rows.at[17:].set(jnp.nan)
        out = moe._expert_ffn(
            poisoned, group_sizes, layer["w1"], layer["w3"], layer["w2"])
        assert bool(jnp.isfinite(out).all())
        assert float(jnp.abs(out[17:]).max()) == 0.0
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(moe._expert_ffn(
                rows, group_sizes, layer["w1"], layer["w3"], layer["w2"])),
        )
        # and each live row went through its own expert's weights
        h = jax.nn.silu(rows[5:14] @ layer["w1"][2]) * (
            rows[5:14] @ layer["w3"][2])
        np.testing.assert_allclose(
            np.asarray(out[5:14]), np.asarray(h @ layer["w2"][2]),
            atol=1e-5, rtol=1e-5,
        )


T_LIVE, K_LIVE, E_LIVE, CHUNK = 32, 3, 4, 8     # T·k = 96 pairs, 12 chunks


def _pairs(n_live, c, seed=0):
    """x (1, 1, T, D) and the pairs of T tokens, k each, of which exactly
    ``n_live`` are kept by one of E experts, and their f32 gates."""
    rng = np.random.default_rng(seed)
    M = T_LIVE * K_LIVE
    kept = np.zeros(M, bool)
    kept[rng.permutation(M)[:n_live]] = True
    expert = rng.integers(0, E_LIVE, (1, T_LIVE, K_LIVE))
    x = rng.standard_normal((1, 1, T_LIVE, c.dim))
    gates = rng.uniform(0.1, 1.0, (1, T_LIVE, K_LIVE))
    return (jnp.asarray(x, jnp.float32), jnp.asarray(expert, jnp.int32),
            jnp.asarray(kept.reshape(1, T_LIVE, K_LIVE)),
            jnp.asarray(gates, jnp.float32))


def _experts_value_and_grad(c, expert, keep, live_only, probe):
    """The routed rows' output and its gradients with respect to x, the
    gates and the three expert leaves, through one path."""
    def f(x, gates, w1, w3, w2):
        y = moe._routed_rows(x, expert, keep, gates, w1, w3, w2,
                             live_only=live_only)
        return (y * probe).sum(), y
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))


class TestLiveRows:
    """A chip that holds a share of the router's experts moves only the
    live rows into the sorted buffer and out of it; the result is the
    whole-buffer path's, in the output and every gradient."""

    @pytest.mark.parametrize("n_live", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                                        T_LIVE * K_LIVE])
    def test_the_live_path_equals_the_whole_buffer(self, n_live,
                                                   monkeypatch):
        monkeypatch.setattr(moe, "_CHUNK", CHUNK)
        c = _tiny(n_experts=E_LIVE, top_k=K_LIVE)
        x, expert, keep, gates = _pairs(n_live, c)
        layer = _layer(c, jax.random.PRNGKey(7))
        leaves = (layer["w1"], layer["w3"], layer["w2"])
        probe = jax.random.normal(jax.random.PRNGKey(8), x.shape)
        (_, whole), d_whole = _experts_value_and_grad(
            c, expert, keep, False, probe)(x, gates, *leaves)
        (_, live), d_live = _experts_value_and_grad(
            c, expert, keep, True, probe)(x, gates, *leaves)
        tol = dict(atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(live), np.asarray(whole),
                                   **tol)
        for name, a, b in zip(("x", "gates", "w1", "w3", "w2"), d_live,
                              d_whole):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       err_msg=name, **tol)
        # a dead pair's gate has no gradient
        d_gates = np.asarray(d_live[1])
        assert (d_gates[~np.asarray(keep)] == 0).all()
        if n_live == 0:
            assert float(jnp.abs(live).max()) == 0.0

    @pytest.mark.parametrize("live_only", [False, True],
                             ids=["whole", "live"])
    def test_a_dead_row_reads_zero_into_and_out_of_the_experts(
            self, live_only, monkeypatch):
        """Tokens whose every pair is dead hold NaN, and the grouped
        matmuls leave NaN in every dead row they return, forward and
        backward, as the chip's kernels leave those rows unwritten: the
        dead rows go in as zeros (else every output is NaN), those tokens
        get zero, and no gradient holds a NaN."""
        monkeypatch.setattr(moe, "_CHUNK", CHUNK)
        c = _tiny(n_experts=E_LIVE, top_k=K_LIVE)
        x, expert, keep, gates = _pairs(CHUNK * 5 + 3, c, seed=1)
        dead = ~np.asarray(keep).any(-1)[0]                 # (T,)
        assert 0 < dead.sum() < T_LIVE
        x = x.at[0, 0, dead].set(jnp.nan)
        real = moe._grouped_ffn

        def live_rows(a, n):
            return (jnp.arange(a.shape[0]) < n)[:, None]

        def fwd(rows, group_sizes, w1, w3, w2):
            # a dead row that goes in other than zero spoils every output
            n = group_sizes.sum()
            clean = jnp.where(live_rows(rows, n), True, rows == 0).all()
            out = real(rows, group_sizes, w1, w3, w2)
            out = jnp.where(clean & live_rows(out, n), out, jnp.nan)
            return out, (rows, group_sizes, w1, w3, w2)

        def bwd(residuals, g):
            rows, group_sizes, *w = residuals
            _, vjp = jax.vjp(
                lambda r, *w: real(r, group_sizes, *w), rows, *w)
            d_rows, *d_w = vjp(g)
            n = group_sizes.sum()
            return (jnp.where(live_rows(d_rows, n), d_rows, jnp.nan), None,
                    *d_w)

        unwritten = jax.custom_vjp(lambda *a: fwd(*a)[0])
        unwritten.defvjp(fwd, bwd)
        monkeypatch.setattr(moe, "_grouped_ffn", unwritten)
        layer = _layer(c, jax.random.PRNGKey(7))
        probe = jax.random.normal(jax.random.PRNGKey(8), x.shape)
        (_, y), grads = _experts_value_and_grad(
            c, expert, keep, live_only, probe)(
                x, gates, layer["w1"], layer["w3"], layer["w2"])
        assert bool(jnp.isfinite(y).all())
        assert float(jnp.abs(y[0, 0, dead]).max()) == 0.0
        assert float(jnp.abs(y[0, 0, ~dead]).min(-1).max()) > 0.0
        d_x, d_gates, *d_w = grads
        assert bool(jnp.isfinite(d_x).all())
        assert float(jnp.abs(d_x[0, 0, dead]).max()) == 0.0
        assert bool(jnp.isfinite(d_gates).all())
        for name, g in zip(("w1", "w3", "w2"), d_w):
            assert bool(jnp.isfinite(g).all()), name

    @pytest.mark.parametrize("axes", [{"ep": 4}, {"ep": 2, "tp": 2}],
                             ids=["ep4xfsdp2", "ep2xtp2xfsdp2"])
    def test_the_live_path_under_a_mesh_equals_the_meshless(self, axes):
        """A share of 4 experts of a 16-wide router, each chip of the
        expert group at its columns: output and every gradient as on one
        device."""
        c = _tiny(router_experts=16, first_expert=4, capacity_factor=8.0)
        layer = _layer(dataclasses.replace(c, n_experts=16),
                       jax.random.PRNGKey(5))
        layer.update({k: layer[k][4:8] for k in ("w1", "w3", "w2")})
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 32, c.dim))

        def value_and_grad(x, layer, mesh):
            return jax.jit(jax.value_and_grad(
                lambda x, layer: moe._moe_ffn(x, layer, c, mesh)[0].sum(),
                argnums=(0, 1)))(x, layer)

        want = value_and_grad(x, layer, None)
        mesh = build_mesh(plan_mesh(8, **axes))
        logical = moe.param_logical_axes(c)["layers"]
        layer = shard_tree(mesh, layer, {k: logical[k][1:] for k in LEAVES})
        x = jax.device_put(
            x, NamedSharding(mesh, P(("dp", "fsdp"), None, None)))
        got = value_and_grad(x, layer, mesh)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_the_counter_names_the_path_each_layer_takes(self):
        from dlrover_tpu.observability.registry import get_registry

        def read():
            calls = get_registry().counter(
                "dlrover_moe_permute_calls_total", labelnames=("path",))
            return {p: calls.labels(path=p).value for p in ("live", "whole")}

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
        for config, path in (
                (_tiny(), "whole"),
                (_tiny(router_experts=16, first_expert=4), "live")):
            layer = _layer(dataclasses.replace(
                config, n_experts=config.router_width), jax.random.PRNGKey(1))
            layer.update({k: layer[k][:config.n_experts]
                          for k in ("w1", "w3", "w2")})
            before = read()
            jax.jit(lambda x, layer: moe._ffn(x, layer, config)[0]).lower(
                x, layer)
            after = read()
            assert {p: after[p] - before[p] for p in after} == {
                p: float(p == path) for p in after}


LEAVES = ("router", "w1", "w3", "w2")


@pytest.mark.parametrize("axes", [None, {"ep": 4}, {"ep": 2, "tp": 2}],
                         ids=["unsharded", "ep4xfsdp2", "ep2xtp2xfsdp2"])
@pytest.mark.parametrize("group", [None, 16], ids=["seq-groups", "groups16"])
@pytest.mark.parametrize("capacity_factor", [0.25, 1.25, 2.0],
                         ids=["cf0.25", "cf1.25", "dropless"])
def test_sorted_experts_equal_the_one_hot_dispatch(
        capacity_factor, group, axes):
    """The grouped matmul over sorted pairs computes what the einsums over
    (group, expert, slot) computed: output, aux and every gradient, with
    pairs dropped (0.25, 1.25) and with none (n_experts / top_k)."""
    c = _tiny(capacity_factor=capacity_factor, route_group_size=group)
    dropless = capacity_factor == c.n_experts / c.top_k
    B, S = 4, 32
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, c.dim))
    layer = _layer(c, jax.random.PRNGKey(5))
    probe = jax.random.normal(jax.random.PRNGKey(6), (B, S, c.dim))
    mesh = build_mesh(plan_mesh(8, **axes)) if axes else None

    def scalar(ffn):
        def f(x, layer):
            out, aux = ffn(x, layer)
            return (out * probe).sum() + aux, (out, aux)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    (_, (ref_out, ref_aux)), (ref_dx, ref_dl) = scalar(
        lambda x, layer: _old_moe_ffn(x, layer, c))(x, layer)
    if mesh is not None:
        logical = moe.param_logical_axes(c)["layers"]
        layer = shard_tree(
            mesh, layer, {k: logical[k][1:] for k in LEAVES})
        x = jax.device_put(
            x, NamedSharding(mesh, P(("dp", "fsdp"), None, None)))
    (_, (out, aux)), (dx, dl) = scalar(
        lambda x, layer: moe._moe_ffn(x, layer, c, mesh))(x, layer)

    dropped = int((~_routed_keep(c, x, layer)).sum())
    assert (dropped == 0) == dropless
    tol = dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), **tol)
    np.testing.assert_allclose(float(aux), float(ref_aux), **tol)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx), **tol)
    for name in LEAVES:
        np.testing.assert_allclose(
            np.asarray(dl[name]), np.asarray(ref_dl[name]), err_msg=name,
            **tol)


def _routed_keep(c, x, layer):
    B, S, D = x.shape
    g = moe._group_size(c, B, S)
    return moe._route(
        np.asarray(x).reshape(B * S // g, g, D), np.asarray(layer["router"]),
        c, moe.expert_capacity(c, B, S))[2]


class TestMoEModel:
    def test_forward_and_loss_finite(self):
        c = _tiny()
        params = moe.init_params(c, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 33), 0, c.vocab_size
        )
        logits, aux = moe.forward(params, tokens[:, :-1], c)
        assert logits.shape == (2, 32, c.vocab_size)
        loss = moe.next_token_loss(params, tokens, c)
        assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(aux))

    def test_the_loss_is_traced_once_a_process(self, monkeypatch):
        # next_token_loss is jitted: a second program that holds it (the
        # train step after a check of the gradient) reuses the first's
        # trace and its differentiation
        calls = []
        real = moe._hidden_states
        monkeypatch.setattr(
            moe, "_hidden_states",
            lambda *a, **k: calls.append(1) or real(*a, **k))
        c = _tiny(vocab_size=257)         # a signature no other test has
        params = moe.init_params(c, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 17), 0, c.vocab_size)
        first = jax.jit(jax.grad(
            lambda p: moe.next_token_loss(p, tokens, c)))(params)
        second = jax.jit(
            lambda p: 2.0 * moe.next_token_loss(p, tokens, c))(params)
        assert len(calls) == 1
        assert bool(jnp.isfinite(second))
        assert all(bool(jnp.isfinite(g).all())
                   for g in jax.tree.leaves(first))

    def test_num_params_mixtral_scale(self):
        total, active = moe.num_params(moe.MoEConfig.mixtral8x7b())
        assert 45e9 < total < 48e9
        assert 12e9 < active < 14e9

    def test_train_step_learns(self):
        c = _tiny()
        params = moe.init_params(c, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 17), 0, c.vocab_size
        )
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)
        step = jax.jit(
            lambda p, s, t: _update(p, s, t, c, opt)
        )
        l0 = None
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens)
            l0 = l0 if l0 is not None else float(loss)
        assert float(loss) < l0


def _update(params, opt_state, tokens, c, opt):
    loss, grads = jax.value_and_grad(moe.next_token_loss)(params, tokens, c)
    updates, opt_state = opt.update(grads, opt_state)
    return optax.apply_updates(params, updates), opt_state, loss


class TestExpertParallel:
    def test_ep_sharded_matches_unsharded(self):
        c = _tiny()
        mesh = build_mesh(plan_mesh(8, ep=4))  # ep=4, fsdp=2
        params = moe.init_params(c, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 32), 0, c.vocab_size
        )
        ref, _ = moe.forward(params, tokens, c)
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        tok_s = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None))
        )
        out, _ = jax.jit(lambda p, t: moe.forward(p, t, c, mesh))(
            sharded, tok_s
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3
        )

    def test_ep_with_sp_ring(self):
        c = _tiny(use_ring_attention=True)
        mesh = build_mesh(plan_mesh(8, ep=2, sp=2))
        params = moe.init_params(c, jax.random.PRNGKey(0))
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, c.vocab_size),
            NamedSharding(mesh, P(("dp", "fsdp"), None)),
        )
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: moe.next_token_loss(p, t, c, mesh)
        ))(sharded, tokens)
        assert bool(jnp.isfinite(loss))
        assert all(
            bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads)
        )


MESHES = [{"ep": 4}, {"ep": 2, "tp": 2}]
MESH_IDS = ["ep4xfsdp2", "ep2xtp2xfsdp2"]


class TestSlicedExperts:
    """Every chip of an ``ep`` group holds every expert at a slice of its
    columns (PR 33), where until then it held whole experts."""

    @pytest.mark.parametrize("axes", MESHES, ids=MESH_IDS)
    @pytest.mark.parametrize("leaf", ["w1", "w3", "w2"])
    def test_a_device_holds_every_expert_at_its_columns(self, leaf, axes):
        c = _tiny()
        mesh = build_mesh(plan_mesh(8, **axes))
        params = moe.init_params(c, jax.random.PRNGKey(0))
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        whole = params["layers"][leaf]
        slices, fsdp = mesh.shape["ep"] * mesh.shape["tp"], mesh.shape["fsdp"]
        columns, embed = c.ffn_dim // slices, c.dim // fsdp
        local = ((c.n_layers, c.n_experts, columns, embed) if leaf == "w2"
                 else (c.n_layers, c.n_experts, embed, columns))
        # what a device held with whole experts on chips: n_experts / ep
        # of them, at ffn_dim / tp columns
        placed = (c.n_layers * (c.n_experts // mesh.shape["ep"]) * embed
                  * (c.ffn_dim // mesh.shape["tp"]) * whole.dtype.itemsize)
        shards = sharded["layers"][leaf].addressable_shards
        assert len(shards) == 8
        for shard in shards:
            assert shard.data.shape == local, shard.device
            assert shard.data.nbytes == placed
        # and the slices are distinct columns: together the whole leaf
        assert len({tuple((i.start, i.stop) for i in s.index)
                    for s in shards}) == slices * fsdp
        np.testing.assert_array_equal(
            np.asarray(sharded["layers"][leaf]), np.asarray(whole))

    def test_a_collapsed_routing_costs_every_chip_the_same(self, monkeypatch):
        """The router rigged so that every token chooses the same two
        experts (all logits equal: ``top_k`` takes the first two), which
        with whole experts on chips left two chips of four idle: loss and
        every gradient leaf equal the unsharded ones, and every device
        computes the same ``group_sizes``."""
        c = _tiny(capacity_factor=2.0)       # dropless: nothing is masked
        mesh = build_mesh(plan_mesh(8, ep=4))
        params = moe.init_params(c, jax.random.PRNGKey(0))
        params["layers"]["router"] = jnp.zeros_like(
            params["layers"]["router"])
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 33), 0, c.vocab_size)
        seen = []
        real = moe._expert_ffn

        def watched(rows, group_sizes, *weights):
            jax.debug.callback(
                lambda g: seen.append(tuple(int(n) for n in g)), group_sizes)
            return real(rows, group_sizes, *weights)

        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, t: moe.next_token_loss(p, t, c)))(params, tokens)
        monkeypatch.setattr(moe, "_expert_ffn", watched)
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        tok_s = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: moe.next_token_loss(p, t, c, mesh)))(sharded, tok_s)
        jax.effects_barrier()
        tol = dict(atol=2e-3, rtol=2e-3)    # test_ep_sharded_matches_unsharded
        np.testing.assert_allclose(float(loss), float(ref_loss), **tol)
        for (path, g), ref in zip(
                jax.tree_util.tree_leaves_with_path(grads),
                jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(ref),
                err_msg=jax.tree_util.keystr(path), **tol)
        # a layer's call on each of 8 devices, one sequence of 32 tokens
        # a data replica: both choices of every token on experts 0 and 1
        assert len(seen) >= 8 * c.n_layers
        assert set(seen) == {(32, 32, 0, 0)}

    @pytest.mark.parametrize("axes", MESHES, ids=MESH_IDS)
    def test_columns_that_the_chips_do_not_divide_are_refused(self, axes):
        c = _tiny(ffn_dim=90)               # 90 / 4 is no whole number
        mesh = build_mesh(plan_mesh(8, **axes))
        layer = _layer(c, jax.random.PRNGKey(5))
        x = jnp.zeros((4, 32, c.dim))
        with pytest.raises(ValueError, match=r"ffn_dim 90 .*ep.*tp"):
            moe._moe_ffn(x, layer, c, mesh)
        # two chips divide it
        moe._moe_ffn(x, layer, c, build_mesh(plan_mesh(8, ep=2)))

    @pytest.mark.parametrize("k,n", [
        (4096, 3584),     # w1, w3 of Mixtral-8x7B over ep 4; tgmm
        (3584, 4096),     # w2, where the sliced width is contracted
        (4096, 14336),    # the unsliced widths, as decode on one chip
        (14336, 4096),
        (4096, 7168),     # over ep 2
        (7168, 4096),
        (64, 24),         # smaller than a tile: the whole dimension
    ])
    def test_the_tiles_divide_the_widths_of_the_grouped_matmuls(self, k, n):
        """A contraction tile that does not divide its width is masked on
        every visit of the kernel: the tiles are whole 128-lane registers
        that divide, as a function of the shapes alone."""
        tm, tk, tn = moe._tiles(k, n)
        most = moe._GMM_TILING
        assert tm == most[0]
        for tile, dim, cap in ((tk, k, most[1]), (tn, n, most[2])):
            assert dim % tile == 0 and tile <= cap
            assert tile == dim or tile % 128 == 0
            # and no wider tile under the cap divides
            assert not any(dim % t == 0
                           for t in range(tile + 128, min(cap, dim) + 1, 128))


# -- the output head, its vocabulary spread over ep and tp (PR 34) -----------

HEAD_MESHES = {"ep4xfsdp2": {"ep": 4}, "ep2xtp2xfsdp2": {"ep": 2, "tp": 2},
               "tp2xfsdp4": {"tp": 2}}
HEAD_MESH_IDS = sorted(HEAD_MESHES)
MODEL_LEAVES = ["tok_embed", "lm_head", "final_norm"] + [
    f"layers/{name}" for name in (
        "attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "router",
        "w1", "w3", "w2")]
_HEAD_TOKENS = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)


def _loss_and_grads(c, params, tokens, mesh=None):
    if mesh is not None:
        params = shard_tree(mesh, params, moe.param_logical_axes(c))
        tokens = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    return jax.jit(jax.value_and_grad(
        lambda p, t: moe.next_token_loss(p, t, c, mesh)))(params, tokens)


@functools.lru_cache(maxsize=None)
def _head_case(mesh_id):
    """(loss, gradients) of one seeded model without a mesh, and on the
    mesh of that id: computed once for all the leaves' cases."""
    c = _tiny(capacity_factor=2.0)       # dropless: nothing is masked
    params = moe.init_params(c, jax.random.PRNGKey(0))
    mesh = build_mesh(plan_mesh(8, **HEAD_MESHES[mesh_id]))
    return (_loss_and_grads(c, params, _HEAD_TOKENS),
            _loss_and_grads(c, params, _HEAD_TOKENS, mesh))


class TestVocabShardedHead:
    """``DEFAULT_RULES["vocab"]`` spreads the head's columns and the
    embedding's rows over ``ep`` and ``tp``; ``llama.head_nll`` takes the
    loss over the logits where they are."""

    @pytest.mark.parametrize("mesh_id", HEAD_MESH_IDS)
    def test_a_device_holds_its_share_of_the_vocabulary(self, mesh_id):
        c = _tiny()
        mesh = build_mesh(plan_mesh(8, **HEAD_MESHES[mesh_id]))
        sharded = shard_tree(
            mesh, moe.init_params(c, jax.random.PRNGKey(0)),
            moe.param_logical_axes(c))
        share = c.vocab_size // (mesh.shape["ep"] * mesh.shape["tp"])
        embed = c.dim // mesh.shape["fsdp"]
        for shard in sharded["lm_head"].addressable_shards:
            assert shard.data.shape == (embed, share)
        for shard in sharded["tok_embed"].addressable_shards:
            assert shard.data.shape == (share, embed)
        assert sharding.vocab_split(mesh, c.vocab_size)[1] \
            == c.vocab_size // share

    @pytest.mark.parametrize("mesh_id", HEAD_MESH_IDS)
    @pytest.mark.parametrize("leaf", ["loss"] + MODEL_LEAVES)
    def test_loss_and_every_gradient_leaf_equal_the_meshless(
            self, leaf, mesh_id):
        (ref_loss, ref_grads), (loss, grads) = _head_case(mesh_id)
        tol = dict(atol=2e-3, rtol=2e-3)    # test_ep_sharded_matches_unsharded
        if leaf == "loss":
            np.testing.assert_allclose(float(loss), float(ref_loss), **tol)
            return
        got, want = grads, ref_grads
        for key in leaf.split("/"):
            got, want = got[key], want[key]
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)

    @pytest.mark.parametrize("mesh_id", HEAD_MESH_IDS)
    @pytest.mark.parametrize("where", ["first-column", "last-column",
                                       "shard-edges", "random"])
    def test_the_targets_logit_comes_from_the_shard_that_holds_it(
            self, where, mesh_id):
        mesh = build_mesh(plan_mesh(8, **HEAD_MESHES[mesh_id]))
        B, S, D, V = 4, 16, 32, 64
        shards = sharding.vocab_split(mesh, V)[1]
        edges = jnp.arange(B * S).reshape(B, S) % shards * (V // shards)
        targets = {
            "first-column": jnp.zeros((B, S), jnp.int32),
            "last-column": jnp.full((B, S), V - 1, jnp.int32),
            # the first column of every shard, and the last of the one
            # before it
            "shard-edges": (edges - (jnp.arange(S) % 2)) % V,
            "random": jax.random.randint(
                jax.random.PRNGKey(2), (B, S), 0, V),
        }[where]
        x = jax.random.normal(jax.random.PRNGKey(3), (B, S, D), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(4), (D, V), jnp.float32)

        def nll(x, w, mesh):
            return llama.head_nll(x, w, targets, mesh)

        want = nll(x, w, None)
        got = jax.jit(lambda x, w: nll(x, w, mesh))(x, w)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        # and so does its gradient: one column of the head a token
        g_want = jax.grad(lambda w: nll(x, w, None).sum())(w)
        g_got = jax.jit(jax.grad(lambda w: nll(x, w, mesh).sum()))(w)
        np.testing.assert_allclose(
            np.asarray(g_got), np.asarray(g_want), atol=1e-4, rtol=1e-4)

    def test_a_vocabulary_the_chips_do_not_divide_stays_whole(
            self, monkeypatch):
        """258 / 4 is no whole number: the head and the embedding stay
        whole on every chip, the loss is the mesh-less one's, and the log
        says so once however often the loss is traced."""
        c = _tiny(vocab_size=258)
        mesh = build_mesh(plan_mesh(8, ep=4))
        params = moe.init_params(c, jax.random.PRNGKey(0))
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        for name in ("lm_head", "tok_embed"):
            for shard in sharded[name].addressable_shards:
                assert c.vocab_size in shard.data.shape
        from dlrover_tpu.common.log import logger

        lines = []
        monkeypatch.setattr(
            logger, "info", lambda msg, *a: lines.append(msg % a))
        assert sharding.vocab_split(mesh, c.vocab_size) == (None, 1)
        tokens = _HEAD_TOKENS[:2]
        ref_loss, ref_grads = _loss_and_grads(c, params, tokens)
        loss, grads = _loss_and_grads(c, params, tokens, mesh)
        moe.next_token_loss(sharded, tokens[:, :17], c, mesh)  # traced anew
        tol = dict(atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(float(loss), float(ref_loss), **tol)
        np.testing.assert_allclose(
            np.asarray(grads["lm_head"]), np.asarray(ref_grads["lm_head"]),
            **tol)
        said = [line for line in lines if "vocabulary 258" in line]
        assert len(said) == 1, lines
        assert "4 chips" in said[0] and "stay whole" in said[0]

    def test_the_step_says_how_many_chips_share_the_vocabulary(self):
        """Where the split is made: ``vocab_split`` of the step's mesh
        gives 4 chips on ``ep`` 4 and 1 on no mesh, and after two steps
        each chip's ``lm_head`` holds that share of the vocabulary's
        columns (the step keeps the layout it was given)."""
        from dlrover_tpu.trainer.elastic import (
            ElasticTrainer,
            make_train_state,
        )

        c = _tiny(vocab_size=264)         # a signature no other test has
        optimizer = optax.sgd(0.1)
        plan = plan_mesh(8, ep=4)
        for mesh, rows, want in ((build_mesh(plan), 2, 4), (None, 1, 1)):
            params = moe.init_params(c, jax.random.PRNGKey(0))
            if mesh is not None:
                params = shard_tree(
                    mesh, params, moe.param_logical_axes(c))
            trainer = ElasticTrainer(
                loss_fn=lambda p, t, mesh=mesh: moe.next_token_loss(
                    p, t, c, mesh),
                optimizer=optimizer, global_batch_size=2 * rows,
                micro_batch_per_replica=1)
            trainer.configure_for_world(
                plan if mesh is not None else plan_mesh(1))
            state = make_train_state(params, optimizer)
            batch = _HEAD_TOKENS[:2 * rows, :17].reshape(2, rows, 17)
            for _ in range(2):
                state, result = trainer.train_step(state, batch)
            assert bool(jnp.isfinite(result.loss))
            assert sharding.vocab_split(mesh, c.vocab_size)[1] == want
            for shard in state["params"]["lm_head"].addressable_shards:
                assert shard.data.shape[1] == c.vocab_size // want


# -- attention's heads over ep and tp ------------------------------------------

# Mixtral's 32 query and 8 key/value heads at a tiny width (head_dim 8)
ATTN = dict(dim=256, n_heads=32, n_kv_heads=8, capacity_factor=2.0)
ATTN_LEAVES = ("wq", "wk", "wv", "wo")


def _attn_tiny(**kw):
    return _tiny(**{**ATTN, **kw})


@functools.lru_cache(maxsize=None)
def _heads_case(mesh_id, flash):
    """(loss, gradients) of the 32-head model without a mesh (dense
    attention) and on the mesh of that id, through the flash kernel's
    ``shard_map`` (interpret mode here) or GSPMD's dense attention."""
    c = _attn_tiny()
    params = moe.init_params(c, jax.random.PRNGKey(0))
    mesh = build_mesh(plan_mesh(8, **HEAD_MESHES[mesh_id]))
    return (_loss_and_grads(c, params, _HEAD_TOKENS),
            _loss_and_grads(_attn_tiny(use_flash_attention=flash), params,
                            _HEAD_TOKENS, mesh))


def _step(loss_fn, params, mesh, rows, seq=17):
    """``ElasticTrainer``'s step and its arguments: two microbatches of
    ``rows`` rows of ``seq`` tokens a data replica."""
    from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

    optimizer = optax.adamw(3e-4)
    dp = mesh.shape["dp"] * mesh.shape["fsdp"] * mesh.shape["dcn"]
    trainer = ElasticTrainer(
        loss_fn=loss_fn, optimizer=optimizer,
        global_batch_size=2 * rows * dp, micro_batch_per_replica=rows)
    trainer.configure_for_world(plan_mesh(
        mesh.devices.size, **{a: n for a, n in mesh.shape.items()
                              if a in ("ep", "tp", "sp", "pp") and n > 1}))
    state = make_train_state(params, optimizer)
    tokens = jax.random.randint(
        jax.random.PRNGKey(7), (2, rows * dp, seq), 0, 256)
    return trainer, state, tokens


class TestHeadsOverTheGroup:
    """``DEFAULT_RULES["heads"]`` and ``["kv_heads"]`` name ``ep`` and
    ``tp`` together, as ``expert_mlp`` and ``vocab`` do: each chip of an
    expert group projects, attends and un-projects its share of the
    heads."""

    @pytest.mark.parametrize("axes", MESHES, ids=MESH_IDS)
    @pytest.mark.parametrize("leaf", ATTN_LEAVES)
    def test_a_device_holds_its_share_of_the_heads(self, leaf, axes):
        c = _attn_tiny()
        mesh = build_mesh(plan_mesh(8, **axes))
        params = moe.init_params(c, jax.random.PRNGKey(0))
        sharded = shard_tree(mesh, params, moe.param_logical_axes(c))
        chips, fsdp = mesh.shape["ep"] * mesh.shape["tp"], mesh.shape["fsdp"]
        heads = c.n_kv_heads if leaf in ("wk", "wv") else c.n_heads
        width, embed = heads // chips * c.head_dim, c.dim // fsdp
        local = ((c.n_layers, width, embed) if leaf == "wo"
                 else (c.n_layers, embed, width))
        shards = sharded["layers"][leaf].addressable_shards
        assert len(shards) == 8
        for shard in shards:
            assert shard.data.shape == local, shard.device
        assert len({tuple((i.start, i.stop) for i in s.index)
                    for s in shards}) == chips * fsdp
        assert sharding.head_split(mesh, c.n_heads) == (("ep", "tp"), chips)

    @pytest.mark.parametrize("mesh_id", ["ep4xfsdp2", "ep2xtp2xfsdp2"])
    @pytest.mark.parametrize("flash", [True, False], ids=["flash", "dense"])
    @pytest.mark.parametrize("leaf", ["loss"] + MODEL_LEAVES)
    def test_loss_and_every_gradient_leaf_equal_the_meshless(
            self, leaf, flash, mesh_id):
        (ref_loss, ref_grads), (loss, grads) = _heads_case(mesh_id, flash)
        tol = dict(atol=2e-3, rtol=2e-3)    # test_ep_sharded_matches_unsharded
        if leaf == "loss":
            np.testing.assert_allclose(float(loss), float(ref_loss), **tol)
            return
        got, want = grads, ref_grads
        for key in leaf.split("/"):
            got, want = got[key], want[key]
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)

    @pytest.mark.parametrize("mesh_id", ["ep4xfsdp2", "ep2xtp2xfsdp2",
                                         "tp2xfsdp4", "one-device"])
    def test_the_step_says_how_many_chips_split_the_heads(self, mesh_id):
        """Where the split is made: ``head_split`` of the step's mesh gives
        ``ep x tp`` chips (1 on one device), and after two steps each
        chip's ``wq`` holds that share of the query heads."""
        c = _attn_tiny(vocab_size=272)    # a signature no other test has
        axes = HEAD_MESHES.get(mesh_id)
        mesh = (build_mesh(plan_mesh(8, **axes)) if axes
                else build_mesh(plan_mesh(1)))
        want = mesh.shape["ep"] * mesh.shape["tp"]
        params = shard_tree(mesh, moe.init_params(c, jax.random.PRNGKey(0)),
                            moe.param_logical_axes(c))
        trainer, state, tokens = _step(
            lambda p, t: moe.next_token_loss(p, t, c, mesh), params,
            mesh, rows=1)
        for _ in range(2):
            state, result = trainer.train_step(state, tokens)
        assert bool(jnp.isfinite(result.loss))
        assert sharding.head_split(mesh, c.n_heads)[1] == want
        width = c.n_heads // want * c.head_dim
        for shard in state["params"]["layers"]["wq"].addressable_shards:
            assert shard.data.shape[-1] == width

    def test_the_expert_step_sums_no_query_key_or_value(self):
        """The step on ``ep`` 4 through the flash kernel's ``shard_map``:
        q, k and v come in split by heads as the projections made them,
        so the transpose sums none of their cotangents over the group
        (with the heads replicated it all-reduced the three, each
        ``[B, H, S, D]``); what joins the heads is the all-reduce of the
        ``[B, S, D]`` hidden states after ``wo``."""
        import re

        c = _attn_tiny(use_flash_attention=True)
        mesh = build_mesh(plan_mesh(8, ep=4))
        params = shard_tree(mesh, moe.init_params(c, jax.random.PRNGKey(0)),
                            moe.param_logical_axes(c))
        seq = 32
        trainer, state, tokens = _step(
            lambda p, t: moe.next_token_loss(p, t, c, mesh), params, mesh,
            rows=1, seq=seq + 1)
        text = trainer._build_step().lower(state, tokens).compile().as_text()

        def moves(line):
            # XLA:CPU keeps a sum over axes of size 1: one device a group
            listed = re.search(r"replica_groups=\{\{([\d,]*)\}", line)
            if listed:
                return len(listed.group(1).split(",")) > 1
            return int(re.search(
                r"replica_groups=\[[\d,]*?(\d+)\]", line).group(1)) > 1

        sums = [line for line in text.splitlines()
                if re.search(r" all-reduce(-start)?\(", line) and moves(line)]
        shapes = [tuple(int(d) for d in dims.split(","))
                  for line in sums
                  for dims in re.findall(r"\w+\[([\d,]+)\]",
                                         line.split(" all-reduce")[0])]
        said = "all-reduces:\n  " + "\n  ".join(
            line.strip()[:160] for line in sums)
        assert not [s for s in shapes
                    if len(s) == 4 and s[2:] == (seq, c.head_dim)], said
        assert (1, seq, c.dim) in shapes, said

    @pytest.mark.parametrize("model", ["llama", "looped", "moe"])
    def test_one_device_lowers_the_step_of_a_tp_rule(
            self, model, monkeypatch):
        """On one device the rules lower the training step to the text the
        table that named ``tp`` alone for the heads lowered: the one-chip
        programs are unchanged, to the annotation."""
        from dlrover_tpu.models import looped

        make = {
            "llama": (llama.LlamaConfig.tiny, llama),
            "looped": (looped.LoopedConfig.tiny, looped),
            "moe": (moe.MoEConfig.tiny, moe),
        }
        tiny, module = make[model]
        c = dataclasses.replace(tiny(), use_flash_attention=True)
        mesh = build_mesh(plan_mesh(1))

        def lowered():
            jax.clear_caches()       # the jitted losses trace anew
            params = shard_tree(mesh, module.init_params(
                c, jax.random.PRNGKey(0)), module.param_logical_axes(c))
            trainer, state, tokens = _step(
                lambda p, t: module.next_token_loss(p, t, c, mesh), params,
                mesh, rows=1)
            return trainer._build_step().lower(state, tokens).as_text()

        now = lowered()
        for name in ("heads", "kv_heads"):
            monkeypatch.setitem(sharding.DEFAULT_RULES, name, "tp")
        assert lowered() == now
        assert '{"tp"}' in now

    def test_heads_the_group_does_not_divide_stay_whole(self, monkeypatch):
        """Five heads over ``ep`` 4: ``head_split`` gives one chip, so
        every chip computes every head, the loss and gradients are the
        meshless ones, and the log says so once however often attention
        is traced."""
        from dlrover_tpu.common.log import logger

        c = llama.LlamaConfig(
            vocab_size=256, dim=40, n_layers=2, n_heads=5, n_kv_heads=5,
            ffn_dim=64, max_seq_len=64, remat=False, dtype=jnp.float32,
            use_flash_attention=True)
        mesh = build_mesh(plan_mesh(8, ep=4))
        lines = []
        monkeypatch.setattr(
            logger, "info", lambda msg, *a: lines.append(msg % a))
        assert sharding.head_split(mesh, c.n_heads) == (None, 1)
        params = llama.init_params(c, jax.random.PRNGKey(0))
        tokens = _HEAD_TOKENS[:2]
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: llama.next_token_loss(
                p, tokens, dataclasses.replace(c, use_flash_attention=False)
            ))(params)
        sharded = shard_tree(mesh, params, llama.param_logical_axes(c))
        tok_s = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: llama.next_token_loss(p, t, c, mesh)))(sharded, tok_s)
        llama.next_token_loss(sharded, tok_s[:, :17], c, mesh)  # traced anew
        tol = dict(atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(float(loss), float(ref_loss), **tol)
        for name in ATTN_LEAVES:
            np.testing.assert_allclose(
                np.asarray(grads["layers"][name]),
                np.asarray(ref_grads["layers"][name]), err_msg=name, **tol)
        said = [line for line in lines if "5 heads" in line]
        assert len(said) == 1, lines
        assert "4 chips" in said[0] and "every head" in said[0]


def test_cross_entropy_matches_log_softmax_gather():
    """The logsumexp-gather formulation (llama.cross_entropy) is the
    log_softmax+gather NLL with the (B,S,V) logp intermediate elided —
    values must agree to float tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import llama

    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (2, 5, 17), jnp.float32) * 3.0
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 17)
    ours = llama.cross_entropy(logits, targets)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ref = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].mean()
    np.testing.assert_allclose(float(ours), float(ref), rtol=1e-6)


# -- the benchmark's reader of the layer (benchmarks/layer_metrics) ----------

_T = "{3,2,1,0:T(8,128)(2,1)}"
# HLO lines of the kept traces of PR 30 (mixtral-8x7b.train-steady, chip 0),
# shortened: (line, is an op of the experts)
SLOT_LINES = [   # the one-hot dispatch: activations laid out (e, f, g, c)
    (f"%fusion.564 = (bf16[2,14336,8,512]{_T}, bf16[2,14336,8,512]{_T}) "
     f"fusion(bf16[2,14336,8,512]{_T} %fusion.561, bf16[8,2,512,4096]{_T} "
     f"%fusion.562, bf16[1,2,4096,14336]{_T} %get-tuple-element.1865), "
     "kind=kOutput", True),
    (f"%bitcast_add_fusion.20 = f32[1,2,14336,4096]{_T} fusion("
     f"f32[1,2,14336,4096]{_T} %get-tuple-element.1803, "
     f"bf16[8,2,512,14336]{_T} %bitcast_multiply_fusion.31, "
     f"bf16[2,4096,8,512]{_T} %convolution_bitcast_fusion.4), kind=kOutput",
     True),
    (f"%fusion.566 = bf16[8,2,512,4096]{_T} fusion(bf16[2,8,512,4096]{_T} "
     f"%fusion.565, bf16[1,2,4096,14336]{_T} %get-tuple-element.1865, "
     f"bf16[2,14336,8,512]{_T} %get-tuple-element.1719), kind=kOutput", True),
    (f"%fusion.545 = bf16[8,2,512,4096]{_T} fusion(bf16[8,512,4096,1]{_T} "
     f"%bitcast.964, bf16[8,512,2,512]{_T} %multiply_reduce_fusion.15), "
     "kind=kOutput", False),                       # the dispatch einsum
]
SORTED_LINES = [  # the sorted pairs: activations (T·k, f)
    (f"%gmm.8 = bf16[8192,14336]{_T} custom-call(s32[] %fusion.634, "
     f"s32[17] %copy-done.137, bf16[8192,4096]{_T} "
     f"%broadcast_select_fusion.24, bf16[2,4096,14336]{_T} %bitcast.795), "
     'custom_call_target="tpu_custom_call"', True),
    (f"%tgmm.1 = bf16[2,4096,14336]{_T} custom-call(s32[] "
     f"%get-tuple-element.5516, bf16[8192,4096]{_T} %copy-done.20, "
     f"bf16[8192,14336]{_T} %get-tuple-element.5484), "
     'custom_call_target="tpu_custom_call"', True),
    (f"%multiply_multiply_fusion.21 = bf16[8192,14336]{_T} fusion("
     f"bf16[8192,14336]{_T} %gmm.3, bf16[8192,14336]{_T} %gmm.4), kind=kLoop",
     True),
    (f"%bitcast_add_fusion.20 = f32[1,2,4096,14336]{_T} fusion("
     f"f32[1,2,4096,14336]{_T} %get-tuple-element.5715, "
     f"bf16[2,4096,14336]{_T} %tgmm.1, pred[] %compare.533), kind=kLoop",
     False),                                       # sum into the f32 gradient
    (f"%fusion.672 = bf16[8192,4096]{_T} fusion(bf16[8192,4096]{_T} "
     "%get-tuple-element.5485, s32[8192] %copy-done.59), kind=kCustom",
     False),                                       # a gather of rows
]
BOTH_LINES = [
    (f"%add_convert_fusion.8 = (bf16[1,2,4096,14336]{_T}, "
     f"f32[1,2,4096,14336]{_T}) fusion(bf16[1,2,4096,14336]{_T} %param.319, "
     f"f32[1,2,4096,14336]{_T} %param.341), kind=kLoop", False),   # AdamW
    (f"%while.311 = (s32[], f32[1,2,4096,14336]{_T}, bf16[8192,14336]{_T}) "
     "while((s32[], f32[1,2,4096,14336]) %tuple.1), condition=%c, body=%b",
     False),
    (f"%flash_fwd.3 = bf16[1,32,4096,128]{_T} custom-call("
     f"bf16[1,32,4096,128]{_T} %q), "
     'custom_call_target="tpu_custom_call"', False),
]


@pytest.mark.parametrize("lines", [SLOT_LINES, SORTED_LINES],
                         ids=["one-hot-slots", "sorted-pairs"])
def test_expert_ms_reads_the_expert_ops_of_either_program(lines):
    """``moe.expert_ms`` on a hand-made profile of three step programs,
    the last cut by the profile's edge: the self times of the ops that
    hold an expert activation, a whole step, on the one chip profiled
    (the reader gives the largest over the chips); the same rule reads
    the program of before PR 30 and the one of PR 30 to PR 32. Since
    PR 33 a chip's arrays are ``intermediate_size / (ep x tp)`` wide and
    the rule finds none: ``moe.gmm_ms`` reads the kernels by name."""
    from benchmarks import run as bench_run

    lines = lines + BOTH_LINES
    events, want = [], 0
    for step, start in enumerate((0, 10_000_000, 20_000_000)):
        at = start + 1000
        if any(" while(" in line for line, _ in lines):
            # the accumulation loop holds the step's ops nested inside it
            loop = next(line for line, _ in lines if " while(" in line)
            events.append([loop, at, 9_000_000])
        ops = [t for t in lines if " while(" not in t[0]]
        for n, (line, expert) in enumerate(ops[:2] if step == 2 else ops):
            dur = 100_000 * (n + 1)
            events.append([line, at + 10, dur])
            at += dur + 10
            want += dur if expert and step < 2 else 0
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step_fn(1)", s, 9_500_000]
            for s in (0, 10_000_000, 20_000_000)]},
        {"name": "XLA Ops", "events": events}]}]}
    ctx = {"trace_raw": trace, "step_module": "step_fn", "job": {},
           "fields": {"hidden_size": 4096, "intermediate_size": 14336,
                      "num_local_experts": 8}}
    reader = bench_run.load_reader("moe.expert_ms")
    assert reader.read(ctx) == pytest.approx(want / 1e6 / 2)
    # a dense configuration has no expert FFN to read
    dense = {k: v for k, v in ctx["fields"].items()
             if k != "num_local_experts"}
    assert reader.read({**ctx, "fields": dense}) is None
    assert reader.read({**ctx, "trace_raw": None}) is None


# the kernels' own lines under either layout of the expert leaves over
# ep 4: whole experts on chips (two of eight, every column) and every
# expert at a quarter of its columns (PR 33)
_PALLAS = 'custom_call_target="tpu_custom_call"'
KERNEL_LINES = {
    "whole-experts": (
        f"%gmm.8 = bf16[8192,14336]{_T} custom-call(s32[17] %copy-done.137, "
        f"bf16[8192,4096]{_T} %fusion.24, bf16[2,4096,14336]{_T} "
        f"%bitcast.795), {_PALLAS}",
        f"%tgmm.1 = bf16[2,4096,14336]{_T} custom-call(bf16[8192,4096]{_T} "
        f"%copy-done.20, bf16[8192,14336]{_T} %get-tuple-element.5484), "
        f"{_PALLAS}"),
    "sliced-columns": (
        f"%gmm.8 = bf16[8192,3584]{_T} custom-call(s32[17] %copy-done.137, "
        f"bf16[8192,4096]{_T} %fusion.24, bf16[8,4096,3584]{_T} "
        f"%bitcast.795), {_PALLAS}",
        f"%tgmm.1 = bf16[8,4096,3584]{_T} custom-call(bf16[8192,4096]{_T} "
        f"%copy-done.20, bf16[8192,3584]{_T} %get-tuple-element.5484), "
        f"{_PALLAS}"),
}


@pytest.mark.parametrize("layout", sorted(KERNEL_LINES))
def test_gmm_ms_reads_the_grouped_matmuls_by_name(layout, capsys):
    """``moe.gmm_ms`` on a hand-made profile of four chips, three step
    programs each, the last cut by the profile's edge: the ``gmm`` and
    ``tgmm`` kernels' milliseconds a whole step on the chip where they
    take longest, whatever the width of a chip's arrays; not the op that
    takes a kernel's result, not another Pallas call."""
    import json

    from benchmarks import run as bench_run

    gmm, tgmm = KERNEL_LINES[layout]
    others = [BOTH_LINES[2][0],                    # the flash kernel
              f"%multiply_fusion.2 = bf16[8192,3584]{_T} fusion("
              f"bf16[8192,3584]{_T} %gmm.8), kind=kLoop"]
    gmm_ns = (500_000, 1_500_000, 1_000_000, 1_000_000)     # by chip
    planes = []
    for chip, ns in enumerate(gmm_ns):
        events = []
        for step, start in enumerate((0, 10_000_000, 20_000_000)):
            at = start + 1000
            ops = [(gmm, ns), (gmm, ns), (tgmm, 2 * ns)] + [
                (line, 400_000) for line in others]
            for line, dur in ops[:1] if step == 2 else ops:
                events.append([line, at, dur])
                at += dur + 10
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step_fn(1)", s, 9_500_000]
                for s in (0, 10_000_000, 20_000_000)]},
            {"name": "XLA Ops", "events": events}]})
    ctx = {"trace_raw": {"planes": planes}, "step_module": "step_fn",
           "job": {}, "fields": {"hidden_size": 4096,
                                 "intermediate_size": 14336,
                                 "num_local_experts": 8}}
    read = bench_run.load_reader("moe.gmm_ms").read
    by_chip = [4 * ns / 1e6 for ns in gmm_ns]
    assert read(ctx) == pytest.approx(max(by_chip))
    note = next(n for n in map(
        json.loads, capsys.readouterr().out.splitlines())
        if n["note"] == "gmm_ms_by_chip")
    assert note["chips"] == pytest.approx(by_chip)
    assert note["calls_a_step"] == [3] * 4
    assert note["largest_over_smallest"] == pytest.approx(3.0)
    # the readers of PR 30 and PR 32 find the arrays of the older layout
    # alone: this one is what keeps the layer in sight
    wide = bench_run.load_reader("moe.expert_ms").read(ctx)
    assert (wide is None) == (layout == "sliced-columns")
    # nothing to read: no trace, or a program without the kernels
    assert read({**ctx, "trace_raw": None}) is None
    for plane in planes:
        plane["lines"][1]["events"] = [
            e for e in plane["lines"][1]["events"] if "gmm." not in e[0][:8]]
    assert read(ctx) is None


@pytest.mark.parametrize("layout", ["whole", "quarter"])
def test_head_ms_reads_the_head_of_either_layout(layout, capsys):
    """``head.ms`` (PR 34) on ``benchmarks/tests/test_head_ms.py``'s
    hand-made profile of four chips: the ops whose line holds the logits
    of ``vocab_size / n`` columns, where every chip makes all of them
    (``n`` 1) and where each makes a quarter (``n`` 4); the chip where
    they take longest; nothing for a shape that is no share of this
    cell's vocabulary."""
    from benchmarks.tests import test_head_ms as made

    read = made.read
    assert read(made.ctx_for(made.trace(layout))) == pytest.approx(4400e-6)
    capsys.readouterr()
    even = made.trace(layout, slow_chip=None)
    assert read(made.ctx_for(even)) == pytest.approx(2400e-6)
    assert read(made.ctx_for(even, vocab_size=made.VOCAB * 8)) is None
    assert read({**made.ctx_for(even), "trace_raw": None}) is None
