"""Latent attention (models/mla.py), a chip's share of sigmoid-routed
experts beside shared ones and a leading dense layer (models/moe.py),
against the plain float32 reference ``benchmarks/reference/mla_moe.py``
on seeded random weights, at small widths on the CPU; the balancing
bias under the trainer; the readers of the new per-layer metrics."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.ad_checkpoint import checkpoint_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.families import mla_moe as family  # noqa: E402
from benchmarks.reference import mla_moe as reference  # noqa: E402
from dlrover_tpu.models import llama, mla, moe  # noqa: E402

SEQ = 64


def _fields(**over):
    with open(os.path.join(
            ROOT, "benchmarks/configs/moonlight-16b-a3b.json")) as f:
        fields = json.load(f)
    return {**fields, **family.REHEARSAL_FIELDS, "num_hidden_layers": 2,
            **over}


def _f32_params(config, seed=3):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        family.init_params(config, jax.random.PRNGKey(seed)))


def _tokens(fields, rows=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, fields["vocab_size"], size=(rows, SEQ + 1), dtype=np.int32))


def _close(a, b, err_msg="", rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol, err_msg=err_msg)


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
def test_the_latent_attention_block_matches_the_reference(flash):
    """One block, forward and the gradients of its input and every leaf:
    192/128-shaped (here 24 over 16) queries and keys over values through
    the dense path and through the flash kernels (interpret mode)."""
    fields = _fields()
    config = dataclasses.replace(family.program_config(fields, SEQ),
                                 use_flash_attention=flash)
    layer = jax.tree.map(lambda a: a[0],
                         _f32_params(config)["dense_layers"])
    keys = [k for k in mla.param_axes() if k != "attn_norm"]
    layer = {k: layer[k] for k in keys}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, config.dim))
    probe = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, config.dim))
    positions = jnp.broadcast_to(jnp.arange(SEQ)[None, :], (2, SEQ))

    def program(x, layer):
        return (mla.attention(x, layer, config, positions, None)
                * probe).sum()

    def plain(x, layer):
        return (reference._attention(x, layer, fields) * probe).sum()

    with jax.default_matmul_precision("highest"):
        got, (dx, dl) = jax.value_and_grad(program, argnums=(0, 1))(x, layer)
        want, (rx, rl) = jax.value_and_grad(plain, argnums=(0, 1))(x, layer)
    _close(got, want)
    _close(dx, rx, "x")
    for name in keys:
        _close(dl[name], rl[name], name)


def test_the_model_matches_the_reference_loss_and_every_gradient():
    """A dense layer then an expert layer, the whole loss (the sequence-
    wise balance term and the bias's pull in it): loss and every
    gradient leaf, the selection bias's (each expert's share of the pairs
    less the mean share) among them."""
    fields = _fields()
    config = family.program_config(fields, SEQ)
    params = _f32_params(config)
    tokens = _tokens(fields)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(family.loss_fn(config, None))(
            params, tokens)
    want, want_grads = jax.value_and_grad(reference.next_token_loss)(
        params, tokens, fields)
    _close(got, want, rtol=2e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(want_grads)):
        _close(g, r, jax.tree_util.keystr(path))
    bias = grads["layers"]["router_bias"]
    assert float(jnp.abs(bias).max()) > 0
    assert abs(float(bias.sum())) < 1e-6    # shares less their mean


def test_the_parameter_tree_is_what_the_family_counts():
    fields = _fields()
    config = family.program_config(fields, SEQ)
    params = family.init_params(config, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        family.param_count(fields))
    assert params["layers"]["router_bias"].dtype == jnp.float32
    assert params["layers"]["router"].shape[-1] == 16     # router width
    assert params["layers"]["w1"].shape[1] == 4           # experts held
    assert set(params["dense_layers"]) == {
        *mla.param_axes(), "ffn_norm", "w1", "w3", "w2"}
    axes = moe.param_logical_axes(config)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


def _expert_layer_config(held, first, router=16):
    fields = _fields(n_routed_experts=held, program={
        "router_experts": router, "first_expert": first,
        "capacity_factor": 6.0})
    return fields, family.program_config(fields, SEQ)


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """The router 4 x the experts held: what each of the four shares'
    experts add, with the shared experts counted once, is the layer of
    all sixteen experts, in the program and in the reference."""
    fields, whole = _expert_layer_config(held=16, first=0)
    layer = jax.tree.map(lambda a: a[0], _f32_params(whole)["layers"])
    layer["router_bias"] = jax.random.normal(
        jax.random.PRNGKey(9), layer["router_bias"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, whole.dim))
    shared = llama._mlp(x, {k: layer["shared_" + k]
                            for k in ("w1", "w3", "w2")})
    with jax.default_matmul_precision("highest"):
        uncut, _ = moe._ffn(x, layer, whole)
        want, _, _ = reference._expert_layer(x, layer, fields)
        parts = []
        for first in range(0, 16, 4):
            _, config = _expert_layer_config(held=4, first=first)
            share = {**layer, **{k: layer[k][first:first + 4]
                                 for k in ("w1", "w3", "w2")}}
            out, _ = moe._ffn(x, share, config)
            parts.append(out - shared)
            share_fields, _ = _expert_layer_config(held=4, first=first)
            ref_out, _, _ = reference._expert_layer(x, share, share_fields)
            _close(out, ref_out, f"share from {first}")
    _close(uncut, want)
    _close(sum(parts) + shared, uncut)
    # and no share is the whole: each leaves some pairs to the others
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_the_live_rows_path_is_the_whole_buffer_path(monkeypatch):
    """The held share's layer moves only the live rows (the registry
    counts its traced calls as ``live``); forced onto the whole-buffer
    path it gives the same loss and every gradient leaf, to f32's
    summation order."""
    from dlrover_tpu.observability.registry import get_registry

    fields = _fields()
    config = family.program_config(fields, SEQ)
    params = _f32_params(config)
    tokens = _tokens(fields)
    calls = get_registry().counter(
        "dlrover_moe_permute_calls_total", labelnames=("path",))
    before = {p: calls.labels(path=p).value for p in ("live", "whole")}

    def value_and_grad():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: moe.loss_and_stats(p, tokens, config)[0])(params)

    got, grads = value_and_grad()
    traced = {p: calls.labels(path=p).value - before[p]
              for p in ("live", "whole")}
    assert traced["live"] >= 1 and traced["whole"] == 0
    real = moe._routed_rows
    monkeypatch.setattr(moe, "_routed_rows",
                        lambda *a, live_only: real(*a, live_only=False))
    monkeypatch.setattr(moe, "loss_and_stats",
                        moe.loss_and_stats.__wrapped__)
    want, want_grads = value_and_grad()
    assert calls.labels(path="whole").value > before["whole"]
    _close(got, want, rtol=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(want_grads)):
        _close(g, r, jax.tree_util.keystr(path), rtol=1e-5, atol=1e-6)


def test_the_selection_bias_chooses_but_does_not_gate():
    _, config = _expert_layer_config(held=4, first=0)
    E, k = config.router_width, config.top_k
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, config.dim))
    router = jax.random.normal(jax.random.PRNGKey(2), (config.dim, E)) * 0.1
    cap = moe.expert_capacity(config, 2, SEQ)

    def route(bias):
        expert, gates, keep, _ = moe._route(x, router, config, cap, bias)
        return np.asarray(expert), np.asarray(gates), np.asarray(keep)

    zero = route(jnp.zeros(E))
    # a bias that moves every expert alike changes no choice and no gate
    shifted = route(jnp.full(E, 5.0))
    np.testing.assert_array_equal(zero[0], shifted[0])
    np.testing.assert_array_equal(zero[1], shifted[1])
    # one expert favoured: every token chooses it, and its gate is its
    # score's share of the chosen scores, the bias in none of them
    favoured = route(jnp.zeros(E).at[7].set(10.0))
    assert (favoured[0] == 7).any(-1).all()
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum("gtd,de->gte", x, router)))
    top = np.take_along_axis(scores, favoured[0], -1)
    np.testing.assert_allclose(
        favoured[1], config.routed_scaling * top / top.sum(-1, keepdims=True),
        rtol=1e-6)
    assert favoured[2].all()              # the capacity drops nothing
    assert k == 3


def test_the_bias_moves_against_the_load_under_the_trainer():
    """A batch skewed onto three experts (a bias that starts them two
    ahead, so every token chooses them), a few steps of the trainer with
    AdamW: the bias of those three, which draw more than the mean share,
    falls and that of the others rises; the step's stats hold each
    expert's share, and the registry's gauges read the held experts'."""
    from dlrover_tpu.observability.registry import (
        get_registry,
        reset_registry,
    )
    from dlrover_tpu.parallel.mesh import plan_mesh
    from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

    fields = _fields()
    config = family.program_config(fields, SEQ)
    params = family.init_params(config, jax.random.PRNGKey(0))
    start = np.zeros((1, 16), np.float32)
    start[:, :3] = 2.0
    params["layers"]["router_bias"] = jnp.asarray(start)
    reset_registry()
    try:
        optimizer = optax.adamw(1e-2)
        trainer = ElasticTrainer(
            loss_fn=family.loss_fn(config, None), optimizer=optimizer,
            global_batch_size=2, micro_batch_per_replica=1)
        trainer.configure_for_world(plan_mesh(1))
        state = make_train_state(params, optimizer)
        for step in range(4):
            batch = _tokens(fields, seed=step).reshape(2, 1, SEQ + 1)
            state, result = trainer.train_step(state, batch)
        load = np.asarray(result.stats["expert_load"])     # (1, 16)
        assert load.shape == (1, 16)
        np.testing.assert_allclose(load.sum(-1), 1.0, rtol=1e-6)
        assert (load[0, :3] > 1 / 16).all()
        moved = np.asarray(state["params"]["layers"]["router_bias"]
                           - start)[0]
        assert (moved[:3] < 0).all() and (moved[3:] > 0).all()
        text = get_registry().render()
        gauges = dict(line.split() for line in text.splitlines()
                      if line.startswith("dlrover_moe_held_"))
        held = load[0, 4:8]       # first_expert 4, four held
        np.testing.assert_allclose(
            float(gauges["dlrover_moe_held_pair_share"]), held.sum(),
            rtol=1e-6)
        np.testing.assert_allclose(
            float(gauges["dlrover_moe_held_load_ratio"]),
            held.max() / load[0].mean(), rtol=1e-6)
    finally:
        reset_registry()


def _old_mixtral_init(c, key):
    """``moe.init_params`` as it drew a Mixtral before it could hold a
    share of a wider router, shared experts or dense layers."""
    keys = jax.random.split(key, 7)
    dt, dense, L, E = c.dtype, llama.dense_init, c.n_layers, c.n_experts
    return {
        "tok_embed": dense(keys[0], (c.vocab_size, c.dim), c.dim, dt),
        "layers": {
            **llama.init_attention_params(c, keys[1]),
            "ffn_norm": jnp.ones((L, c.dim), dtype=dt),
            "router": jax.random.normal(
                keys[2], (L, c.dim, E), dtype=jnp.float32) * (c.dim ** -0.5),
            "w1": dense(keys[3], (L, E, c.dim, c.ffn_dim), c.dim, dt),
            "w3": dense(keys[4], (L, E, c.dim, c.ffn_dim), c.dim, dt),
            "w2": dense(keys[5], (L, E, c.ffn_dim, c.dim), c.ffn_dim, dt),
        },
        "final_norm": jnp.ones((c.dim,), dtype=dt),
        "lm_head": dense(keys[6], (c.dim, c.vocab_size), c.dim, dt),
    }


def test_a_mixtral_draws_and_routes_as_before(monkeypatch):
    """The softmax router holding every expert: the same tree drawn bit
    for bit from a key, no stats, the layer's report the balance term
    alone (its numbers are held to the one-hot dispatch in test_moe.py),
    and the layer's jaxpr, forward and backward, the whole-buffer path's
    as it was."""
    c = dataclasses.replace(moe.MoEConfig.tiny(), n_layers=2)
    key = jax.random.PRNGKey(17)
    got, want = moe.init_params(c, key), _old_mixtral_init(c, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                c.vocab_size)
    loss, stats = moe.loss_and_stats(got, tokens, c)
    assert stats == {} and bool(jnp.isfinite(loss))
    assert not hasattr(moe.make_loss_fn(c), "with_stats")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, c.dim))
    layer = jax.tree.map(lambda a: a[0], got["layers"])
    _, report = moe._ffn(x, layer, c)
    assert set(report) == {"aux"}

    # the layer and its gradient trace to the jaxpr they traced to before
    # the held share's layer moved its live rows alone
    def traced():
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda x, layer: moe._ffn(x, layer, c)[0].sum(),
            argnums=(0, 1)))(x, layer))

    now = traced()
    monkeypatch.setattr(moe, "_routed_rows", _old_routed_rows)
    assert traced() == now


@jax.custom_vjp
def _permute(x, perm, inverse):
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _old_sort_by_expert(expert, keep, n_experts):
    key = jnp.where(keep, expert, n_experts)
    order = jnp.argsort(key, stable=True)
    group_sizes = (key[:, None] == jnp.arange(n_experts)).sum(
        0, dtype=jnp.int32)
    return order, group_sizes


def _old_routed_rows(x, expert, keep, gates, w1, w3, w2, live_only=False):
    """``moe._routed_rows`` with its sort, permutation and SwiGLU as they
    were before a chip's share of the experts moved its live rows alone:
    every pair's row into the sorted buffer and out of it."""
    assert not live_only
    k, D = expert.shape[-1], x.shape[-1]
    by_choice = lambda a: a.reshape(-1, k).T
    order, group_sizes = _old_sort_by_expert(
        by_choice(expert).reshape(-1), by_choice(keep).reshape(-1),
        w1.shape[0])
    inverse = jnp.argsort(order)
    rows = _permute(jnp.tile(x.reshape(-1, D), (k, 1)), order, inverse)
    live = (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]
    rows = jnp.where(live, rows, 0)
    gate = checkpoint_name(moe._grouped_matmul(rows, w1, group_sizes),
                           "moe_gate")
    up = checkpoint_name(moe._grouped_matmul(rows, w3, group_sizes),
                         "moe_up")
    down = checkpoint_name(moe._grouped_matmul(
        jax.nn.silu(gate) * up, w2, group_sizes), "moe_down")
    out = jnp.where(live, down, 0)
    out = _permute(out, inverse, order).reshape(k, -1, D)
    y = (out.astype(jnp.float32) * by_choice(gates)[..., None]).sum(0)
    return y.astype(x.dtype).reshape(x.shape)


# -- the readers of the new per-layer metrics ---------------------------------

PALLAS = 'custom_call_target="tpu_custom_call"'
FIELDS = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "hidden_size": 2048,
          "moe_intermediate_size": 1408, "n_routed_experts": 8}
OPS = [
    # (HLO line, microseconds a step): latent, experts, neither
    ("%fusion.1 = bf16[8192,576]{1,0} fusion(bf16[8192,2048]{1,0} %p)", 30),
    ("%fusion.2 = bf16[8192,512]{1,0} fusion(bf16[8192,576]{1,0} %f)", 20),
    ("%convolution.3 = bf16[8192,4096]{1,0} convolution(bf16[8192,512]"
     "{1,0} %n, bf16[512,4096]{1,0} %w)", 50),
    ("%gmm.4 = bf16[49152,1408]{1,0} custom-call(bf16[49152,2048]{1,0} %r,"
     f" bf16[8,2048,1408]{{2,1,0}} %w), {PALLAS}", 400),
    ("%tgmm.5 = bf16[8,2048,1408]{2,1,0} custom-call(bf16[2048,49152]{1,0}"
     f" %r, bf16[49152,1408]{{1,0}} %g), {PALLAS}", 300),
    ("%fusion.6 = bf16[49152,1408]{1,0} fusion(bf16[49152,1408]{1,0} %a, "
     "bf16[49152,1408]{1,0} %b)", 100),
    ("%fusion.7 = f32[8,2048,1408]{2,1,0} fusion(f32[8,2048,1408]{2,1,0} "
     "%m)", 700),                                     # AdamW: neither
    ("%flash_fwd.8 = bf16[1,16,8192,128]{3,2,1,0} custom-call(bf16[1,16,"
     f"8192,192]{{3,2,1,0}} %q), {PALLAS}", 900),       # a kernel: neither
    ("%fusion.9 = bf16[8192,2816]{1,0} fusion(bf16[8192,2816]{1,0} %s)",
     80),                                             # shared: neither
]


def _trace(steps=3):
    """Chip 0 runs ``steps`` step programs of OPS back to back; the
    profile's edge cuts a fourth after its first two ops."""
    modules, ops, t = [], [], 0
    for n in range(steps + 1):
        start = t
        for i, (name, us) in enumerate(OPS):
            if n == steps and i == 2:
                break
            ops.append([name, t, us * 1000])
            t += us * 1000
        modules.append(["jit_step_fn(1)", start, t - start])
        t += 1000
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}
    return {"planes": [plane]}


@pytest.mark.parametrize("metric,expected_ms", [
    ("mla.latent_ms", 0.1), ("moe.held_expert_ms", 0.8)])
def test_the_trace_readers_take_their_ops_in_whole_steps(metric,
                                                         expected_ms):
    ctx = {"trace_raw": _trace(), "step_module": "step_fn",
           "fields": FIELDS, "job": {"steps": 3}}
    got = bench_run.load_reader(metric).read(ctx)
    assert got == pytest.approx(expected_ms)
    assert bench_run.load_reader(metric).read(
        {**ctx, "fields": {"hidden_size": 2048}}) is None
    assert bench_run.load_reader(metric).read(
        {**ctx, "trace_raw": None}) is None


@pytest.mark.parametrize("metric,gauge", [
    ("moe.held_pair_share", "dlrover_moe_held_pair_share"),
    ("moe.held_load_ratio", "dlrover_moe_held_load_ratio")])
def test_the_gauge_readers_read_the_programs_registry(metric, gauge):
    text = (f"# TYPE {gauge} gauge\n{gauge} 0.25\n"
            f"{gauge}_other 9\n")
    ctx = {"job": {"steps": 3}, "fields": FIELDS, "registry_text": text}
    reader = bench_run.load_reader(metric)
    assert reader.read(ctx) == 0.25
    # a program that does not publish the gauge, or has no step to
    # compute it from; a cell without a share
    assert reader.read({**ctx, "registry_text": "x 1\n"}) is None
    assert reader.read({**ctx, "registry_text": f"{gauge} NaN\n"}) is None
    assert reader.read({**ctx, "fields": {"hidden_size": 1}}) is None
    assert reader.read({**ctx, "job": {}}) is None
