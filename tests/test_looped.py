"""models/looped.py: the weight-shared stack run several times, against
the benchmark's plain reference (benchmarks/reference/looplm.py) on
seeded weights at a tiny size, and through the normal path
(ElasticTrainer, Checkpointer) on a CPU mesh."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import looplm  # noqa: E402
from benchmarks.reference.decoder import _rms_norm  # noqa: E402
from dlrover_tpu.ckpt.checkpointer import Checkpointer, StorageType  # noqa: E402
from dlrover_tpu.ckpt.shm_handler import shm_name  # noqa: E402
from dlrover_tpu.common.constants import SpanName  # noqa: E402
from dlrover_tpu.common.multi_process import unlink_shared_memory  # noqa: E402
from dlrover_tpu.models import llama, looped  # noqa: E402
from dlrover_tpu.observability import compile_watch, tracing  # noqa: E402
from dlrover_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry,
    reset_registry,
)
from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh  # noqa: E402
from dlrover_tpu.parallel.sharding import shard_tree  # noqa: E402
from dlrover_tpu.trainer.elastic import (  # noqa: E402
    ElasticTrainer,
    make_train_state,
)

SEQ = 32
# the benchmark's rehearsal limits: bf16 at width 64 against float32
BF16_TOLERANCE = {"loss_rel": 2e-3, "grad_norm_rel": 2e-2}


def config_for(n_passes, dtype=jnp.float32, **kw):
    return dataclasses.replace(
        looped.LoopedConfig.tiny(), n_passes=n_passes, dtype=dtype, **kw)


def fields_for(config):
    """The configuration-file keys the reference reads."""
    c = config
    return {
        "num_attention_heads": c.n_heads, "num_key_value_heads": c.n_kv_heads,
        "head_dim": c.head_dim, "hidden_size": c.dim,
        "num_hidden_layers": c.n_layers, "rope_theta": c.rope_theta,
        "rms_norm_eps": c.norm_eps, "total_ut_steps": c.n_passes,
        "exit_entropy_beta": c.exit_entropy_beta,
    }


def seeded_params(config, seed=3):
    """Init, with every norm weight and the gate's bias moved off their
    ones and zero: a fault in a norm must show."""
    params = looped.init_params(config, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def off_one(x):
        return (x.astype(jnp.float32) + 0.3 * jax.random.normal(
            next(keys), x.shape)).astype(x.dtype)

    for name in ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm"):
        params["layers"][name] = off_one(params["layers"][name])
    params["final_norm"] = off_one(params["final_norm"])
    params["exit_gate"]["b"] = off_one(params["exit_gate"]["b"])
    return params


def tokens_for(config, rows=2, seq=SEQ, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, config.vocab_size, size=(rows, seq + 1), dtype=np.int32))


def grad_norm(grads):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(grads))))


def program(config, params, tokens):
    """(loss, gradient norm, gradients) of the program's loss."""
    loss, grads = jax.jit(
        jax.value_and_grad(looped.next_token_loss), static_argnums=2)(
            params, tokens, config)
    return float(loss), grad_norm(grads), grads


def reference(config, params, tokens, fault=None):
    """(loss, gradient norm) of the benchmark's plain reference."""
    fields = fields_for(config)
    return jax.jit(lambda p, t: looplm.loss_and_grad_norm(
        p, t, fields, fault=fault))(params, tokens)


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("n_passes", [1, 2, 4])
def test_program_agrees_with_the_reference_in_float32(n_passes):
    config = config_for(n_passes)
    params, tokens = seeded_params(config), tokens_for(config)
    with jax.default_matmul_precision("highest"):
        loss, norm, _ = program(config, params, tokens)
    want, want_norm = reference(config, params, tokens)
    assert loss == pytest.approx(float(want), rel=1e-5)
    assert norm == pytest.approx(float(want_norm), rel=1e-4)


@pytest.mark.parametrize("n_passes", [1, 2, 4])
def test_program_agrees_with_the_reference_in_bf16(n_passes):
    """bf16 weights and activations, as the benchmark runs them, against
    the float32 reference on the same (bf16) weights."""
    config = config_for(n_passes, dtype=jnp.bfloat16)
    params, tokens = seeded_params(config), tokens_for(config)
    loss, norm, _ = program(config, params, tokens)
    want, want_norm = reference(config, params, tokens)
    assert loss == pytest.approx(float(want), rel=BF16_TOLERANCE["loss_rel"])
    assert norm == pytest.approx(
        float(want_norm), rel=BF16_TOLERANCE["grad_norm_rel"])


@pytest.mark.parametrize(
    "fault", ["one_pass_fewer", "pre_norm_only", "unnormed_state"])
def test_a_planted_fault_in_the_reference_breaks_the_agreement(fault):
    """The comparison is no tautology: each fault moves the reference by
    more than the bf16 limits the rehearsal compares under."""
    config = config_for(4)
    params, tokens = seeded_params(config), tokens_for(config)
    with jax.default_matmul_precision("highest"):
        loss, norm, _ = program(config, params, tokens)
    want, want_norm = reference(config, params, tokens, fault)
    loss_rel = abs(loss - float(want)) / abs(float(want))
    norm_rel = abs(norm - float(want_norm)) / abs(float(want_norm))
    assert (loss_rel > BF16_TOLERANCE["loss_rel"]
            or norm_rel > BF16_TOLERANCE["grad_norm_rel"]), (
                fault, loss_rel, norm_rel)
    with pytest.raises(ValueError, match="unknown fault"):
        looplm.next_token_loss(params, tokens, fields_for(config),
                               fault="no_such_fault")


# -- the exit distribution ----------------------------------------------------


def test_exit_mass_sums_to_one_for_every_token():
    """A batch of one position: the means over tokens are that token's
    own ``p(t)``."""
    config = config_for(4)
    params = seeded_params(config)
    for seed in range(4):
        tokens = tokens_for(config, rows=1, seq=1, seed=seed)
        _, stats = jax.jit(looped.loss_and_stats, static_argnums=2)(
            params, tokens, config)
        mass = np.asarray(stats["exit_mass"])
        assert mass.shape == (4,) and (mass > 0).all()
        assert mass.sum() == pytest.approx(1.0, abs=1e-6)
        entropy = -(mass * np.log(mass)).sum()
        assert float(stats["exit_entropy"]) == pytest.approx(entropy, rel=1e-5)
        assert np.asarray(stats["pass_nll"]).shape == (4,)


def test_the_last_gate_output_has_zero_gradient():
    """With one pass the gate's only output is the last: ``p(T)`` is what
    the earlier gates left, so nothing flows into the gate. With two
    passes the first output does take part."""
    params = seeded_params(config_for(1))
    tokens = tokens_for(config_for(1))
    _, _, grads = program(config_for(1), params, tokens)
    assert float(jnp.abs(grads["exit_gate"]["w"]).max()) == 0.0
    assert float(grads["exit_gate"]["b"]) == 0.0
    _, _, grads = program(config_for(2), params, tokens)
    assert float(jnp.abs(grads["exit_gate"]["w"]).max()) > 0.0
    assert float(grads["exit_gate"]["b"]) != 0.0


def test_one_pass_is_a_sandwich_norm_decoder():
    """``n_passes`` 1: the exit distribution is one point, and the loss is
    the plain next-token loss of the stack with its four norms a layer."""
    config = config_for(1)
    params, tokens = seeded_params(config), tokens_for(config)
    logits = llama.forward(params, tokens[:, :-1], config)
    assert float(looped.next_token_loss(params, tokens, config)) == (
        pytest.approx(float(llama.cross_entropy(logits, tokens[:, 1:])),
                      rel=1e-6))


# -- the parameters -----------------------------------------------------------


def test_parameter_count_does_not_depend_on_the_passes():
    counts = set()
    for n_passes in (1, 2, 4):
        config = config_for(n_passes)
        shapes = jax.eval_shape(
            lambda k: looped.init_params(config, k), jax.random.PRNGKey(0))
        counts.add(sum(x.size for x in jax.tree.leaves(shapes)))
        assert looped.num_params(config) in counts
    assert len(counts) == 1
    # what the looped tree adds to the llama tree
    c = config_for(4)
    assert looped.num_params(c) - llama.num_params(c) == (
        2 * c.n_layers * c.dim + c.dim + 1)
    params = looped.init_params(c, jax.random.PRNGKey(0))
    assert params["exit_gate"]["b"].shape == ()
    # embedding and head from different keys
    assert not np.allclose(np.asarray(params["tok_embed"]).T,
                           np.asarray(params["lm_head"]))
    axes = looped.param_logical_axes(c)
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, axes,
                     is_leaf=lambda x: isinstance(x, tuple))
    ) == jax.tree.structure(jax.tree.map(lambda _: 0, params))


def untied_loss(per_pass, rest, tokens, fields):
    """The reference's loss with a copy of the layers for every pass
    (``per_pass``: the layer tree with a leading pass axis)."""
    f = fields
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    passes = f["total_ut_steps"]
    with jax.default_matmul_precision("highest"):
        x = rest["tok_embed"][inputs]
        stay = jnp.ones(inputs.shape, jnp.float32)
        total = 0.0
        for t in range(passes):
            for n in range(f["num_hidden_layers"]):
                x = looplm._layer(
                    x, jax.tree.map(lambda a: a[t, n], per_pass), f, True)
            x = _rms_norm(x, rest["final_norm"], f["rms_norm_eps"])
            nll, gate = looplm._head(
                x, rest["lm_head"], rest["exit_gate"], targets)
            prob = stay if t == passes - 1 else gate * stay
            stay = stay * (1.0 - gate)
            total = total + prob * nll + (
                f["exit_entropy_beta"] * prob * jnp.log(prob))
        return jnp.mean(total)


def test_shared_gradient_is_the_sum_over_the_passes_of_an_untied_copy():
    config = config_for(3)
    params, tokens = seeded_params(config), tokens_for(config)
    fields = fields_for(config)
    with jax.default_matmul_precision("highest"):
        _, _, grads = program(config, params, tokens)
    per_pass = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (config.n_passes,) + a.shape),
        params["layers"])
    rest = {k: v for k, v in params.items() if k != "layers"}
    untied = jax.jit(lambda a, b, t: jax.grad(untied_loss)(a, b, t, fields))(
        per_pass, rest, tokens)
    for name, got in grads["layers"].items():
        want = np.asarray(untied[name]).sum(axis=0)
        scale = np.abs(want).max()
        np.testing.assert_allclose(
            np.asarray(got), want, atol=2e-5 * scale, err_msg=name)


def test_the_shared_gradient_is_summed_in_float32():
    """bf16 weights: autodiff alone would add the passes' cotangents in
    bf16. The loop closes over float32 copies, so every add over passes in
    the backward pass's loop carries float32 for the shared leaves."""
    config = config_for(4, dtype=jnp.bfloat16)
    params, tokens = seeded_params(config), tokens_for(config)
    _, _, grads = program(config, params, tokens)
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree.leaves(grads))
    jaxpr = jax.make_jaxpr(jax.grad(looped.next_token_loss), static_argnums=2)(
        params, tokens, config)
    # the backward loop over passes is the scan that carries the shared
    # leaves' cotangent sums: a w1-shaped float32 carry, and none in bf16
    w1 = params["layers"]["w1"].shape
    carries = [
        [v.aval for v in eqn.outvars[:eqn.params["num_carry"]]]
        for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"
        and eqn.params["length"] == config.n_passes]
    held = [a.dtype for avals in carries for a in avals if a.shape == w1]
    assert held and all(d == jnp.float32 for d in held), held


# -- through the normal path --------------------------------------------------


@pytest.fixture()
def fresh_watcher():
    reset_registry()
    compile_watch.reset_watcher()
    yield compile_watch.get_watcher()
    reset_registry()
    compile_watch.reset_watcher()


def looped_trainer(with_stats, devices=2):
    config = config_for(3, remat=True)
    plan = plan_mesh(devices)
    mesh = build_mesh(plan, devices=jax.devices()[:devices])
    params = shard_tree(
        mesh, looped.init_params(config, jax.random.PRNGKey(0)),
        looped.param_logical_axes(config))
    optimizer = optax.adamw(1e-2)
    trainer = ElasticTrainer(
        loss_fn=looped.make_loss_fn(config, mesh, with_stats=with_stats),
        optimizer=optimizer, global_batch_size=4 * plan.dp_total,
        micro_batch_per_replica=2)
    trainer.configure_for_world(plan)
    state = jax.block_until_ready(make_train_state(params, optimizer))
    batch = tokens_for(config, rows=4 * plan.dp_total).reshape(
        trainer.grad_accum_steps, trainer.micro_batch_global, SEQ + 1)
    return config, trainer, state, batch


@pytest.mark.parametrize("devices", [1, 2])
def test_three_trainer_steps_compile_once_and_lower_the_loss(
        fresh_watcher, devices):
    """One trace of the step whatever the mesh. On one device (the
    benchmark's cell) one compilation; over fsdp 2 the first step hands
    back another layout than ``make_train_state`` made and the second
    compiles for it, for every model (PERF.md section 6, PR 23, finding
    3): none after that."""
    config, trainer, state, batch = looped_trainer(True, devices)
    losses, results = [], []
    for _ in range(3):
        state, result = trainer.train_step(state, batch)
        results.append(result)
        losses.append(float(result.loss))
    assert losses[2] < losses[1] < losses[0]
    assert fresh_watcher.compile_count("trainer.train_step") == 1
    spans = [sp for sp in tracing.get_tracer().finished_spans()
             if sp.name == SpanName.TRAIN_STEP][-3:]
    assert [sp.attrs["passes"] for sp in spans] == [3, 3, 3]
    assert "compiles" in spans[0].attrs
    assert all("compiles" not in sp.attrs for sp in spans[devices:])
    # the step's stats: means over its microbatches, still on the device
    stats = results[-1].stats
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(stats))
    host = jax.device_get(stats)
    assert host["exit_mass"].shape == (3,)
    assert host["exit_mass"].sum() == pytest.approx(1.0, abs=1e-5)
    registry = MetricsRegistry()
    looped.publish_stats(host, registry)
    text = registry.render()
    for t in (1, 2, 3):
        assert f'dlrover_loop_exit_mass{{pass="{t}"}}' in text
        assert f'dlrover_loop_pass_nll{{pass="{t}"}}' in text
    assert "dlrover_loop_exit_entropy " in text


def test_a_scalar_loss_has_no_stats_and_the_same_loss(fresh_watcher):
    _, trainer, state, batch = looped_trainer(with_stats=False)
    _, scalar = trainer.train_step(state, batch)
    assert scalar.stats == {}
    _, trainer, state, batch = looped_trainer(with_stats=True)
    _, with_stats = trainer.train_step(state, batch)
    assert float(scalar.loss) == float(with_stats.loss)
    assert float(scalar.grad_norm) == float(with_stats.grad_norm)


JOB = f"loopedtest{os.getpid()}"


def test_flash_checkpoint_of_the_looped_state_is_bit_equal(tmp_path):
    """Memory save and restore of the trained state, the zero-dimensional
    gate bias and its moments beside ``step`` included."""
    _, trainer, state, batch = looped_trainer(with_stats=False)
    state, _ = trainer.train_step(state, batch)
    state = jax.block_until_ready(state)
    assert state["params"]["exit_gate"]["b"].shape == ()
    ckpt = Checkpointer(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0)
    try:
        assert ckpt.save_checkpoint(1, state, StorageType.MEMORY)
        assert ckpt.engine.wait_drained(60)
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)
        restored, step = ckpt.load_checkpoint(target)
        assert step == 1
        saved_leaves, saved_tree = jax.tree.flatten(state)
        got_leaves, got_tree = jax.tree.flatten(restored)
        assert saved_tree == got_tree
        for a, b in zip(saved_leaves, got_leaves):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.sharding == b.sharding
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    finally:
        unlink_shared_memory(shm_name(JOB, 0, 0))
