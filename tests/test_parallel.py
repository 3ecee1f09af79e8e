"""Parallelism layer tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src import monitoring
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama, mnist
from dlrover_tpu.parallel.mesh import (
    ElasticMeshManager,
    build_mesh,
    plan_mesh,
)
from dlrover_tpu.parallel.ring_attention import (
    full_causal_attention,
    ring_attention,
)
from dlrover_tpu.parallel.sharding import (
    batch_sharding,
    shard_tree,
    spec_for,
    tree_shardings,
)
from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state


class TestMeshPlan:
    def test_fsdp_absorbs_remainder(self):
        plan = plan_mesh(8, tp=2)
        assert plan.axes == {
            "dcn": 1, "pp": 1, "dp": 1, "fsdp": 4, "ep": 1, "sp": 1,
            "tp": 2,
        }
        assert plan.dp_total == 4

    def test_explicit_dp(self):
        plan = plan_mesh(8, tp=2, dp=2)
        assert plan.size("fsdp") == 2 and plan.size("dp") == 2

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            plan_mesh(6, tp=4)

    def test_elastic_replan(self):
        mgr = ElasticMeshManager(tp=2, sp=1)
        plan8 = mgr.replan(8)
        assert plan8.dp_total == 4
        # world shrinks to 6 → use 6 (divisible by tp=2)
        plan6 = mgr.replan(6)
        assert plan6.dp_total == 3 and plan6.n_devices == 6
        # world shrinks to 5 → only 4 usable
        plan4 = mgr.replan(5)
        assert plan4.n_devices == 4
        assert mgr.usable_devices(5) == 4

    def test_min_unit(self):
        mgr = ElasticMeshManager(tp=2, pp=2)
        assert mgr.min_unit == 4
        with pytest.raises(ValueError):
            mgr.replan(3)


class TestShardingRules:
    def test_spec_mapping(self):
        assert spec_for(("embed", "heads")) == P("fsdp", ("ep", "tp"))
        # on a mesh without an expert group the heads read tp alone
        one = build_mesh(plan_mesh(1))
        assert spec_for(("embed", "heads", "kv_heads", "vocab"), mesh=one) \
            == P("fsdp", "tp", "tp", ("ep", "tp"))
        tp2 = build_mesh(plan_mesh(8, tp=2))
        assert spec_for(("heads",), mesh=tp2) == P("tp")
        ep2 = build_mesh(plan_mesh(8, ep=2))
        assert spec_for(("heads", "kv_heads"), mesh=ep2) \
            == P(("ep", "tp"), ("ep", "tp"))
        # layers are stage-major (pp) so pipeline shard_map needs no
        # repartition; on pp=1 meshes the axis is size 1 — a no-op
        assert spec_for(("layers", "norm")) == P("pp", None)
        assert spec_for(("batch", "seq")) == P(("dcn", "dp", "fsdp"), "sp")

    def test_shard_llama_params(self):
        plan = plan_mesh(8, tp=2)
        mesh = build_mesh(plan)
        config = llama.LlamaConfig.tiny()
        params = llama.init_params(config, jax.random.PRNGKey(0))
        sharded = shard_tree(
            mesh, params, llama.param_logical_axes(config)
        )
        wq = sharded["layers"]["wq"]
        assert wq.sharding.spec == P("pp", "fsdp", "tp")
        # each device holds 1/8 of wq
        assert wq.addressable_shards[0].data.size == wq.size // 8


class TestRingAttention:
    def test_matches_dense_oracle(self):
        plan = plan_mesh(8, sp=8)
        mesh = build_mesh(plan)
        B, H, S, D = 2, 4, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (
            jax.random.normal(kk, (B, H, S, D), dtype=jnp.float32)
            for kk in ks
        )
        ref = full_causal_attention(q, k, v)
        spec = P(("dp", "fsdp"), "tp", "sp", None)
        qs, ks_, vs = (
            jax.device_put(t, NamedSharding(mesh, spec)) for t in (q, k, v)
        )
        out = ring_attention(qs, ks_, vs, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_under_jit(self):
        plan = plan_mesh(4, sp=4)
        mesh = build_mesh(plan)
        B, H, S, D = 1, 2, 32, 8
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (
            jax.random.normal(kk, (B, H, S, D), dtype=jnp.float32)
            for kk in ks
        )
        spec = P(("dp", "fsdp"), "tp", "sp", None)
        sh = NamedSharding(mesh, spec)
        fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))
        out = fn(*(jax.device_put(t, sh) for t in (q, k, v)))
        ref = full_causal_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestLlama:
    def test_forward_shapes_and_finite(self):
        config = llama.LlamaConfig.tiny()
        params = llama.init_params(config, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size
        )
        logits = llama.forward(params, tokens, config)
        assert logits.shape == (2, 16, config.vocab_size)
        assert logits.dtype == jnp.float32
        assert bool(jnp.isfinite(logits).all())

    def test_sharded_forward_matches_single_device(self):
        # f32 so sharded vs single-device reduction order stays comparable
        config = llama.LlamaConfig(
            **{**llama.LlamaConfig.tiny().__dict__, "dtype": jnp.float32}
        )
        params = llama.init_params(config, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 16), 0, config.vocab_size
        )
        ref = llama.forward(params, tokens, config)
        plan = plan_mesh(8, tp=2)
        mesh = build_mesh(plan)
        sharded = shard_tree(mesh, params, llama.param_logical_axes(config))
        tok_sharded = jax.device_put(tokens, batch_sharding(mesh))
        fn = jax.jit(lambda p, t: llama.forward(p, t, config, mesh))
        out = fn(sharded, tok_sharded)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3
        )

    def test_ring_attention_forward(self):
        config = llama.LlamaConfig(
            **{**llama.LlamaConfig.tiny().__dict__, "dtype": jnp.float32}
        )
        ring_config = llama.LlamaConfig(
            **{**config.__dict__, "use_ring_attention": True}
        )
        params = llama.init_params(config, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size
        )
        ref = llama.forward(params, tokens, config)
        plan = plan_mesh(8, sp=2, tp=2)
        mesh = build_mesh(plan)
        sharded = shard_tree(mesh, params, llama.param_logical_axes(config))
        tok_sharded = jax.device_put(tokens, batch_sharding(mesh))
        fn = jax.jit(lambda p, t: llama.forward(p, t, ring_config, mesh))
        out = fn(sharded, tok_sharded)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3
        )

    def test_num_params_llama7b_scale(self):
        n = llama.num_params(llama.LlamaConfig.llama7b())
        assert 6.5e9 < n < 7.5e9


class TestElasticTrainer:
    def _data(self, key, n, accum, micro):
        x = jax.random.normal(key, (n, 8))
        w_true = jnp.arange(8.0)
        y = (x @ w_true > 0).astype(jnp.int32)
        return x[: accum * micro].reshape(accum, micro, 8), y[: accum * micro].reshape(accum, micro)

    def test_grad_accum_rescale_keeps_global_batch(self):
        trainer = ElasticTrainer(
            loss_fn=lambda p, b: 0.0,
            optimizer=optax.sgd(0.1),
            global_batch_size=64,
            micro_batch_per_replica=2,
        )
        assert trainer.configure_for_world(plan_mesh(8)) == 4  # 64/(2*8)
        assert trainer.configure_for_world(plan_mesh(4)) == 8  # 64/(2*4)
        assert trainer.micro_batch_global * trainer.grad_accum_steps == 64

    def test_indivisible_world_raises(self):
        trainer = ElasticTrainer(
            loss_fn=lambda p, b: 0.0,
            optimizer=optax.sgd(0.1),
            global_batch_size=64,
            micro_batch_per_replica=3,
        )
        with pytest.raises(ValueError):
            trainer.configure_for_world(plan_mesh(8))

    def test_training_reduces_loss(self):
        config = mnist.MnistConfig(input_dim=8, hidden_dim=16, n_classes=2)
        params = mnist.init_params(config, jax.random.PRNGKey(0))
        trainer = ElasticTrainer(
            loss_fn=mnist.loss_fn,
            optimizer=optax.adam(1e-2),
            global_batch_size=32,
            micro_batch_per_replica=2,
        )
        plan = plan_mesh(8)
        trainer.configure_for_world(plan)
        accum = trainer.grad_accum_steps
        micro = trainer.micro_batch_global
        state = make_train_state(params, trainer._optimizer)
        key = jax.random.PRNGKey(42)
        xs = jax.random.normal(key, (accum, micro, 8))
        w_true = jnp.arange(8.0)
        ys = (jnp.einsum("amf,f->am", xs, w_true) > 0).astype(jnp.int32)
        batch = {"x": xs, "y": ys}
        losses = []
        for _ in range(30):
            state, result = trainer.train_step(state, batch)
            losses.append(float(result.loss))
        assert losses[-1] < losses[0] * 0.5
        assert int(state["step"]) == 30

    def test_step_runs_on_sharded_mesh(self):
        config = mnist.MnistConfig(input_dim=8, hidden_dim=16, n_classes=2)
        params = mnist.init_params(config, jax.random.PRNGKey(0))
        plan = plan_mesh(8, tp=2)
        mesh = build_mesh(plan)
        params = shard_tree(mesh, params, mnist.param_logical_axes(config))
        trainer = ElasticTrainer(
            loss_fn=mnist.loss_fn,
            optimizer=optax.adam(1e-2),
            global_batch_size=16,
            micro_batch_per_replica=2,
        )
        trainer.configure_for_world(plan)
        state = make_train_state(params, trainer._optimizer)
        accum, micro = trainer.grad_accum_steps, trainer.micro_batch_global
        xs = jax.random.normal(jax.random.PRNGKey(1), (accum, micro, 8))
        ys = (xs.sum(-1) > 0).astype(jnp.int32)
        state, result = trainer.train_step(state, {"x": xs, "y": ys})
        assert bool(jnp.isfinite(result.loss))

    def test_bf16_params_start_with_the_f32_moments_step_one_gives(self):
        """The trainer's f32 grads promote adamw's moments to f32 in the
        first update. ``make_train_state`` starts them there: the state is
        a fixed point of the step (one program, same dtypes and layout out
        as in) and the numbers are those of optax's own promotion."""
        config = mnist.MnistConfig(input_dim=8, hidden_dim=16, n_classes=2)
        plan = plan_mesh(8, tp=2)
        mesh = build_mesh(plan)
        params = shard_tree(
            mesh,
            jax.tree.map(lambda p: p.astype(jnp.bfloat16),
                         mnist.init_params(config, jax.random.PRNGKey(0))),
            mnist.param_logical_axes(config),
        )
        optimizer = optax.adamw(1e-2)

        def run(state):
            trainer = ElasticTrainer(
                loss_fn=mnist.loss_fn, optimizer=optimizer,
                global_batch_size=16, micro_batch_per_replica=2,
            )
            trainer.configure_for_world(plan)
            accum, micro = trainer.grad_accum_steps, trainer.micro_batch_global
            xs = jax.random.normal(jax.random.PRNGKey(1), (accum, micro, 8))
            batch = {"x": xs, "y": (xs.sum(-1) > 0).astype(jnp.int32)}
            compiles = []

            def on_compile(event, _seconds, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    compiles.append(event)

            monitoring.register_event_duration_secs_listener(on_compile)
            try:
                state, _ = trainer.train_step(state, batch)
                first = len(compiles)
                for _ in range(2):
                    state, _ = trainer.train_step(state, batch)
            finally:
                monitoring.unregister_event_duration_listener(on_compile)
            return state, first, len(compiles) - first

        def same_layout(a, b):
            return all(
                x.dtype == y.dtype
                and x.sharding.is_equivalent_to(y.sharding, x.ndim)
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        state = make_train_state(params, optimizer)
        adam = state["opt_state"][0]
        assert {x.dtype for x in jax.tree.leaves((adam.mu, adam.nu))} == {
            jnp.dtype(jnp.float32)}
        assert same_layout(
            adam.mu, jax.tree.map(lambda p: p.astype(jnp.float32), params))
        settled, first, later = run(jax.tree.map(jnp.copy, state))
        assert same_layout(settled, state)
        assert first >= 1 and later == 0, "a step after the first compiled"
        # the parent's way: bf16 moments, which step 1 promotes for good
        promoted, _, later = run(
            {**state, "opt_state": optimizer.init(params)})
        assert later >= 1  # ... so step 2 compiles a second program
        for a, b in zip(jax.tree.leaves(settled), jax.tree.leaves(promoted)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMultiSlice:
    """dcn (cross-slice data parallel) — the multi-pod hybrid mesh."""

    def test_plan_and_mesh_shape(self):
        plan = plan_mesh(8, tp=2, dcn=2)
        assert plan.size("dcn") == 2 and plan.size("fsdp") == 2
        assert plan.dp_total == 4  # dcn × fsdp replicas of the batch
        mesh = build_mesh(plan)
        assert mesh.shape["dcn"] == 2
        # slice-major: the dcn axis maps contiguous device blocks, so
        # every intra-slice axis stays inside one block (ICI on real pods)
        devs = mesh.devices.reshape(2, -1)
        ids0 = {d.id for d in devs[0]}
        ids1 = {d.id for d in devs[1]}
        assert max(ids0) < min(ids1)

    def test_dcn_step_matches_single_slice(self):
        """A dcn=2 train step computes the same update as dcn=1: the
        cross-slice gradient all-reduce is exact, only the layout moves."""
        import optax

        import dataclasses

        # f32 everywhere: the assertion is about collective EXACTNESS
        # (same update either layout), so keep dtype drift out of it
        config = dataclasses.replace(
            llama.LlamaConfig.tiny(), dtype=jnp.float32
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, config.vocab_size
        )
        results = {}
        for dcn in (1, 2):
            plan = plan_mesh(8, tp=2, dcn=dcn)
            mesh = build_mesh(plan)
            params = shard_tree(
                mesh, llama.init_params(config, jax.random.PRNGKey(0)),
                llama.param_logical_axes(config),
            )
            opt = optax.sgd(0.1)
            opt_state = opt.init(params)
            batch = jax.device_put(
                tokens, NamedSharding(mesh, P(("dcn", "dp", "fsdp"), None))
            )

            @jax.jit
            def step(p, s, t):
                loss, g = jax.value_and_grad(
                    lambda q: llama.next_token_loss(q, t, config)
                )(p)
                u, s = opt.update(g, s)
                return optax.apply_updates(p, u), loss

            new_params, loss = step(params, opt_state, batch)
            results[dcn] = (
                float(loss),
                np.asarray(jax.tree.leaves(new_params)[0], dtype=np.float32),
            )
        assert abs(results[1][0] - results[2][0]) < 1e-5
        np.testing.assert_allclose(
            results[1][1], results[2][1], atol=2e-5
        )

    def test_slice_loss_shrinks_dcn(self):
        mgr = ElasticMeshManager(tp=2, dcn=2)
        assert mgr.replan(8).size("dcn") == 2
        # half the fleet gone as a whole slice: still two (smaller) slices
        assert mgr.replan(4).size("dcn") == 2
        # 6 devices can't form two equal tp=2 slices (3 per slice) —
        # dcn elasticity falls back to one flat world rather than failing
        plan = mgr.replan(6)
        assert plan.size("dcn") == 1 and plan.n_devices == 6
