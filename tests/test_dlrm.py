"""DeepFM/DLRM recommender tests — the TPU-native counterpart of the
reference's criteo deepfm system-test workload
(examples/tensorflow/criteo_deeprec/deepfm.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models import dlrm
from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
from dlrover_tpu.parallel.sharding import shard_tree, spec_for


def _batch(key, n, config):
    return dlrm.synthetic_criteo_batch(key, n, config)


class TestModel:
    def test_forward_shapes_and_dtype(self):
        c = dlrm.DLRMConfig.tiny()
        params = dlrm.init_params(c, jax.random.PRNGKey(0))
        b = _batch(jax.random.PRNGKey(1), 32, c)
        logits = dlrm.forward(params, b["dense"], b["sparse"], c)
        assert logits.shape == (32,)
        assert logits.dtype == jnp.float32

    def test_hash_routes_fields_to_disjoint_stripes(self):
        c = dlrm.DLRMConfig.tiny()
        ids = jnp.arange(26, dtype=jnp.int32)[None, :] * 7919
        rows = dlrm.hash_features(ids, c)
        stripe = np.asarray(rows[0]) // c.hash_buckets
        np.testing.assert_array_equal(stripe, np.arange(26))
        assert int(rows.max()) < c.table_rows

    def test_num_params_matches_tree(self):
        c = dlrm.DLRMConfig.tiny()
        params = dlrm.init_params(c, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert actual == dlrm.num_params(c)

    def test_fm_term_matches_pairwise(self):
        # the sum-square trick equals the explicit Σ_{i<j} e_i∘e_j
        e = np.random.randn(4, 5, 3).astype(np.float32)
        s = e.sum(1)
        fast = 0.5 * (s * s - (e * e).sum(1))
        slow = np.zeros((4, 3), np.float32)
        for i in range(5):
            for j in range(i + 1, 5):
                slow += e[:, i] * e[:, j]
        np.testing.assert_allclose(fast, slow, atol=1e-4)

    def test_batch_auc_known_values(self):
        logits = jnp.array([0.9, 0.8, 0.1, 0.2])
        labels = jnp.array([1, 1, 0, 0])
        assert float(dlrm.batch_auc(logits, labels)) == 1.0
        labels = jnp.array([0, 0, 1, 1])
        assert float(dlrm.batch_auc(logits, labels)) == 0.0
        # degenerate single-class batch → 0.5
        assert float(dlrm.batch_auc(logits, jnp.ones(4))) == 0.5

    def test_learns_synthetic_signal(self):
        c = dlrm.DLRMConfig.tiny()
        params = dlrm.init_params(c, jax.random.PRNGKey(0))
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)

        @jax.jit
        def step(p, s, batch):
            loss, grads = jax.value_and_grad(dlrm.bce_loss)(p, batch, c)
            updates, s = opt.update(grads, s)
            return optax.apply_updates(p, updates), s, loss

        first = None
        for i in range(60):
            b = _batch(jax.random.PRNGKey(100 + i), 256, c)
            params, opt_state, loss = step(params, opt_state, b)
            if first is None:
                first = float(loss)
        b = _batch(jax.random.PRNGKey(999), 512, c)
        logits = dlrm.forward(params, b["dense"], b["sparse"], c)
        auc = float(dlrm.batch_auc(logits, b["label"]))
        assert float(loss) < first
        assert auc > 0.75, f"AUC {auc} — model failed to learn the signal"


class TestSharded:
    def test_table_shards_over_mesh_and_step_runs(self):
        plan = plan_mesh(len(jax.devices()), tp=2, fsdp=4)
        mesh = build_mesh(plan)
        c = dlrm.DLRMConfig.tiny()
        params = dlrm.init_params(c, jax.random.PRNGKey(0))
        axes = dlrm.param_logical_axes(c)
        params = shard_tree(mesh, params, axes)
        # the stacked table is row-sharded over tp (the PS-partitioner
        # analogue)
        table_shard = params["table"].addressable_shards[0]
        assert table_shard.data.shape[0] == c.table_rows // 2

        opt = optax.adam(1e-2)
        opt_state = opt.init(params)
        b = _batch(jax.random.PRNGKey(1), 64, c)
        b = jax.device_put(b, NamedSharding(mesh, P()))

        @jax.jit
        def step(p, s, batch):
            loss, grads = jax.value_and_grad(dlrm.bce_loss)(p, batch, c)
            updates, s = opt.update(grads, s)
            return optax.apply_updates(p, updates), s, loss

        params, opt_state, loss = step(params, opt_state, b)
        assert np.isfinite(float(loss))
        # sharding preserved through the step (no silent replication)
        # (the rule names ep beside tp; on this mesh ep has size 1)
        assert params["table"].sharding.is_equivalent_to(
            NamedSharding(mesh, spec_for(axes["table"])), 2)
