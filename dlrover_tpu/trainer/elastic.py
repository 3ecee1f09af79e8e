"""ElasticTrainer: fixed global batch under a changing world.

Reference: dlrover/trainer/torch/elastic/trainer.py:181 — ``ElasticTrainer``
keeps the *global* batch size constant as the DDP world grows/shrinks by
rescaling gradient-accumulation steps (``_set_gradient_accumulation_steps``
:307). TPU translation: the mesh re-forms (parallel/mesh.py) and this
trainer recomputes ``grad_accum = global_batch / (micro_batch × dp_total)``,
so optimization dynamics (tokens per optimizer step) are identical before
and after any elastic event.

The train step is one jit: ``lax.scan`` over the accumulation microbatches
(grads accumulated in f32), then one optimizer update — donated state, so
params/opt-state update in place in HBM.
"""

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from dlrover_tpu.common.constants import MetricLabel, SpanName
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.compile_watch import get_watcher
from dlrover_tpu.observability.memory import get_accountant
from dlrover_tpu.parallel.mesh import ElasticMeshManager, MeshPlan, plan_mesh


class TrainStepResult(NamedTuple):  # NamedTuple ⇒ a pytree, jit can return it
    loss: Any
    grad_norm: Any
    # what a loss_fn returned beside its loss, averaged over the step's
    # microbatches; device arrays the step path never reads back. Empty
    # for a scalar loss
    stats: Any = {}


def make_train_state(params, optimizer) -> Dict:
    opt_state = optimizer.init(params)
    # the trainer hands the optimizer f32 grads, which promote moments
    # made from bf16 params to f32 in the first update, for good. Start
    # them where that update leaves them, so the state's dtypes are a
    # fixed point of the step — else step 2 retraces, step 1's donation
    # cannot alias, and a checkpoint written after step 1 does not match
    # this function's restore target. Same numbers: bf16 to f32 is exact.
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params
    )
    _, settled = jax.eval_shape(optimizer.update, grads, opt_state, params)
    opt_state = jax.tree.map(
        lambda x, to: x if x.dtype == to.dtype else x.astype(to.dtype),
        opt_state, settled,
    )
    state = {
        "params": params,
        "opt_state": opt_state,
        "step": jnp.zeros((), dtype=jnp.int32),
    }
    # counters made from nothing land on the default device, but the step
    # returns them replicated over the params' mesh: put them there now,
    # so the state's layout is a fixed point of the step (else step 2
    # recompiles for the new input shardings, and a restored state is not
    # laid out like the saved one)
    mesh = next(
        (leaf.sharding.mesh for leaf in jax.tree.leaves(params)
         if isinstance(getattr(leaf, "sharding", None), NamedSharding)),
        None,
    )
    if mesh is None:
        return state
    replicated = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, replicated),
        state,
    )


class ElasticTrainer:
    def __init__(
        self,
        # loss_fn(params, microbatch) -> scalar, or (scalar, stats dict);
        # a dict ``span_attrs`` on it (models/looped.py: ``passes``) rides
        # on every train.step span. A scalar loss_fn may carry
        # ``with_stats``, its (scalar, stats) form, which the step takes
        # instead, and ``stats_gauges(read)``, called once here with a
        # function that reads the last step's stats back, for gauges
        # computed when the registry is read (models/moe.py
        # make_loss_fn): nothing on the step path reads them
        loss_fn: Callable,
        optimizer,          # optax GradientTransformation
        global_batch_size: int,
        micro_batch_per_replica: int,
        mesh_manager: Optional[ElasticMeshManager] = None,
    ):
        self._loss_fn = getattr(loss_fn, "with_stats", loss_fn)
        self._span_attrs = dict(getattr(loss_fn, "span_attrs", {}))
        gauges = getattr(loss_fn, "stats_gauges", None)
        self._keep_stats = gauges is not None
        self._last_stats = None
        if self._keep_stats:
            gauges(self._last_stats_on_host)
        self._optimizer = optimizer
        self.global_batch_size = global_batch_size
        self.micro_batch_per_replica = micro_batch_per_replica
        self._mesh_manager = mesh_manager
        self.grad_accum_steps = 1
        self._train_step = None
        self._mesh_version = 0

    def configure_for_world(self, plan: MeshPlan) -> int:
        """(Re)compute grad-accum for the current mesh
        (reference trainer.py:307 semantics)."""
        dp_total = plan.dp_total
        denom = self.micro_batch_per_replica * dp_total
        if self.global_batch_size % denom != 0:
            raise ValueError(
                f"global_batch_size={self.global_batch_size} not divisible "
                f"by micro_batch×dp_total={denom} — adjust micro batch or "
                f"constrain the world with node_unit"
            )
        self.grad_accum_steps = self.global_batch_size // denom
        self._train_step = None  # world changed ⇒ retrace
        logger.info(
            "elastic trainer: dp_total=%s grad_accum=%s (global batch %s)",
            dp_total, self.grad_accum_steps, self.global_batch_size,
        )
        return self.grad_accum_steps

    def apply_parallel_config(self, config) -> Optional[MeshPlan]:
        """Re-form the mesh from a re-planned ``ParallelConfig`` — the
        tuner-shipped JSON dict (agent/config_tuner.py) or the comm
        message itself. A ``mesh_version`` the trainer has not applied
        yet turns the (data, fsdp, tp) decomposition into a
        :class:`MeshPlan`, adopts it on the mesh manager (so later
        world-size replans keep the shape), and recomputes grad-accum.
        Returns the new plan, or None when nothing changed."""
        if isinstance(config, dict):
            def get(key):
                return config.get(key, 0)
        else:
            def get(key):
                return getattr(config, key, 0)
        version = int(get("mesh_version") or 0)
        data = max(1, int(get("mesh_data") or 0))
        fsdp = max(1, int(get("mesh_fsdp") or 0))
        tp = max(1, int(get("mesh_tp") or 0))
        if version <= self._mesh_version or data * fsdp * tp <= 1:
            return None
        plan = plan_mesh(data * fsdp * tp, tp=tp, fsdp=fsdp, dp=data)
        if self._mesh_manager is not None:
            self._mesh_manager.apply_plan(plan)
        self._mesh_version = version
        self.configure_for_world(plan)
        logger.info(
            "elastic trainer: mesh v%s applied — data=%s fsdp=%s tp=%s",
            version, data, fsdp, tp,
        )
        return plan

    @property
    def micro_batch_global(self) -> int:
        """Rows per microbatch across the whole mesh."""
        return self.global_batch_size // self.grad_accum_steps

    def _build_step(self):
        loss_fn = self._loss_fn
        optimizer = self._optimizer
        accum = self.grad_accum_steps

        def step_fn(state, batch):
            """batch: (accum, micro_batch_global, ...) — leading accum axis
            iterated sequentially, second axis sharded over data axes."""
            params = state["params"]

            def loss_and_stats(p, microbatch):
                out = loss_fn(p, microbatch)
                return out if isinstance(out, tuple) else (out, {})

            def micro_step(carry, microbatch):
                grad_acc, loss_acc = carry
                (loss, stats), grads = jax.value_and_grad(
                    loss_and_stats, has_aux=True)(params, microbatch)
                grads = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
                )
                return (grads, loss_acc + loss), stats

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss_sum), stats = jax.lax.scan(
                micro_step, (zeros, jnp.zeros((), jnp.float32)), batch
            )
            stats = jax.tree.map(lambda s: s.mean(axis=0), stats)
            grads = jax.tree.map(lambda g: g / accum, grads)
            grad_norm = optax_global_norm(grads)
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], params
            )
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
                params, updates,
            )
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            }
            return new_state, TrainStepResult(
                loss_sum / accum, grad_norm, stats)

        return jax.jit(step_fn, donate_argnums=(0,))

    def _last_stats_on_host(self):
        """The last step's stats read back, None before the first step."""
        if self._last_stats is None:
            return None
        return jax.device_get(self._last_stats)

    def _register_state(self, state) -> None:
        """Claim the training state in the device-memory ledger: params,
        optimizer state, and the f32 grad accumulator the scan carries
        (the trainer's known activation workspace). Re-claimed at every
        retrace — buffer shapes only change when the world does."""
        try:
            params_b = sum(int(leaf.nbytes)
                           for leaf in jax.tree.leaves(state["params"]))
            opt_b = sum(int(leaf.nbytes)
                        for leaf in jax.tree.leaves(state["opt_state"]))
            accum_b = sum(4 * int(leaf.size)
                          for leaf in jax.tree.leaves(state["params"]))
        except (KeyError, AttributeError, TypeError):
            return  # toy states without nbytes-bearing leaves
        acc = get_accountant()
        acc.register(MetricLabel.MEM_PARAMS, "trainer/params", params_b)
        acc.register(MetricLabel.MEM_OPT_STATE, "trainer/opt_state", opt_b)
        acc.register(MetricLabel.MEM_ACTIVATIONS, "trainer/grad_accum",
                     accum_b)

    def train_step(self, state, batch):
        built = self._train_step is None
        if built:
            self._train_step = self._build_step()
            self._register_state(state)
        shape = tuple(getattr(batch, "shape", ()) or ())
        watcher = get_watcher()
        traced = tracing.enabled()
        with tracing.span(
            SpanName.TRAIN_STEP, accum=self.grad_accum_steps,
            **self._span_attrs,
        ) as sp:
            # structured compile signature: a varying rows-per-microbatch
            # is exactly the ragged-batch storm the watcher attributes
            watcher.note(
                "trainer.train_step",
                accum=self.grad_accum_steps,
                batch=shape[1] if len(shape) > 1 else 0,
                seq_len=shape[2] if len(shape) > 2 else 0,
            )
            # the counter is read only to fill the span's attribute
            requests = watcher.compile_requests() if traced else 0
            out = self._train_step(state, batch)
            if self._keep_stats:
                self._last_stats = out[1].stats
            if traced:
                compiles = watcher.compile_requests() - requests
                if compiles:  # what the backend was asked, cached or not
                    sp.attrs["compiles"] = compiles
            return out


def optax_global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))
