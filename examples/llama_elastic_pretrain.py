"""End-to-end elastic Llama pretraining on the full stack.

Wires every L1–L4 feature together the way a real job would (the
counterpart of the reference's examples/pytorch/ jobs):

- `worker.init()` — agent env → jax.distributed bootstrap + master client
- mesh planning from the live world size (tp/sp fixed, fsdp absorbs)
- `ElasticTrainer` — fixed global batch via grad-accum, donated train state
- `ElasticDataLoader` + `ElasticDistributedSampler` — resumable, re-tunable
- Flash Checkpoint — async memory saves every step, storage every N
- training-event span + per-step publishing (goodput accounting, hang
  detection feed)
- `MODEL=looped` trains the weight-shared looped model (models/looped.py)
  on the same path: the step's exit statistics come back with the loss
  and are published as registry gauges

Run (single host, 2 workers on CPU for a quick look):

    JAX_PLATFORMS=cpu python -m dlrover_tpu.agent.run --standalone \
        --nproc-per-node=2 --ckpt-dir /tmp/llama_ckpt \
        examples/llama_elastic_pretrain.py

On a TPU pod slice, the same script runs under the operator-launched
master with `dtpu-run` on every host — nothing changes in user code.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu import worker
from dlrover_tpu.ckpt.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models import llama, looped
from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
from dlrover_tpu.parallel.sharding import global_batch_from_local, shard_tree
from dlrover_tpu.trainer.data import ElasticDataLoader, ElasticDistributedSampler
from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

TOTAL_STEPS = int(os.getenv("TRAIN_STEPS", "30"))
GLOBAL_BATCH = int(os.getenv("GLOBAL_BATCH", "8"))
SEQ_LEN = int(os.getenv("SEQ_LEN", "64"))
MODEL = os.getenv("MODEL", "llama")  # or "looped"
CKPT_EVERY = 10


def synthetic_dataset(vocab: int, n: int = 4096):
    rng = np.random.default_rng(0)
    return rng.integers(0, vocab, size=(n, SEQ_LEN + 1), dtype=np.int32)


def main() -> int:
    ctx = worker.init()
    n_devices = len(jax.devices())
    if MODEL not in ("llama", "looped"):
        raise SystemExit(f"unknown MODEL {MODEL!r}; llama or looped")
    model = looped if MODEL == "looped" else llama
    shapes = dict(
        vocab_size=2048, dim=128, n_layers=4, n_heads=4, n_kv_heads=2,
        ffn_dim=256, max_seq_len=SEQ_LEN, remat=True, dtype=jnp.float32,
    )
    if MODEL == "looped":
        config = looped.LoopedConfig(**shapes, n_passes=2)
    else:
        config = llama.LlamaConfig(**shapes)

    # mesh from the live world: model axes fixed, fsdp absorbs the rest
    plan = plan_mesh(n_devices, tp=1, sp=1)
    mesh = build_mesh(plan)
    params = shard_tree(
        mesh, model.init_params(config, jax.random.PRNGKey(0)),
        model.param_logical_axes(config),
    )
    if MODEL == "looped":
        loss_fn = looped.make_loss_fn(config, mesh, with_stats=True)
    else:
        def loss_fn(p, t):
            return llama.next_token_loss(p, t, config, mesh)

    trainer = ElasticTrainer(
        loss_fn=loss_fn,
        optimizer=optax.adamw(3e-4),
        global_batch_size=GLOBAL_BATCH,
        micro_batch_per_replica=max(1, GLOBAL_BATCH // (2 * plan.dp_total)),
    )
    trainer.configure_for_world(plan)
    state = make_train_state(params, trainer._optimizer)

    # sampler state rides the checkpoint: a restarted job resumes the data
    # stream where it left off instead of replaying consumed batches
    data = synthetic_dataset(config.vocab_size)
    sampler = ElasticDistributedSampler(
        len(data), num_replicas=ctx.world_size, rank=ctx.rank, shuffle=True,
    )
    global_bs = trainer.micro_batch_global * trainer.grad_accum_steps
    per_host = global_bs // ctx.world_size

    ckpt = Checkpointer(os.getenv("CKPT_DIR", "/tmp/llama_ckpt"))
    state["sampler_epoch"] = jnp.zeros((), jnp.int32)
    state["sampler_completed"] = jnp.zeros((), jnp.int32)
    state, start_step = ckpt.load_checkpoint(state)
    sampler.load_state_dict({
        "epoch": int(state["sampler_epoch"]),
        "completed": int(state["sampler_completed"]),
    })
    if start_step >= 0 and ctx.rank == 0:
        print(f"resumed from step {start_step} "
              f"(sampler at {int(state['sampler_completed'])})", flush=True)

    # each host loads its 1/world_size of the global batch; the library
    # assembles the sharded global array (multi-host data path)
    loader = ElasticDataLoader(data, batch_size=per_host, sampler=sampler)

    step = max(start_step, 0)
    with ctx.training_span(steps=TOTAL_STEPS):
        for batch in loader:
            if step >= TOTAL_STEPS:
                break
            step += 1
            sampler.record_batch(global_bs)
            tokens = global_batch_from_local(mesh, batch)
            tokens = tokens.reshape(
                trainer.grad_accum_steps, trainer.micro_batch_global,
                SEQ_LEN + 1,
            )
            state, result = trainer.train_step(state, tokens)
            sd = sampler.state_dict()
            state["sampler_epoch"] = jnp.int32(sd["epoch"])
            state["sampler_completed"] = jnp.int32(sd["completed"])
            ckpt.save_checkpoint(
                step, state,
                storage_type=StorageType.DISK if step % CKPT_EVERY == 0
                else StorageType.MEMORY,
            )
            ctx.publish_step(step)
            if ctx.is_leader:
                # cross-host RPC only from the leader; other ranks' progress
                # reaches the master via the agent's SharedDict forward
                ctx.report_step(step)
                if step % 10 == 0:
                    # one read-back for the loss and whatever came with it
                    loss, stats = jax.device_get((result.loss, result.stats))
                    print(f"step {step}: loss {float(loss):.4f}", flush=True)
                    if stats:
                        looped.publish_stats(stats)
                        print(f"step {step}: exit mass "
                              f"{np.round(stats['exit_mass'], 3).tolist()}",
                              flush=True)
    if ctx.is_leader:
        print(f"DONE at step {step}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
