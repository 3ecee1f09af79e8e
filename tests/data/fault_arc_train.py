"""Worker script of ``tests/test_fault_arc_spans.py``.

    fault_arc_train.py <ckpt_dir> <out_file> <ring_prefix>

Memory saves only, each waited for, so the agent's breakpoint persist is
what brings a frame to the disk. The first incarnation dies at
``CRASH_AT_STEP`` (-1: never); every incarnation writes its tracer's ring
to ``<ring_prefix>.<restart count>`` once its restore returned. The state
is one float32 matrix of ``W_SIDE`` x ``W_SIDE``, so that the frame's read,
CRC pass and write take what a persist takes and not the IPC around it.
"""

import json
import os
import sys

import jax.numpy as jnp

from dlrover_tpu import worker
from dlrover_tpu.ckpt import Checkpointer, StorageType
from dlrover_tpu.observability import tracing

ctx = worker.init()
ckpt_dir, out_file, ring_prefix = sys.argv[1:4]
crash_step = int(os.environ["CRASH_AT_STEP"])
side = int(os.environ["W_SIDE"])

ckpt = Checkpointer(ckpt_dir)
state = {"w": jnp.zeros((side, side), jnp.float32), "step": 0}
state, step = ckpt.load_checkpoint(state)
with open(f"{ring_prefix}.{ctx.restart_count}", "w") as f:
    json.dump([sp.to_dict() for sp in tracing.get_tracer().finished_spans()],
              f)
start = step + 1 if step >= 0 else 0
for s in range(start, 6):
    state = {"w": state["w"] + 1.0, "step": s}
    ckpt.save_checkpoint(s, state, StorageType.MEMORY)
    ckpt.engine.wait_drained()
    if s == crash_step and ctx.restart_count == 0:
        os._exit(7)

with open(out_file, "w") as f:
    f.write(f"done w={float(state['w'][0, 0])} start={start} "
            f"restarts={ctx.restart_count}")
