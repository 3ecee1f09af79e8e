"""Job kind ``train``: the worker's normal entry points in one process.

``worker.init()`` -> mesh from the live devices -> jitted, sharded init
from the seed -> the float32 reference check -> ``ElasticTrainer`` ->
warm-up -> the measured window -> (flash-save traffic) a warm-up restore
from shm, then the timed ones, each compared bit for bit. No agent, no
child process.

What the architecture computes is its family's to say (``run.py``
``FAMILY_CONTRACT``): this file asks ``family.reference(fields, seq)`` for
the plain computation, ``family.param_count(fields)`` for what the state
must hold, ``family.train_flops_per_token`` and
``family.flash_attention_flops`` for the counts the readers divide by
(``job["train_flops_per_token"]``, ``job["flash_fwd_flops"]``,
``job["flash_bwd_flops"]``: no reader imports a family).

Traffic parameters (``benchmarks/traffic/<name>.json``):

- ``seq``, ``grad_accum``, ``rows_per_replica``: a step trains
  ``grad_accum * rows_per_replica * data replicas * seq`` tokens;
- ``save_every_steps``: 0 for none, else a memory save after every N
  steps. The window is then whole cycles of N steps and one save;
- ``restore_warmups``: how many ``load_checkpoint`` calls come first
  once the window has closed, untimed as restores: the first restore of
  a process loads (or, in a fresh checkout, compiles) the programs that
  rebuild large leaves and faults its staging chunks in, which is
  warm-up like a step's compilation. Their seconds are added to
  ``setup_s`` and kept in ``job["restore_warmups_s"]``; each is compared
  bit for bit like a timed one. They come after the window and not
  before it so that the window's memory peak stays the snapshot's;
- ``restores_after_window``: how many timed ``load_checkpoint`` calls
  follow, one after another, each into a fresh target and compared bit
  for bit; ``restore_s`` is their total time over their number, and a
  note gives each;
- ``trace_steps``: how many steps the ``--trace 1`` run profiles.

The loop keeps one step in flight: it dispatches step k+1, then blocks
on step k's loss, as a trainer that logs the previous step's loss does.
A step's time is the interval between successive completions. Before a
save the loop blocks on the step in flight, so the stall is timed from a
quiet device and no step interval holds a save.
"""

import contextlib
import hashlib
import shutil
import tempfile
import time
import types

from benchmarks.harness import stats

STEP_MODULE = "step_fn"  # ElasticTrainer._build_step's jitted function
RESTORE_HIST = "dlrover_ckpt_restore_seconds{source=shm}"


def _batch_maker(np, mesh, vocab, rows, accum, seq, seed):
    from dlrover_tpu.parallel.sharding import global_batch_from_local

    def batch_for(step):
        rng = np.random.default_rng([seed, step])
        local = rng.integers(0, vocab, size=(accum * rows, seq + 1),
                             dtype=np.int32)
        return global_batch_from_local(mesh, local).reshape(
            accum, rows, seq + 1)

    return batch_for


def bits_of(jax, jnp):
    """``x -> x``'s bit patterns as unsigned integers of its own width."""
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
    return lambda x: jax.lax.bitcast_convert_type(x, uint[x.dtype.itemsize])


def _bits_equal_fn(jax, jnp):
    """(every bit equal, wrap-around sum of a's bits, of b's) over two
    trees of equal structure, on the device: a digest that costs no
    transfer. Equality is of bit patterns, so it is exact for NaNs too."""
    bits = bits_of(jax, jnp)

    def compare(a, b):
        same, sum_a, sum_b = jnp.bool_(True), jnp.uint32(0), jnp.uint32(0)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            bx, by = bits(x), bits(y)
            same = same & jnp.all(bx == by)
            sum_a = sum_a + jnp.sum(bx.astype(jnp.uint32))
            sum_b = sum_b + jnp.sum(by.astype(jnp.uint32))
        return same, sum_a, sum_b

    return jax.jit(compare)


class _Tracer:
    """Profiles ``steps`` steps inside the window of a ``--trace 1`` run:
    in a steady loop the first ones after a third of the window; in a
    save cycle the last half before a save and the first half after it.
    The seconds its own start and stop hold the loop are kept apart."""

    def __init__(self, jax, enabled, steps, after_s, every):
        self._jax, self._steps, self._after = jax, steps, after_s
        self._every = every
        self.state = "armed" if enabled else "off"
        self.dir = None
        self.overhead_s = 0.0
        self._left = 0

    @property
    def on(self):
        return self.state == "on"

    def before_step(self, elapsed, index_in_cycle):
        if self.state == "armed" and elapsed >= self._after:
            half = self._steps // 2
            if self._every and index_in_cycle != self._every - half:
                return
            self.dir = tempfile.mkdtemp(prefix="dlrover_bench_trace_")
            t = time.monotonic()
            self._jax.profiler.start_trace(self.dir)
            self.overhead_s += time.monotonic() - t
            self.state, self._left = "on", self._steps
        elif self.state == "on":
            self._left -= 1
            if self._left <= 0:
                self.stop()

    def stop(self):
        if self.state != "on":
            return
        t = time.monotonic()
        self._jax.profiler.stop_trace()
        self.overhead_s += time.monotonic() - t
        self.state = "done"


def run(env) -> dict:
    """``env``: ``args`` (seed, seconds, trace, rehearsal), ``cell`` (the
    BENCHMARK.json workload entry), ``fields`` (configuration file),
    ``traffic`` (traffic file), ``family`` (module), ``t_start``
    (monotonic, process start), ``note`` (prints an earlier line),
    ``root`` (the checkout)."""
    with contextlib.ExitStack() as cleanup:  # shm frame, work directory
        j = bootstrap(env)
        build_model(j)
        check_reference(j)
        build_trainer(j)
        warm_up(j)
        if j.every:
            open_checkpointer(j, own_checkpointer(j, cleanup))
        window(j, cleanup)
        restores(j)
        return result(j)


# The phases below share one namespace ``j``. ``jobs/resume.py`` runs the
# same ones in a worker under the agent, in another order after a restart.


def bootstrap(env):
    """``worker.init()``, the devices, the compile listener, and what the
    traffic file says of the job."""
    import jax

    from dlrover_tpu import worker

    j = types.SimpleNamespace(
        env=env, args=env["args"], fields=env["fields"],
        traffic=env["traffic"], family=env["family"], note=env["note"],
        jax=jax)
    j.worker = worker.init()  # the compile cache, as every worker gets it
    j.t_init_returned = time.monotonic()  # the backend starts after this
    j.chips = env["cell"]["chips"]
    j.devices = jax.devices()[:j.chips]
    j.note("device", backend_init_s=time.monotonic() - env["t_start"],
           cache_dir=jax.config.jax_compilation_cache_dir)

    j.compiles = {"n": 0}

    def on_duration(event, duration, **_):
        # one per compilation request, served by the cache or not
        if event == "/jax/core/compile/backend_compile_duration":
            j.compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    rehearsal, traffic = j.args.rehearsal, j.traffic
    j.seq = 64 if rehearsal else traffic["seq"]
    j.accum = traffic["grad_accum"]
    j.every = traffic["save_every_steps"]
    if rehearsal and j.every:
        j.every = 16  # steps of a few ms: room for a drain under them
    j.trace_steps = 4 if rehearsal else traffic["trace_steps"]
    j.losses, j.step, j.failed, j.ckpt = {}, 0, 0, None
    return j


def build_model(j):
    """Mesh from the live devices, sharded init from the seed, the batch
    maker and the loss."""
    import numpy as np
    from jax.sharding import NamedSharding

    from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
    from dlrover_tpu.parallel.sharding import (
        DEFAULT_RULES,
        axis_size,
        valid_spec_for,
    )

    jax, family, fields = j.jax, j.family, j.fields
    j.plan = plan_mesh(j.chips, **fields["mesh"])
    j.mesh = mesh = build_mesh(j.plan, devices=j.devices)
    # the chips that share an expert's intermediate width (1: none do)
    j.expert_mlp_shards = axis_size(mesh, DEFAULT_RULES["expert_mlp"])
    j.rows = j.traffic["rows_per_replica"] * j.plan.dp_total
    j.tokens_per_step = j.accum * j.rows * j.seq
    config = family.program_config(fields, j.seq)
    axes = family.logical_axes(config)
    seed = j.args.seed
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    shapes = jax.eval_shape(lambda k: family.init_params(config, k), key)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(n, (str, type(None))) for n in x)
    shardings = jax.tree.map(
        lambda ax, leaf: NamedSharding(
            mesh, valid_spec_for(mesh, leaf.shape, ax)),
        axes, shapes, is_leaf=is_axes)
    j.params = jax.block_until_ready(jax.jit(
        lambda k: family.init_params(config, k),
        out_shardings=shardings)(key))
    j.n_params = sum(x.size for x in jax.tree.leaves(j.params))
    counted = family.param_count(fields)
    if j.n_params != counted:
        raise RuntimeError(
            f"the program made {j.n_params} parameters, the family counts "
            f"{counted} from the configuration file")
    j.batch_for = _batch_maker(np, mesh, fields["vocab_size"], j.rows,
                               j.accum, j.seq, seed)
    j.loss_fn = family.loss_fn(config, mesh)


def check_reference(j):
    """(a) the system against the float32 reference."""
    from dlrover_tpu.trainer.elastic import optax_global_norm

    jax, loss_fn = j.jax, j.loss_fn
    first = j.batch_for(1)[0]

    def system(p, t):
        loss, grads = jax.value_and_grad(loss_fn)(p, t)
        return loss, optax_global_norm(grads)

    sys_loss, sys_norm = (float(x) for x in jax.jit(system)(j.params, first))
    ref_loss, ref_norm = (float(x) for x in jax.jit(
        j.family.reference(j.fields, j.seq))(j.params, first))
    tol = j.fields["reference_tolerance"]
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_rel = abs(sys_norm - ref_norm) / abs(ref_norm)
    j.reference_ok = (loss_rel <= tol["loss_rel"]
                      and norm_rel <= tol["grad_norm_rel"])
    j.compared = {"loss_rel": [loss_rel, tol["loss_rel"]],
                  "grad_norm_rel": [norm_rel, tol["grad_norm_rel"]]}
    j.note("reference", system_loss=sys_loss, reference_loss=ref_loss,
           loss_rel=loss_rel, system_grad_norm=sys_norm,
           reference_grad_norm=ref_norm, grad_norm_rel=norm_rel,
           tolerance=tol, ok=j.reference_ok)


def build_trainer(j):
    """``ElasticTrainer`` and the train state made of ``j.params``."""
    import optax

    from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

    jax, fields, traffic = j.jax, j.fields, j.traffic
    opt = fields["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    optimizer = optax.adamw(opt["learning_rate"])
    j.trainer = ElasticTrainer(
        loss_fn=j.loss_fn, optimizer=optimizer,
        global_batch_size=j.accum * j.rows,
        micro_batch_per_replica=traffic["rows_per_replica"],
    )
    j.trainer.configure_for_world(j.plan)
    j.state = jax.block_until_ready(make_train_state(j.params, optimizer))
    del j.params
    j.state_bytes = sum(x.nbytes for x in jax.tree.leaves(j.state))
    # what the architecture needs, as its family counts it: a token's
    # forward and backward, and the flash kernels' least for one microbatch
    j.flops_per_token = j.family.train_flops_per_token(fields, j.seq)
    j.flash_fwd, j.flash_bwd = j.family.flash_attention_flops(
        fields, j.seq, traffic["rows_per_replica"])
    j.note("model", params=j.n_params, state_bytes=j.state_bytes,
           train_flops_per_token=j.flops_per_token,
           flash_fwd_flops=j.flash_fwd, flash_bwd_flops=j.flash_bwd,
           mesh={k: v for k, v in j.mesh.shape.items() if v > 1},
           tokens_per_step=j.tokens_per_step, seq=j.seq, rows=j.rows,
           accum=j.accum,
           state_ready_s=time.monotonic() - j.env["t_start"])


def warm_up(j):
    """Steps until one asks for no compilation: on a mesh the step may
    hand back another layout than make_train_state's and compile twice.
    Four at the most."""
    warm = []
    while not warm or (warm[-1]["compiles"] and len(warm) < 4):
        j.step += 1
        n, t = j.compiles["n"], time.monotonic()
        j.state, result = j.trainer.train_step(j.state, j.batch_for(j.step))
        j.losses[j.step] = float(result.loss)
        warm.append({"step": j.step, "seconds": time.monotonic() - t,
                     "compiles": j.compiles["n"] - n,
                     "loss": j.losses[j.step]})
    j.note("warmup", steps=warm)


def own_checkpointer(j, cleanup):
    """A ``Checkpointer`` with no agent behind it. The frame lives in
    /dev/shm under a name of this checkout's own, so that two checkouts
    share nothing and a killed run's segment is found and replaced by the
    next."""
    from dlrover_tpu.ckpt.checkpointer import Checkpointer
    from dlrover_tpu.ckpt.shm_handler import shm_name
    from dlrover_tpu.common.multi_process import unlink_shared_memory

    job_name = "bench" + hashlib.blake2b(
        j.env["root"].encode(), digest_size=6).hexdigest()
    unlink_shared_memory(shm_name(job_name, 0, 0))
    cleanup.callback(unlink_shared_memory, shm_name(job_name, 0, 0))
    workdir = tempfile.mkdtemp(prefix="dlrover_bench_ckpt_")
    cleanup.callback(shutil.rmtree, workdir, ignore_errors=True)
    return Checkpointer(workdir, job_name=job_name, node_rank=0,
                        local_rank=0, world_size=1, rank=0)


def open_checkpointer(j, ckpt):
    """The first save faults the frame's pages in: set-up, waited for."""
    from dlrover_tpu.ckpt.checkpointer import StorageType

    j.ckpt = ckpt
    free = shutil.disk_usage("/dev/shm").free
    if free < 1.1 * j.state_bytes:
        raise RuntimeError(
            f"/dev/shm has {free} bytes free, the frame needs "
            f"{j.state_bytes}")
    t = time.monotonic()
    ok = ckpt.save_checkpoint(j.step, j.state, StorageType.MEMORY)
    block_s = time.monotonic() - t
    ok = ckpt.engine.wait_drained(600) and ok
    j.note("first_save", ok=bool(ok), block_s=block_s,
           drain_s=time.monotonic() - t - block_s)
    if not ok:
        raise RuntimeError("the set-up save failed")


def window(j, cleanup):
    """The measured window: ``--seconds`` of steps, in a cell that saves
    whole cycles of ``every`` steps and one save."""
    from dlrover_tpu.ckpt.checkpointer import StorageType
    from dlrover_tpu.observability.registry import get_registry

    jax, env, args = j.jax, j.env, j.args
    every, trainer, ckpt, losses = j.every, j.trainer, j.ckpt, j.losses
    state, step, compiles = j.state, j.step, j.compiles
    del j.state  # the loop's own from here on: a step donates its input

    registry = get_registry()
    hists = {
        "dlrover_ckpt_save_block_seconds":
            registry.histogram("dlrover_ckpt_save_block_seconds"),
        "dlrover_ckpt_drain_seconds":
            registry.histogram("dlrover_ckpt_drain_seconds"),
        RESTORE_HIST:
            registry.histogram("dlrover_ckpt_restore_seconds",
                               labelnames=("source",)).labels(source="shm"),
    }

    def snapshot():
        return {k: (h.count, h.sum) for k, h in hists.items()}

    j.snapshot = snapshot
    annotate = jax.profiler.TraceAnnotation
    intervals = []  # between successive step completions
    spans = {"input.batch": [], "save.block": [], "step.interval": intervals}

    def make_batch(n):
        t = time.perf_counter()
        with annotate("bench:batch"):
            batch = j.batch_for(n)
        spans["input.batch"].append(time.perf_counter() - t)
        return batch

    drain_rate = registry.gauge("dlrover_ckpt_drain_bytes_per_second")
    drains_s = []

    def save(n):
        if drain_rate.value:  # of the drain that ended before this save
            drains_s.append(j.state_bytes / drain_rate.value)
        t = time.monotonic()
        with annotate("bench:save"):
            ok = ckpt.save_checkpoint(n, state, StorageType.MEMORY)
        spans["save.block"].append(time.monotonic() - t)
        return ok

    if every:
        # the window opens right after a save returns, so that each of its
        # cycles is N steps under the previous save's drain and one save
        if not save(step):
            raise RuntimeError("the save that opens the window was refused")
        spans["save.block"].clear()
        drains_s.clear()

    tracer = _Tracer(jax, args.trace, j.trace_steps, args.seconds / 3, every)
    cleanup.callback(tracer.stop)
    j.before = snapshot()
    compiles_before = compiles["n"]
    t0 = last_done = time.monotonic()
    in_flight = None
    steps = index = saves = 0
    done = False
    j.setup_s = t0 - env["t_start"]
    while not done:
        tracer.before_step(time.monotonic() - t0, index)
        step += 1
        batch = make_batch(step)
        with annotate("bench:dispatch"):
            state, result = trainer.train_step(state, batch)
        previous, in_flight = in_flight, (step, result)
        steps += 1
        index += 1
        boundary = every and index == every
        for n, res in ([previous] if previous else []) + (
                [in_flight] if boundary else []):
            with annotate("bench:wait_loss"):
                losses[n] = float(res.loss)
            now = time.monotonic()
            intervals.append(now - last_done)
            last_done = now
        if boundary:
            in_flight, index = None, 0
            saves += 1
            if not save(step):
                j.failed += 1
            last_done = time.monotonic()  # a save is no step's time
            done = (last_done - t0 >= args.seconds) and not tracer.on
        elif not every and time.monotonic() - t0 >= args.seconds:
            done = not tracer.on
    if in_flight:
        losses[in_flight[0]] = float(in_flight[1].loss)
        now = time.monotonic()
        intervals.append(now - last_done)
        last_done = now
    t_end = last_done
    j.compiled_in_window = compiles["n"] - compiles_before
    tracer.stop()
    j.window_s = t_end - t0
    j.memory = [d.memory_stats() or {} for d in j.mesh.devices.flat]
    j.state, j.step, j.steps, j.saves = state, step, steps, saves
    j.tracer, j.spans, j.intervals, j.drains_s = (
        tracer, spans, intervals, drains_s)


def restores(j):
    """After the window (flash-save traffic): a warm-up restore from shm,
    then the timed ones, each compared bit for bit."""
    import jax.numpy as jnp

    jax, traffic, ckpt, state, step = (
        j.jax, j.traffic, j.ckpt, j.state, j.step)
    j.restore_times, j.warmup_times = [], []
    j.n_restores = traffic["restores_after_window"] if j.every else 0
    j.n_warmups = (traffic["restore_warmups"]
                   if j.every and j.n_restores else 0)
    j.saved_ok = True
    if j.every and not ckpt.engine.wait_drained(600):
        j.failed += 1  # the last snapshot was lost
        j.saved_ok = False
    if j.n_restores:
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)
    for n in range(j.n_warmups + j.n_restores):
        timed = n >= j.n_warmups
        times = j.restore_times if timed else j.warmup_times
        t = time.monotonic()
        restored, restored_step = ckpt.load_checkpoint(target)
        jax.block_until_ready(restored)
        times.append(time.monotonic() - t)
        same = sum_saved = sum_restored = None
        if restored_step == step:
            same, sum_saved, sum_restored = (
                x.item() for x in _bits_equal_fn(jax, jnp)(state, restored))
        if not same:
            j.failed += 1
            j.saved_ok = False
        j.note("restore" if timed else "restore_warmup",
               seconds=times[-1], saved_step=step,
               restored_step=restored_step, bits_equal=same,
               digest_saved=sum_saved, digest_restored=sum_restored)
        del restored
        if not timed:  # the registry's restores are the timed ones
            j.before[RESTORE_HIST] = j.snapshot()[RESTORE_HIST]
    j.setup_s += sum(j.warmup_times)  # warm-up is set-up, wherever it runs


def result(j) -> dict:
    """What ``run.py`` and the readers take from the job (``README.md``)."""
    import numpy as np

    after = j.snapshot()
    losses, intervals, tracer = j.losses, j.intervals, j.tracer
    hbm_peak = [int(m.get("peak_bytes_in_use", 0)) for m in j.memory]
    hbm_reserved = [int(m.get("bytes_reserved", 0)) for m in j.memory]
    finite = [n for n, v in losses.items() if not np.isfinite(v)]
    failed = j.failed + len(finite)
    tenth = max(1, len(intervals) // 10)
    j.note("window", steps=j.steps, saves=j.saves, window_s=j.window_s,
           step_samples=len(intervals),
           compiled_in_window=j.compiled_in_window,
           # a step that slows through the window (a routing that drifts)
           step_ms_first_tenth=1e3 * sum(intervals[:tenth]) / tenth,
           step_ms_last_tenth=1e3 * sum(intervals[-tenth:]) / tenth,
           trace_overhead_s=tracer.overhead_s, non_finite_steps=finite,
           loss_step_20=losses.get(20), last_loss=losses[max(losses)],
           drains_s=j.drains_s, save_stalls_s=j.spans["save.block"],
           hbm_peak_bytes=hbm_peak, hbm_reserved_bytes=hbm_reserved,
           hbm_peak_reserved_bytes=[
               int(m.get("peak_bytes_reserved", 0)) for m in j.memory])

    tokens = j.steps * j.tokens_per_step
    end_to_end = {
        "tokens_per_s": tokens / j.window_s,
        "step_ms.p90": 1e3 * stats.percentile(intervals, 90),
        "setup_s": j.setup_s,
    }
    if j.restore_times:
        end_to_end["restore_s"] = (
            sum(j.restore_times) / len(j.restore_times))
    registry_delta = {
        k: {"count": after[k][0] - j.before[k][0],
            "sum": after[k][1] - j.before[k][1]} for k in after}
    return {
        "correct": bool(j.reference_ok and not finite
                        and j.compiled_in_window == 0 and j.saved_ok),
        "attempted": j.steps + j.saves + j.n_warmups + j.n_restores,
        "failed": failed,
        "end_to_end": end_to_end,
        # each number compared beside its limit
        "compared": {**j.compared,
                     "compiled_in_window": [j.compiled_in_window, 0],
                     "non_finite_losses": [len(finite), 0],
                     "saves_or_restores_lost": [int(not j.saved_ok), 0]},
        "trace_dir": tracer.dir,
        "step_module": STEP_MODULE,
        "spans": j.spans,
        "registry": registry_delta,
        "memory": {"window_peak_bytes": hbm_peak,
                   "window_end_reserved_bytes": hbm_reserved},
        "job": {
            "tokens_per_s_untraced":
                tokens / (j.window_s - tracer.overhead_s),
            "tokens_per_step": j.tokens_per_step, "seq": j.seq,
            "rows": j.rows, "rows_per_replica": j.traffic["rows_per_replica"],
            "grad_accum": j.accum, "steps": j.steps,
            "saves": j.saves, "state_bytes": j.state_bytes,
            "chips": j.chips, "expert_mlp_shards": j.expert_mlp_shards,
            "train_flops_per_token": j.flops_per_token,
            "flash_fwd_flops": j.flash_fwd, "flash_bwd_flops": j.flash_bwd,
            "restore_warmups_s": j.warmup_times,
        },
    }
