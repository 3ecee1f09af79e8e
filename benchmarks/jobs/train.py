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


def _bits_equal_fn(jax, jnp):
    """(every bit equal, wrap-around sum of a's bits, of b's) over two
    trees of equal structure, on the device: a digest that costs no
    transfer. Equality is of bit patterns, so it is exact for NaNs too."""
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    def bits(x):
        return jax.lax.bitcast_convert_type(x, uint[x.dtype.itemsize])

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
        return _run(env, cleanup)


def _run(env, cleanup) -> dict:
    args, fields, traffic, family = (
        env["args"], env["fields"], env["traffic"], env["family"])
    note = env["note"]

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu import worker
    from dlrover_tpu.ckpt.checkpointer import Checkpointer, StorageType
    from dlrover_tpu.ckpt.shm_handler import shm_name
    from dlrover_tpu.common.multi_process import unlink_shared_memory
    from dlrover_tpu.observability.registry import get_registry
    from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
    from dlrover_tpu.parallel.sharding import valid_spec_for
    from dlrover_tpu.trainer.elastic import (
        ElasticTrainer,
        make_train_state,
        optax_global_norm,
    )
    from jax.sharding import NamedSharding

    worker.init()  # the compile cache, as every worker gets it
    chips = env["cell"]["chips"]
    devices = jax.devices()[:chips]
    note("device", backend_init_s=time.monotonic() - env["t_start"],
         cache_dir=jax.config.jax_compilation_cache_dir)

    compiles = {"n": 0}

    def on_duration(event, duration, **_):
        # one per compilation request, served by the cache or not
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    seq = 64 if args.rehearsal else traffic["seq"]
    accum = traffic["grad_accum"]
    every = traffic["save_every_steps"]
    if args.rehearsal and every:
        every = 16  # steps of a few ms: room for a drain under them
    trace_steps = 4 if args.rehearsal else traffic["trace_steps"]

    # -- mesh, sharded init from the seed ----------------------------------
    plan = plan_mesh(chips, **fields["mesh"])
    mesh = build_mesh(plan, devices=devices)
    rows = traffic["rows_per_replica"] * plan.dp_total
    tokens_per_step = accum * rows * seq
    config = family.program_config(fields, seq)
    axes = family.logical_axes(config)
    key = jax.random.fold_in(
        jax.random.PRNGKey(args.seed & 0x7FFFFFFF), args.seed >> 31)
    shapes = jax.eval_shape(lambda k: family.init_params(config, k), key)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(n, (str, type(None))) for n in x)
    shardings = jax.tree.map(
        lambda ax, leaf: NamedSharding(
            mesh, valid_spec_for(mesh, leaf.shape, ax)),
        axes, shapes, is_leaf=is_axes)
    params = jax.block_until_ready(jax.jit(
        lambda k: family.init_params(config, k),
        out_shardings=shardings)(key))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    counted = family.param_count(fields)
    if n_params != counted:
        raise RuntimeError(
            f"the program made {n_params} parameters, the family counts "
            f"{counted} from the configuration file")
    batch_for = _batch_maker(np, mesh, fields["vocab_size"], rows, accum,
                             seq, args.seed)
    loss_fn = family.loss_fn(config, mesh)

    # -- (a) the system against the float32 reference ----------------------
    first = batch_for(1)[0]

    def system(p, t):
        loss, grads = jax.value_and_grad(loss_fn)(p, t)
        return loss, optax_global_norm(grads)

    sys_loss, sys_norm = (float(x) for x in jax.jit(system)(params, first))
    ref_loss, ref_norm = (float(x) for x in jax.jit(
        family.reference(fields, seq))(params, first))
    tol = fields["reference_tolerance"]
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_rel = abs(sys_norm - ref_norm) / abs(ref_norm)
    reference_ok = (loss_rel <= tol["loss_rel"]
                    and norm_rel <= tol["grad_norm_rel"])
    note("reference", system_loss=sys_loss, reference_loss=ref_loss,
         loss_rel=loss_rel, system_grad_norm=sys_norm,
         reference_grad_norm=ref_norm, grad_norm_rel=norm_rel,
         tolerance=tol, ok=reference_ok)

    # -- trainer, state, warm-up -------------------------------------------
    opt = fields["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    optimizer = optax.adamw(opt["learning_rate"])
    trainer = ElasticTrainer(
        loss_fn=loss_fn, optimizer=optimizer,
        global_batch_size=accum * rows,
        micro_batch_per_replica=traffic["rows_per_replica"],
    )
    trainer.configure_for_world(plan)
    state = jax.block_until_ready(make_train_state(params, optimizer))
    del params
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    # what the architecture needs, as its family counts it: a token's
    # forward and backward, and the flash kernels' least for one microbatch
    flops_per_token = family.train_flops_per_token(fields, seq)
    flash_fwd, flash_bwd = family.flash_attention_flops(
        fields, seq, traffic["rows_per_replica"])
    note("model", params=n_params, state_bytes=state_bytes,
         train_flops_per_token=flops_per_token,
         flash_fwd_flops=flash_fwd, flash_bwd_flops=flash_bwd,
         mesh={k: v for k, v in mesh.shape.items() if v > 1},
         tokens_per_step=tokens_per_step, seq=seq, rows=rows, accum=accum,
         state_ready_s=time.monotonic() - env["t_start"])

    # warm up until a step asks for no compilation: on a mesh the step may
    # hand back another layout than make_train_state's and compile twice
    losses = {}
    step = 0
    warm = []
    while not warm or (warm[-1]["compiles"] and step < 4):
        step += 1
        n, t = compiles["n"], time.monotonic()
        state, result = trainer.train_step(state, batch_for(step))
        losses[step] = float(result.loss)
        warm.append({"step": step, "seconds": time.monotonic() - t,
                     "compiles": compiles["n"] - n, "loss": losses[step]})
    note("warmup", steps=warm)

    ckpt = None
    failed = saves = 0
    if every:
        # the frame lives in /dev/shm under a name of this checkout's own,
        # so that two checkouts share nothing and a killed run's segment
        # is found and replaced by the next
        job_name = "bench" + hashlib.blake2b(
            env["root"].encode(), digest_size=6).hexdigest()
        unlink_shared_memory(shm_name(job_name, 0, 0))
        cleanup.callback(unlink_shared_memory, shm_name(job_name, 0, 0))
        workdir = tempfile.mkdtemp(prefix="dlrover_bench_ckpt_")
        cleanup.callback(shutil.rmtree, workdir, ignore_errors=True)
        ckpt = Checkpointer(workdir, job_name=job_name, node_rank=0,
                            local_rank=0, world_size=1, rank=0)
        free = shutil.disk_usage("/dev/shm").free
        if free < 1.1 * state_bytes:
            raise RuntimeError(
                f"/dev/shm has {free} bytes free, the frame needs "
                f"{state_bytes}")
        # the first save faults the frame's pages in: set-up, waited for
        t = time.monotonic()
        ok = ckpt.save_checkpoint(step, state, StorageType.MEMORY)
        block_s = time.monotonic() - t
        ok = ckpt.engine.wait_drained(600) and ok
        note("first_save", ok=bool(ok), block_s=block_s,
             drain_s=time.monotonic() - t - block_s)
        if not ok:
            raise RuntimeError("the set-up save failed")

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

    annotate = jax.profiler.TraceAnnotation
    intervals = []  # between successive step completions
    spans = {"input.batch": [], "save.block": [], "step.interval": intervals}

    def make_batch(n):
        t = time.perf_counter()
        with annotate("bench:batch"):
            batch = batch_for(n)
        spans["input.batch"].append(time.perf_counter() - t)
        return batch

    drain_rate = registry.gauge("dlrover_ckpt_drain_bytes_per_second")
    drains_s = []

    def save(n):
        if drain_rate.value:  # of the drain that ended before this save
            drains_s.append(state_bytes / drain_rate.value)
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

    # -- the window --------------------------------------------------------
    tracer = _Tracer(jax, args.trace, trace_steps, args.seconds / 3, every)
    cleanup.callback(tracer.stop)
    before = snapshot()
    compiles_before = compiles["n"]
    t0 = last_done = time.monotonic()
    in_flight = None
    steps = index = 0
    done = False
    setup_s = t0 - env["t_start"]
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
                failed += 1
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
    compiled_in_window = compiles["n"] - compiles_before
    tracer.stop()
    window_s = t_end - t0
    memory = [d.memory_stats() or {} for d in mesh.devices.flat]
    hbm_peak = [int(m.get("peak_bytes_in_use", 0)) for m in memory]
    hbm_reserved = [int(m.get("bytes_reserved", 0)) for m in memory]

    # -- after the window --------------------------------------------------
    restore_times, warmup_times = [], []
    restores = traffic["restores_after_window"] if every else 0
    warmups = traffic["restore_warmups"] if every and restores else 0
    saved_ok = True
    if every and not ckpt.engine.wait_drained(600):
        failed += 1  # the last snapshot was lost
        saved_ok = False
    if restores:
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)
    for n in range(warmups + restores):
        timed = n >= warmups
        t = time.monotonic()
        restored, restored_step = ckpt.load_checkpoint(target)
        jax.block_until_ready(restored)
        (restore_times if timed else warmup_times).append(
            time.monotonic() - t)
        same = sum_saved = sum_restored = None
        if restored_step == step:
            same, sum_saved, sum_restored = (
                x.item() for x in _bits_equal_fn(jax, jnp)(state, restored))
        if not same:
            failed += 1
            saved_ok = False
        note("restore" if timed else "restore_warmup",
             seconds=(restore_times if timed else warmup_times)[-1],
             saved_step=step, restored_step=restored_step, bits_equal=same,
             digest_saved=sum_saved, digest_restored=sum_restored)
        del restored
        if not timed:  # the registry's restores are the timed ones
            before[RESTORE_HIST] = snapshot()[RESTORE_HIST]
    setup_s += sum(warmup_times)  # warm-up is set-up, wherever it runs
    after = snapshot()

    finite = [n for n, v in losses.items() if not np.isfinite(v)]
    failed += len(finite)
    tenth = max(1, len(intervals) // 10)
    note("window", steps=steps, saves=saves, window_s=window_s,
         step_samples=len(intervals), compiled_in_window=compiled_in_window,
         # a step that slows through the window (a routing that drifts)
         step_ms_first_tenth=1e3 * sum(intervals[:tenth]) / tenth,
         step_ms_last_tenth=1e3 * sum(intervals[-tenth:]) / tenth,
         trace_overhead_s=tracer.overhead_s, non_finite_steps=finite,
         loss_step_20=losses.get(20), last_loss=losses[max(losses)],
         drains_s=drains_s, save_stalls_s=spans["save.block"],
         hbm_peak_bytes=hbm_peak, hbm_reserved_bytes=hbm_reserved,
         hbm_peak_reserved_bytes=[
             int(m.get("peak_bytes_reserved", 0)) for m in memory])

    tokens = steps * tokens_per_step
    end_to_end = {
        "tokens_per_s": tokens / window_s,
        "step_ms.p90": 1e3 * stats.percentile(intervals, 90),
        "setup_s": setup_s,
    }
    if restore_times:
        end_to_end["restore_s"] = sum(restore_times) / len(restore_times)
    registry_delta = {
        k: {"count": after[k][0] - before[k][0],
            "sum": after[k][1] - before[k][1]} for k in after}
    return {
        "correct": bool(reference_ok and not finite
                        and compiled_in_window == 0 and saved_ok),
        "attempted": steps + saves + warmups + restores,
        "failed": failed,
        "end_to_end": end_to_end,
        "trace_dir": tracer.dir,
        "step_module": STEP_MODULE,
        "spans": spans,
        "registry": registry_delta,
        "memory": {"window_peak_bytes": hbm_peak,
                   "window_end_reserved_bytes": hbm_reserved},
        "job": {
            "tokens_per_s_untraced": tokens / (window_s - tracer.overhead_s),
            "tokens_per_step": tokens_per_step, "seq": seq, "rows": rows,
            "grad_accum": accum, "steps": steps, "saves": saves,
            "state_bytes": state_bytes, "chips": chips,
            "train_flops_per_token": flops_per_token,
            "flash_fwd_flops": flash_fwd, "flash_bwd_flops": flash_bwd,
            "restore_warmups_s": warmup_times,
        },
    }
