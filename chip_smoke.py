"""chip_smoke.py — the framework's main path, once, on the chip.

Default run (one chip, no arguments):

1. refuse unless ``jax.devices()[0].platform == "tpu"``;
2. Pallas kernels against the plain reference, in one child process:
   ``flash_attention`` forward and forward+backward, ``flash_decode_attention``
   bf16 and int8, at Llama-2-7B widths (32 heads x 128, sequence 2048),
   non-interpreted, each lowered program checked for ``tpu_custom_call``;
3. how long the chip takes to be free again after its owner is SIGKILLed;
4. ``python -m dlrover_tpu.agent.run --standalone --nproc-per-node=1
   --network-check --ckpt-dir <dir> chip_smoke.py --role worker ...``: node
   check -> rendezvous -> ``worker.init()`` -> mesh from ``jax.devices()`` ->
   ``ElasticTrainer`` on ``models/llama.py`` at 7B widths (depth cut to fit
   one 16 GB chip) -> flash checkpoint to shm every step -> the worker
   SIGKILLs itself -> the agent restarts it -> restore from shm -> training
   continues -> exit 0.

``--chips 4`` runs only phase 4 with one worker process driving four chips
on an fsdp=2 x tp=2 mesh, after the same configuration and seed on one
device of that machine to compare losses with.

``--rehearsal`` runs the same control flow at tiny widths with
interpret-mode kernels under ``JAX_PLATFORMS=cpu`` (tier-1 uses it); it
reports the platform it really ran on.

This process never imports jax: a parent that touched the chip would keep
it from every child. Device facts come from the children's result files.
Every phase fails the run with a non-zero exit. The LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

import argparse
import glob
import hashlib
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# what the worker does, in both sizes: steps 1..KILL_STEP in the first
# incarnation (memory save after each but the last), SIGKILL after step
# KILL_STEP's loss is written and before its save, resume from
# KILL_STEP-1, replay KILL_STEP (the overlap), finish at TOTAL_STEPS
KILL_STEP = 3
# the names ops/flash_attention.py gives its Pallas calls (PR 24)
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
TOTAL_STEPS = 4

# normalized max error (max|a-b| / max|b|) allowed between a bf16 kernel
# and its reference: flash vs f32 "highest"-precision dense attention,
# decode vs the bf16 einsum path of models/decode.py
TOL_FLASH_FWD = 2e-2
TOL_FLASH_BWD = 4e-2
TOL_DECODE_BF16 = 3e-2
TOL_DECODE_INT8 = 5e-2
# first-steps losses, four-chip mesh vs one device (bf16 params, other
# reduction order): relative
TOL_LOSS_SHARDED = 1e-2
# device bytes_in_use after init, max over min across the mesh
MAX_HBM_IMBALANCE = 1.5


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# result files: one JSON object per line, fsynced (a SIGKILL follows some)
# ---------------------------------------------------------------------------


class Recorder:
    def __init__(self, path, **common):
        self._path, self._common = path, common

    def __call__(self, event, **fields):
        line = json.dumps({"event": event, **self._common, **fields})
        with open(self._path, "a") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())


def read_events(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def one(events, event, **match):
    got = [e for e in events if e["event"] == event
           and all(e.get(k) == v for k, v in match.items())]
    check(len(got) == 1,
          f"expected one {event!r} {match} event, got {len(got)}")
    return got[0]


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(rehearsal):
    """(attention shape B,H,S,D; decode shape B,KV,G,T,D,pos) for the
    kernel phase."""
    if rehearsal:
        return (1, 4, 128, 32), (2, 4, 1, 256, 32, 200)
    return (1, 32, 2048, 128), (8, 32, 1, 2048, 128, 1500)


def model_config(rehearsal):
    """(LlamaConfig, grad-accum steps, rows per microbatch, sequence).

    Real size: ``LlamaConfig.llama7b()`` widths (dim 4096, 32 heads x 128,
    MHA, ffn 11008, vocab 32000, bf16, remat), sequence 2048, depth 2,
    adamw with the f32 moments ``make_train_state`` gives (10 B/param of
    state, and the step's f32 grad accumulator on top). A described-chip
    compile of ``ElasticTrainer._build_step`` (v5e:2x2, jax 0.9.0) gave
    arguments + temp of 8.6 GB at depth 1, 13.3 GB at depth 2 and 17.9 GB
    at depth 3 for 2 x 2 x 2048 tokens; between steps the save's
    on-device snapshot stands beside the state (2 x 6.67 GB at depth 2).
    Depth 2 is what one 16 GiB chip holds."""
    import dataclasses

    from dlrover_tpu.models import llama

    if rehearsal:
        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), remat=True, use_flash_attention=True,
        )
        return cfg, 2, 2, 64
    cfg = dataclasses.replace(
        llama.LlamaConfig.llama7b(), n_layers=2, max_seq_len=2048,
    )
    return cfg, 2, 2, 2048


# ---------------------------------------------------------------------------
# role: kernels (phases 1 and 2) — owns the chip while it runs
# ---------------------------------------------------------------------------


def device_facts(jax):
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def role_kernels(a):
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np

    rec = Recorder(a.out)
    facts = device_facts(jax)
    rec("device", backend_init_s=time.monotonic() - t0, **facts)
    if facts["platform"] != "tpu" and not a.rehearsal:
        return 3

    from dlrover_tpu.models import decode
    from dlrover_tpu.ops.flash_attention import (
        flash_attention,
        flash_decode_attention,
    )
    from dlrover_tpu.parallel.ring_attention import full_causal_attention

    interpret = a.rehearsal  # the chip runs the compiled kernels only
    (B, H, S, D), (Bd, KV, G, T, Dd, pos) = sizes(a.rehearsal)
    key = jax.random.PRNGKey(a.seed)

    def nerr(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), "kernel output is not finite")
        return float(np.abs(got - want).max() / np.abs(want).max())

    def run(name, fn, ref_fn, args, tol):
        t = time.monotonic()
        jitted = jax.jit(fn)
        text = jitted.lower(*args).as_text()
        got = jax.block_until_ready(jitted(*args))
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
        errs = [nerr(g, w) for g, w in
                zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        rec("kernel", name=name, err=max(errs), tol=tol,
            shape=[list(x.shape) for x in args[:2]],
            custom_calls=text.count("tpu_custom_call"),
            interpret=interpret, seconds=time.monotonic() - t)
        check(max(errs) <= tol, f"{name}: error {max(errs):.3g} > {tol}")
        check(interpret or "tpu_custom_call" in text,
              f"{name}: no tpu_custom_call in the lowered program")

    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(jax.random.fold_in(key, 9), (B, H, S, D))

    def dense_f32(q, k, v):
        with jax.default_matmul_precision("highest"):
            return full_causal_attention(*(
                x.astype(jnp.float32) for x in (q, k, v)))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def grads_of(attn):
        return jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2),
        )

    run("flash_attention fwd", flash, dense_f32, (q, k, v), TOL_FLASH_FWD)
    run("flash_attention fwd+bwd", grads_of(flash), grads_of(dense_f32),
        (q, k, v), TOL_FLASH_BWD)

    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 1), 3)
    qd = jax.random.normal(kq, (Bd, KV, G, Dd), jnp.bfloat16)
    kc = jax.random.normal(kk, (Bd, KV, T, Dd), jnp.bfloat16)
    vc = jax.random.normal(kv, (Bd, KV, T, Dd), jnp.bfloat16)
    mask = jnp.arange(T)[None, None, None, :] <= pos
    scale = Dd ** -0.5

    def einsum_path(q, k, v):
        # models/decode.py's own non-fused attend, (B, Q=1, H, Dh) queries
        out = decode._attend(
            q.reshape(Bd, 1, KV * G, Dd), k, v, mask, scale)
        return out.reshape(Bd, KV, G, Dd)

    run("flash_decode_attention bf16",
        lambda q, k, v: flash_decode_attention(
            q, k, v, pos, scale=scale, interpret=interpret),
        einsum_path, (qd, kc, vc), TOL_DECODE_BF16)

    (k8, ks), (v8, vs) = decode._quantize(kc), decode._quantize(vc)
    run("flash_decode_attention int8",
        lambda q, k, v: flash_decode_attention(
            q, k, v, pos, scale=scale, interpret=interpret,
            k_scale=ks, v_scale=vs),
        lambda q, k, v: einsum_path(
            q, decode._dequantize(k, ks, jnp.bfloat16),
            decode._dequantize(v, vs, jnp.bfloat16)),
        (qd, k8, v8), TOL_DECODE_INT8)
    return 0


# ---------------------------------------------------------------------------
# roles: hold / probe (phase 3) — how soon is a SIGKILLed owner's chip free
# ---------------------------------------------------------------------------


def role_hold(a):
    import jax
    import jax.numpy as jnp

    facts = device_facts(jax)
    # a loaded chip, like a training worker's: ~8 GB live on the device
    gb = 0.01 if a.rehearsal else 8
    x = jax.block_until_ready(
        jnp.ones((int(gb * (1 << 30)) // 2,), jnp.bfloat16))
    Recorder(a.out)("holding", pid=os.getpid(), gb=x.nbytes / 2**30, **facts)
    time.sleep(3600)
    return 1


def role_probe(a):
    import jax  # the import alone: what a warm spare does while it waits
    import jax.numpy as jnp

    rec = Recorder(a.out)
    rec("imported")
    sys.stdin.readline()  # parked like a warm spare until released
    t0 = time.monotonic()
    facts = device_facts(jax)
    jax.block_until_ready(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
    rec("acquired", t_wall=time.time(), init_s=time.monotonic() - t0, **facts)
    return 0


# ---------------------------------------------------------------------------
# role: worker (phase 4) — started by the agent, or alone with --plain
# ---------------------------------------------------------------------------


def shard_digests(tree):
    """{leaf path: [[device id, shard index, blake2b of the bytes], ...]}
    over every addressable shard: where each byte lives, and what it is."""
    import jax
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = sorted(
            [s.device.id, str(s.index),
             hashlib.blake2b(np.asarray(s.data).tobytes(),
                             digest_size=16).hexdigest()]
            for s in leaf.addressable_shards
        )
    return out


def role_worker(a):
    t_start = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu import worker
    from dlrover_tpu.ckpt.checkpointer import Checkpointer, StorageType
    from dlrover_tpu.models import llama
    from dlrover_tpu.observability.registry import get_registry
    from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
    from dlrover_tpu.parallel.sharding import (
        global_batch_from_local,
        shard_tree,
        valid_spec_for,
    )
    from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

    ctx = worker.init()
    inc = ctx.restart_count
    rec = Recorder(a.out, inc=inc)
    t0 = time.monotonic()
    facts = device_facts(jax)
    rec("device", pid=os.getpid(), t_wall=time.time(),
        backend_init_s=time.monotonic() - t0,
        cache_dir=jax.config.jax_compilation_cache_dir, **facts)
    if facts["platform"] != "tpu" and not a.rehearsal:
        return 3

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    def cache_files():
        d = jax.config.jax_compilation_cache_dir
        return len(os.listdir(d)) if d and os.path.isdir(d) else 0

    # -- mesh from the live devices, sharded init, trainer -----------------
    cfg, accum, rows, seq = model_config(a.rehearsal)
    if a.mesh == "fsdp2tp2":
        check(facts["count"] >= 4, f"mesh needs 4 devices, have {facts}")
        plan = plan_mesh(4, tp=2, fsdp=2)
        mesh = build_mesh(plan)
    else:
        plan = plan_mesh(1)
        mesh = build_mesh(plan, devices=jax.devices()[:1])
    axes = llama.param_logical_axes(cfg)
    raw = llama.init_params(cfg, jax.random.PRNGKey(a.seed))
    params = shard_tree(mesh, raw, axes)
    del raw
    optimizer = optax.adamw(3e-4)
    trainer = ElasticTrainer(
        loss_fn=lambda p, t: llama.next_token_loss(p, t, cfg, mesh),
        optimizer=optimizer,
        global_batch_size=accum * rows,
        micro_batch_per_replica=rows // plan.dp_total,
    )
    trainer.configure_for_world(plan)
    state = jax.block_until_ready(make_train_state(params, optimizer))
    del params
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    hbm = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
           for d in mesh.devices.flat}
    rec("model", depth=cfg.n_layers, dim=cfg.dim, heads=cfg.n_heads,
        head_dim=cfg.head_dim, ffn=cfg.ffn_dim, vocab=cfg.vocab_size,
        seq=seq, global_batch=accum * rows, grad_accum=accum,
        params=llama.num_params(cfg), state_bytes=state_bytes,
        mesh={k: v for k, v in mesh.shape.items() if v > 1},
        mesh_device_ids=np.array([d.id for d in mesh.devices.flat]).reshape(
            mesh.devices.shape).squeeze().tolist(),
        mesh_device_coords={d.id: list(getattr(d, "coords", ()))
                            for d in mesh.devices.flat},
        hbm_in_use_after_init=hbm, init_s=time.monotonic() - t_start)

    if a.mesh == "fsdp2tp2":
        # every leaf the logical axes shard: four addressable shards, on
        # four distinct devices, each a strict part of the array
        ax_leaves = jax.tree.leaves(
            axes, is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(n, (str, type(None))) for n in x))
        placement = {}
        for (path, leaf), ax in zip(
            jax.tree_util.tree_flatten_with_path(state["params"])[0],
            ax_leaves,
        ):
            spec = valid_spec_for(mesh, leaf.shape, ax)
            parts = 1
            for entry in spec:
                for name in ((entry,) if isinstance(entry, str)
                             else entry or ()):
                    parts *= mesh.shape[name]
            if parts == 1:
                continue  # replicated (norms; "layers" rides pp = 1)
            shards = leaf.addressable_shards
            placement[jax.tree_util.keystr(path)] = {
                "spec": str(spec), "parts": parts,
                "devices": sorted({s.device.id for s in shards}),
                "distinct_indices": len({str(s.index) for s in shards}),
                "shard_fraction": shards[0].data.size / leaf.size,
            }
        rec("placement", leaves=placement)

    # the checkpointed state lives in /dev/shm between incarnations
    free = shutil.disk_usage("/dev/shm").free
    check(a.plain or inc > 0 or free > 1.1 * state_bytes,
          f"checkpoint state of {state_bytes / 2**30:.2f} GiB does not fit "
          f"/dev/shm ({free / 2**30:.2f} GiB free)")

    def batch_for(step):
        rng = np.random.default_rng([a.seed, step])
        local = rng.integers(
            0, cfg.vocab_size, size=(accum * rows, seq + 1), dtype=np.int32)
        return global_batch_from_local(mesh, local).reshape(
            accum, rows, seq + 1)

    # -- resume ------------------------------------------------------------
    start = 0
    ckpt = None
    if not a.plain:
        ckpt = Checkpointer(a.ckpt_dir)
        t = time.monotonic()
        state, restored = ckpt.load_checkpoint(state)
        jax.block_until_ready(state)
        restore_s = time.monotonic() - t
        hist = get_registry().histogram(
            "dlrover_ckpt_restore_seconds", labelnames=("source",))
        rec("restore", step=restored, seconds=restore_s,
            sources={s: hist.labels(source=s).count
                     for s in ("shm", "chain", "replica", "storage")})
        if restored >= 0:
            start = restored
            digests = shard_digests(state)
            if a.fail == "digest":  # prove a wrong digest fails the run
                digests[next(iter(digests))][0][2] = "0" * 32
            rec("digest", step=restored, when="restored", digests=digests)

    # -- train -------------------------------------------------------------
    first = True
    last = KILL_STEP if a.plain else TOTAL_STEPS
    for step in range(start + 1, last + 1):
        tokens = batch_for(step)
        files0, ev0 = cache_files(), dict(cache_events)
        t = time.monotonic()
        state, result = trainer.train_step(state, tokens)
        loss = float(result.loss)
        dt = time.monotonic() - t
        check(np.isfinite(loss), f"step {step}: loss {loss}")
        rec("step", step=step, loss=loss, loss_hex=loss.hex(), seconds=dt,
            t_wall=time.time(), first=first,
            cache_hits=cache_events["hits"] - ev0["hits"],
            cache_misses=cache_events["misses"] - ev0["misses"],
            cache_files_new=cache_files() - files0)
        if first and inc == 0 and not a.plain:
            # the program XLA was given and the one it made of it
            lowered = trainer._build_step().lower(state, tokens)
            text = lowered.as_text()
            compiled = lowered.compile().as_text()
            rec("program",
                lowered_custom_calls=text.count("tpu_custom_call"),
                compiled_custom_calls=compiled.count("tpu_custom_call"),
                kernels=sorted({n for n in FLASH_KERNELS
                                if f'"{n}"' in text}),
                collectives={op: compiled.count(f" {op}(")
                             + compiled.count(f" {op}-start(")
                             for op in ("all-gather", "reduce-scatter",
                                        "all-reduce", "all-to-all",
                                        "collective-permute")})
        first = False
        if a.plain:
            continue
        if inc == 0 and step == KILL_STEP:
            rec("kill", t_wall=time.time(), pid=os.getpid())
            os.kill(os.getpid(), signal.SIGKILL)
        t = time.monotonic()
        saved = ckpt.save_checkpoint(step, state, StorageType.MEMORY)
        block_s = time.monotonic() - t
        # the drain must land before the next step donates the state's
        # buffers away from under the snapshot's HBM budget, and before
        # the kill: the smoke wants step KILL_STEP-1 in shm, not a race
        drained = ckpt.engine.wait_drained(600)
        check(saved and drained, f"step {step}: memory save failed")
        rec("save", step=step, block_seconds=block_s,
            drain_seconds=time.monotonic() - t - block_s)
        ctx.publish_step(step)
        ctx.report_step(step)
        if step in (KILL_STEP - 1, TOTAL_STEPS):
            rec("digest", step=step, when="saved",
                digests=shard_digests(state))
    rec("done", t_wall=time.time())
    return 0


# ---------------------------------------------------------------------------
# the parent: phases, checks, the last line
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, a):
        self.a = a
        self.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        self.job = f"smoke{os.getpid()}"
        self.procs = []
        self.device = None
        self.t0 = time.monotonic()
        env = dict(os.environ)
        if a.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={a.chips}")
        self.env = env

    def say(self, msg):
        d = self.device
        where = f"{d['platform']} {d['kind']} x{d['count']}" if d else "-"
        print(f"[{time.monotonic() - self.t0:7.1f}s] [{where}] {msg}",
              flush=True)

    def path(self, name):
        return os.path.join(self.workdir, name)

    def spawn(self, argv, log, **kw):
        proc = subprocess.Popen(  # noqa: S603
            argv, cwd=HERE, env=self.env, start_new_session=True,
            stdout=open(self.path(log), "ab"), stderr=subprocess.STDOUT, **kw)
        self.procs.append(proc)
        return proc

    def role(self, role, out, *extra, **kw):
        argv = [sys.executable, os.path.abspath(__file__), "--role", role,
                "--out", self.path(out), "--seed", str(self.a.seed), *extra]
        if self.a.rehearsal:
            argv.append("--rehearsal")
        return self.spawn(argv, f"{role}.log", **kw)

    def wait(self, proc, what, timeout):
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{what}: still running after {timeout}s")
        return rc

    def log_tail(self, log, n=6000):
        try:
            with open(self.path(log), "rb") as f:
                f.seek(max(0, os.path.getsize(self.path(log)) - n))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    def must(self, rc, what, log):
        if rc != 0:
            sys.stderr.write(self.log_tail(log))
            raise SmokeFailure(f"{what}: exit code {rc}")

    def set_device(self, event):
        self.device = {k: event[k] for k in ("platform", "kind", "count")}
        if not self.a.rehearsal:
            check(self.device["platform"] == "tpu",
                  f"no TPU: jax found {self.device} — refusing (use "
                  "--rehearsal for the CPU control-flow run)")
            check(self.device["count"] == self.a.chips,
                  f"--chips {self.a.chips} but jax found {self.device}")

    # -- phases 1 + 2 ------------------------------------------------------

    def kernels(self):
        t = time.monotonic()
        proc = self.role("kernels", "kernels.jsonl")
        rc = self.wait(proc, "kernel phase", 600)
        events = read_events(self.path("kernels.jsonl"))
        if events:
            self.set_device(one(events, "device"))
        self.must(rc, "kernel phase", "kernels.log")
        init_s = one(events, "device")["backend_init_s"]
        self.say(f"backend init {init_s:.1f}s")
        kernels = [e for e in events if e["event"] == "kernel"]
        check(len(kernels) == 4, f"expected 4 kernel checks, got {kernels}")
        for e in kernels:
            self.say(
                f"kernel {e['name']}: err {e['err']:.2e} <= {e['tol']:.0e} "
                f"vs reference, shapes {e['shape']}, "
                + ("interpret mode" if e["interpret"] else
                   f"tpu_custom_call x{e['custom_calls']}")
                + f", {e['seconds']:.1f}s")
        self.say(f"phase kernels: {time.monotonic() - t:.1f}s wall")

    # -- phase 3 -----------------------------------------------------------

    def chip_release(self):
        t = time.monotonic()
        holder = self.role("hold", "hold.jsonl")
        probe = self.role("probe", "probe.jsonl", stdin=subprocess.PIPE)
        deadline = time.monotonic() + 300
        while not (read_events(self.path("hold.jsonl"))
                   and read_events(self.path("probe.jsonl"))):
            check(holder.poll() is None and probe.poll() is None,
                  "release probe: a child died before the kill:\n"
                  + self.log_tail("hold.log") + self.log_tail("probe.log"))
            check(time.monotonic() < deadline, "release probe: not ready")
            time.sleep(0.1)
        # the probe imported jax while the holder owned the chip, and the
        # holder is still alive: the import alone takes nothing
        held = one(read_events(self.path("hold.jsonl")), "holding")
        t_kill = time.monotonic()
        holder.kill()
        holder.wait()
        reap_s = time.monotonic() - t_kill
        probe.stdin.write(b"go\n")
        probe.stdin.close()
        rc = self.wait(probe, "release probe", 300)
        if rc == 0:
            got = one(read_events(self.path("probe.jsonl")), "acquired")
            self.say(
                f"chip release: owner holding {held['gb']:.1f} GiB "
                f"SIGKILLed, reaped after {reap_s * 1e3:.0f} ms; a warm "
                f"process released at the reap took the chip on its first "
                f"try (backend init {got['init_s']:.1f}s) — free within "
                f"{reap_s * 1e3:.0f} ms of the kill")
        else:
            # not free at the reap: find out when, with fresh processes
            sys.stderr.write(self.log_tail("probe.log"))
            while True:
                check(time.monotonic() - t_kill < 180,
                      "chip still not free 180s after its owner's SIGKILL")
                again = self.role("probe", f"probe{time.monotonic_ns()}.jsonl",
                                  stdin=subprocess.DEVNULL)
                if self.wait(again, "release probe retry", 300) == 0:
                    break
                time.sleep(1.0)
            self.say(
                f"chip release: NOT free at the reap ({reap_s * 1e3:.0f} ms "
                f"after SIGKILL); first fresh process to take it started "
                f"<= {time.monotonic() - t_kill:.1f}s after the kill")
        self.say(f"phase chip-release: {time.monotonic() - t:.1f}s wall")

    # -- phase 4 -----------------------------------------------------------

    def reference_on_one_device(self):
        t = time.monotonic()
        proc = self.role("worker", "reference.jsonl", "--mesh", "one",
                         "--plain")
        rc = self.wait(proc, "one-device reference", 900)
        events = read_events(self.path("reference.jsonl"))
        if events:
            self.set_device(one(events, "device"))
        self.must(rc, "one-device reference", "worker.log")
        self.say(f"phase one-device reference: {time.monotonic() - t:.1f}s "
                 "wall")
        return {e["step"]: e["loss"] for e in events if e["event"] == "step"}

    def elastic(self, mesh):
        a = self.a
        t = time.monotonic()
        out = self.path("worker.jsonl")
        argv = [
            sys.executable, "-m", "dlrover_tpu.agent.run", "--standalone",
            "--nproc-per-node=1", "--network-check", "--max-restarts=2",
            "--job-name", self.job, "--ckpt-dir", self.path("ckpt"),
            os.path.abspath(__file__), "--role", "worker", "--out", out,
            "--seed", str(a.seed), "--mesh", mesh,
            "--ckpt-dir", self.path("ckpt"),
        ]
        if a.rehearsal:
            argv.append("--rehearsal")
        if a.fail:
            argv += ["--fail", a.fail]
        rc = self.wait(self.spawn(argv, "agent.log"), "agent", 1000)
        ev = read_events(out)
        if ev:
            self.set_device(one(ev, "device", inc=0))
        self.must(rc, "agent", "agent.log")
        self.say(f"agent exit code 0 with --network-check, "
                 f"{time.monotonic() - t:.1f}s wall")

        # one kill, one restart
        incs = sorted({e["inc"] for e in ev})
        check(incs == [0, 1], f"expected incarnations [0, 1], got {incs}")
        kill = one(ev, "kill")
        dev1 = one(ev, "device", inc=1)
        self.say(f"worker pid {kill['pid']} SIGKILLed itself after step "
                 f"{KILL_STEP}; the agent spent one restart (pid "
                 f"{dev1['pid']}, restart_count 1)")

        m = one(ev, "model", inc=0)
        self.say(
            f"model: Llama widths dim {m['dim']}, {m['heads']} heads x "
            f"{m['head_dim']}, ffn {m['ffn']}, vocab {m['vocab']}, depth "
            f"{m['depth']}, seq {m['seq']}, global batch {m['global_batch']} "
            f"(grad-accum {m['grad_accum']}), {m['params'] / 1e6:.0f}M "
            f"params, train state {m['state_bytes']} bytes "
            f"({m['state_bytes'] / 2**30:.2f} GiB), mesh {m['mesh'] or '1'}")

        # the kernel is in the program XLA compiled
        p = one(ev, "program")
        if a.rehearsal:
            self.say("train step: interpret-mode kernels (rehearsal)")
        else:
            check(p["kernels"] == sorted(FLASH_KERNELS)
                  and p["compiled_custom_calls"] >= 3,
                  f"flash kernel missing from the train step: {p}")
            self.say(
                f"train step program: tpu_custom_call x"
                f"{p['compiled_custom_calls']} compiled (lowered x"
                f"{p['lowered_custom_calls']}): flash forward "
                f"(flash_fwd) and backward (flash_bwd_dq, flash_bwd_dkv)")

        # restore came from shm, bit for bit
        r0, r1 = one(ev, "restore", inc=0), one(ev, "restore", inc=1)
        check(r0["step"] == -1, f"first incarnation restored {r0}")
        check(r1["step"] == KILL_STEP - 1
              and r1["sources"] == {"shm": 1, "chain": 0, "replica": 0,
                                    "storage": 0},
              f"restore did not come from shm at step {KILL_STEP - 1}: {r1}")
        saved = one(ev, "digest", inc=0, step=KILL_STEP - 1)["digests"]
        back = one(ev, "digest", inc=1, when="restored")["digests"]
        bad = [k for k in saved if saved[k] != back.get(k)]
        check(not bad and len(saved) == len(back),
              f"restored digests differ from the saved ones at {bad[:4]}")
        n_shards = sum(len(v) for v in saved.values())
        self.say(f"restore from shm at step {r1['step']}: digests equal "
                 f"({len(saved)} leaves, {n_shards} shards, each on the "
                 f"device that saved it), {r1['seconds']:.2f}s")

        # the overlapping step gives the same loss
        steps0 = {e["step"]: e for e in ev
                  if e["event"] == "step" and e["inc"] == 0}
        steps1 = {e["step"]: e for e in ev
                  if e["event"] == "step" and e["inc"] == 1}
        check(sorted(steps0) == list(range(1, KILL_STEP + 1))
              and sorted(steps1) == list(range(KILL_STEP, TOTAL_STEPS + 1)),
              f"steps ran {sorted(steps0)} then {sorted(steps1)}")
        before, after = steps0[KILL_STEP], steps1[KILL_STEP]
        check(before["loss_hex"] == after["loss_hex"],
              f"step {KILL_STEP} loss {before['loss']!r} before the kill, "
              f"{after['loss']!r} after resume")
        self.say(f"overlapping loss equal: step {KILL_STEP} = "
                 f"{after['loss']!r} before the kill and after resume; "
                 f"losses {[steps0[s]['loss'] for s in sorted(steps0)]} then "
                 f"{[steps1[s]['loss'] for s in sorted(steps1)]}")

        # one program: after an incarnation's first step nothing compiles
        later = [e for e in ev if e["event"] == "step" and not e["first"]]
        check(all(e["cache_hits"] + e["cache_misses"] == 0 for e in later),
              f"a step after the first asked for a compilation: {later}")

        # the second incarnation compiled nothing for its first step
        check(after["first"] and after["cache_hits"] >= 1
              and after["cache_misses"] == 0
              and after["cache_files_new"] <= 0,  # the cache may evict
              f"second incarnation's first step missed the compile cache: "
              f"{after}")
        self.say(f"compile cache hit on the second incarnation's first "
                 f"step ({after['cache_hits']} hit, 0 miss, no new files in "
                 f"{dev1['cache_dir']})")

        # observations (not benchmark metrics)
        saves = [e for e in ev if e["event"] == "save"]
        steady = [e["seconds"] for e in ev
                  if e["event"] == "step" and not e["first"]]
        first0 = steps0[1]["seconds"]
        self.say(
            f"observed: first step incl. compile {first0:.1f}s, step "
            f"{statistics.median(steady):.3f}s (median of {len(steady)}), "
            f"save blocking "
            f"{statistics.median(e['block_seconds'] for e in saves):.3f}s "
            f"+ drain "
            f"{statistics.median(e['drain_seconds'] for e in saves):.2f}s, "
            f"restore {r1['seconds']:.2f}s, resumed first step "
            f"{after['seconds']:.2f}s, kill to first resumed step "
            f"{after['t_wall'] - kill['t_wall']:.1f}s, kill to next "
            f"backend up {dev1['t_wall'] - kill['t_wall']:.1f}s (backend "
            f"init {dev1['backend_init_s']:.1f}s)")
        return ev, m, p, steps0

    def sharded_checks(self, ev, m, p, steps0, ref_losses):
        hbm = {int(k): v for k, v in m["hbm_in_use_after_init"].items()}
        self.say(f"mesh {m['mesh']} device ids {m['mesh_device_ids']} "
                 f"(axes fsdp x tp), coords {m['mesh_device_coords']}")
        if not self.a.rehearsal:  # the CPU backend reports no memory stats
            check(all(hbm.values())
                  and max(hbm.values()) < MAX_HBM_IMBALANCE * min(
                      hbm.values()),
                  f"bytes_in_use after init differs by >= "
                  f"{MAX_HBM_IMBALANCE}x between devices: {hbm}")
        self.say(f"bytes_in_use after init per device: {hbm}")
        leaves = one(ev, "placement", inc=0)["leaves"]
        check(leaves and all(
            len(v["devices"]) == 4 and v["distinct_indices"] == v["parts"]
            and v["shard_fraction"] == 1 / v["parts"]
            for v in leaves.values()),
            f"a sharded leaf is not spread over four devices: {leaves}")
        self.say(f"{len(leaves)} sharded param leaves, each with four "
                 "addressable shards on four distinct devices: "
                 + ", ".join(f"{k} {v['spec']} 1/"
                             f"{round(1 / v['shard_fraction'])}"
                             for k, v in leaves.items()))
        c = p["collectives"]
        check(c["all-gather"] >= 1
              and c["all-reduce"] + c["reduce-scatter"] >= 1,
              f"the mesh's collectives are not in the compiled step: {c}")
        self.say(f"collectives in the compiled step: {c}; "
                 f"tpu_custom_call x{p['compiled_custom_calls']} inside "
                 "sharded_flash_attention's shard_map")
        for step, want in sorted(ref_losses.items()):
            got = steps0[step]["loss"]
            check(abs(got - want) <= TOL_LOSS_SHARDED * abs(want),
                  f"step {step}: loss {got} on the mesh, {want} on one "
                  f"device (tolerance {TOL_LOSS_SHARDED} relative)")
        self.say(f"losses on the mesh "
                 f"{[steps0[s]['loss'] for s in sorted(ref_losses)]} vs one "
                 f"device {[ref_losses[s] for s in sorted(ref_losses)]} "
                 f"(within {TOL_LOSS_SHARDED} relative)")

    # -- run ---------------------------------------------------------------

    def run(self):
        if self.a.chips == 4:
            ref = self.reference_on_one_device()
            self.sharded_checks(*self.elastic("fsdp2tp2"), ref)
        else:
            self.kernels()
            self.chip_release()
            self.elastic("one")
        self.say(f"total {time.monotonic() - self.t0:.1f}s wall")
        return self.device

    def close(self):
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        for seg in glob.glob(f"/dev/shm/dlrtpu_{self.job}_*"):
            os.unlink(seg)
        # what the children wrote, kept apart by where it ran: a later
        # rehearsal must not overwrite what came back from the chip
        d = self.device or {"platform": "none", "count": 0}
        keep = f"{d['platform']}-x{d['count']}"
        if d["platform"] == "tpu":  # a directory per chip run
            keep += time.strftime("-%Y%m%dT%H%M%SZ", time.gmtime())
        keep = os.path.join(HERE, "chiprun_out", "chip_smoke", keep)
        os.makedirs(keep, exist_ok=True)
        for name in os.listdir(self.workdir):
            if name.endswith((".jsonl", ".log")):
                shutil.copy(self.path(name), keep)
        shutil.rmtree(self.workdir, ignore_errors=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded train/kill/resume phase on an "
                        "fsdp=2 x tp=2 mesh and its one-device comparison")
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny widths, interpret-mode kernels, CPU: the "
                        "control flow only")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fail", choices=("digest",), default="",
                   help="make a phase fail, to see the exit code")
    # internal: what the children of this script are started with
    p.add_argument("--role", default="",
                   choices=("", "kernels", "hold", "probe", "worker"))
    p.add_argument("--out", default="")
    p.add_argument("--mesh", choices=("one", "fsdp2tp2"), default="one")
    p.add_argument("--plain", action="store_true")
    p.add_argument("--ckpt-dir", dest="ckpt_dir", default="")
    a = p.parse_args(argv)

    if a.role:
        try:
            return globals()[f"role_{a.role}"](a)
        except SmokeFailure as e:
            Recorder(a.out)("failure", message=str(e))
            print(f"chip_smoke {a.role}: FAILED: {e}", file=sys.stderr)
            return 1

    smoke = Smoke(a)
    try:
        device = smoke.run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        smoke.close()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
