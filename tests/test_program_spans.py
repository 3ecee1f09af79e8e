"""The program's own spans: the bridge to the profiler's clock, the span
tree of a save, a drain, a restore and a train step, the compile-request
counter, and the benchmark's readers of all of them on hand-made data.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import program_spans  # noqa: E402
from dlrover_tpu.ckpt.engine import CheckpointEngine  # noqa: E402
from dlrover_tpu.ckpt.shm_handler import shm_name  # noqa: E402
from dlrover_tpu.common.constants import ConfigKey, SpanName  # noqa: E402
from dlrover_tpu.common.multi_process import (  # noqa: E402
    unlink_shared_memory,
)
from dlrover_tpu.observability import compile_watch, tracing  # noqa: E402
from dlrover_tpu.observability.registry import (  # noqa: E402
    get_registry,
    reset_registry,
)
from dlrover_tpu.trainer.elastic import (  # noqa: E402
    ElasticTrainer,
    make_train_state,
)
from dlrover_tpu.parallel.mesh import plan_mesh  # noqa: E402

JOB = f"spantest{os.getpid()}"


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records who
    entered and left what, and on which thread."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name, threading.get_ident()))


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    FakeAnnotation.log = []
    tracing.reset_tracer()
    yield
    tracing.install_bridge(None)
    tracing.reset_tracer()
    unlink_shared_memory(shm_name(JOB, 0, 0))


def finished(name=None):
    spans = tracing.get_tracer().finished_spans()
    return [sp for sp in spans if name is None or sp.name == name]


# -- the bridge ---------------------------------------------------------------


def test_bridge_enters_and_leaves_on_the_spans_thread():
    tracing.install_bridge(FakeAnnotation)
    seen = {}

    def work():
        seen["ident"] = threading.get_ident()
        with tracing.span(SpanName.CKPT_DRAIN):
            with tracing.span(SpanName.CKPT_DRAIN_D2H_WAIT):
                pass

    t = threading.Thread(target=work, name="span-thread")
    t.start()
    t.join(10)
    assert not t.is_alive()
    drain, d2h = "dlrover:ckpt.drain", "dlrover:ckpt.drain.d2h_wait"
    assert FakeAnnotation.log == [
        ("enter", drain, seen["ident"]), ("enter", d2h, seen["ident"]),
        ("exit", d2h, seen["ident"]), ("exit", drain, seen["ident"]),
    ]
    assert seen["ident"] != threading.get_ident()


def test_bridge_leaves_its_annotation_when_the_block_raises():
    tracing.install_bridge(FakeAnnotation)
    with pytest.raises(ValueError):
        with tracing.span(SpanName.TRAIN_STEP):
            raise ValueError("boom")
    assert [e[0] for e in FakeAnnotation.log] == ["enter", "exit"]
    assert finished(SpanName.TRAIN_STEP)[0].status == "error"


def test_no_annotation_when_uninstalled_ended_by_hand_or_off(monkeypatch):
    with tracing.span(SpanName.TRAIN_STEP):  # no bridge installed
        pass
    tracing.install_bridge(FakeAnnotation)
    tracing.span(SpanName.TRAIN_STEP).end()  # no ``with``: no thread to sit on
    tracing.install_bridge(None)
    with tracing.span(SpanName.TRAIN_STEP):  # uninstalled again
        pass
    assert len(finished(SpanName.TRAIN_STEP)) == 3
    monkeypatch.setenv(ConfigKey.TRACE, "0")
    tracing.reset_tracer()
    tracing.install_bridge(FakeAnnotation)
    with tracing.span(SpanName.TRAIN_STEP):  # the shared no-op
        pass
    assert FakeAnnotation.log == []
    assert tracing.get_tracer().counts()["started"] == 0


def test_tracing_imports_without_jax():
    code = ("import sys; import dlrover_tpu.observability.tracing as t; "
            "t.install_bridge(None); "
            "assert 'jax' not in sys.modules, 'tracing imported jax'")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_compile_watcher_is_never_the_one_to_import_jax():
    """A serving replica with a toy engine notes shapes too: seconds of
    jax import inside its first request would be its TTFT."""
    code = ("import sys; from dlrover_tpu.observability import compile_watch; "
            "compile_watch.get_watcher().note('engine.step', batch=1); "
            "assert 'jax' not in sys.modules, 'compile_watch imported jax'")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_worker_init_installs_the_profilers_annotation(monkeypatch):
    from dlrover_tpu import worker

    monkeypatch.setenv(ConfigKey.COMPILE_CACHE, "off")
    worker.init(initialize_jax_distributed=False)
    try:
        assert tracing._bridge is jax.profiler.TraceAnnotation
        with tracing.span(SpanName.TRAIN_STEP):  # no profile: a flag check
            pass
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


# -- save, drain, restore -----------------------------------------------------


def tiny_state():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       NamedSharding(mesh, P("data", "model")))
    b = jax.device_put(jnp.ones((8,), jnp.float32),
                       NamedSharding(mesh, P(None)))
    return {"params": {"w": w, "b": b}, "step": 3}


def engine_for(tmp_path):
    return CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0)


def test_save_drain_restore_leave_the_span_tree(tmp_path):
    tracing.install_bridge(FakeAnnotation)
    engine = engine_for(tmp_path)
    state = tiny_state()
    assert engine.save_to_memory(7, state)
    assert engine.wait_drained(30)
    restored, step = engine.load(jax.tree.map(lambda x: x, state))
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))

    def one(name):
        (sp,) = finished(name)
        return sp

    save = one(SpanName.CKPT_SAVE_MEMORY)
    assert save.parent_id is None
    for name in (SpanName.CKPT_SAVE_READY, SpanName.CKPT_SAVE_PLAN):
        assert one(name).parent_id == save.span_id
    assert not finished(SpanName.CKPT_SAVE_REGISTER)  # no agent, no dict
    plan = one(SpanName.CKPT_SAVE_PLAN)
    nbytes = 64 * 4 + 8 * 4
    assert plan.attrs["leaves"] == 3 and plan.attrs["bytes"] == nbytes

    # the drain thread continues the save's trace
    drain = one(SpanName.CKPT_DRAIN)
    assert drain.trace_id == save.trace_id
    assert drain.parent_id == save.span_id
    assert drain.attrs["bytes"] == nbytes
    phases = [one(name) for name in (
        SpanName.CKPT_DRAIN_D2H_WAIT, SpanName.CKPT_DRAIN_SHM_WRITE,
        SpanName.CKPT_DRAIN_PUBLISH)]
    assert all(sp.parent_id == drain.span_id for sp in phases)
    assert [sp.start_t for sp in phases] == sorted(
        sp.start_t for sp in phases)
    write = phases[1]
    assert write.attrs["copy_s"] >= 0 and write.attrs["checksum_s"] > 0
    assert (write.attrs["copy_s"] + write.attrs["checksum_s"]
            <= write.end_t - write.start_t)
    main = threading.get_ident()
    drain_threads = {ident for kind, name, ident in FakeAnnotation.log
                     if name == "dlrover:ckpt.drain.d2h_wait"}
    assert len(drain_threads) == 1 and main not in drain_threads

    # the restore is a trace of its own, one span a rung that was tried
    restore = one(SpanName.CKPT_RESTORE)
    assert restore.trace_id != save.trace_id and restore.parent_id is None
    rungs = [one(name) for name in (
        SpanName.CKPT_RESTORE_WAIT_DRAINED, SpanName.CKPT_RESTORE_RESHARD,
        SpanName.CKPT_RESTORE_VERIFY, SpanName.CKPT_RESTORE_CONSISTENT,
        SpanName.CKPT_RESTORE_SHM)]
    assert all(sp.parent_id == restore.span_id for sp in rungs)
    for name in (SpanName.CKPT_RESTORE_REPLICA_PULL,
                 SpanName.CKPT_CHAIN_RESTORE, SpanName.CKPT_RESTORE_PEER,
                 SpanName.CKPT_RESTORE_STORAGE):
        assert not finished(name)  # shm served it: no further rung
    shm = rungs[-1]
    reads = finished(SpanName.CKPT_RESTORE_READ)
    puts = finished(SpanName.CKPT_RESTORE_H2D)
    # w is four shards of 64 bytes; b (32 bytes) goes to each of 4 devices
    assert sum(sp.attrs["bytes"] for sp in reads
               if sp.attrs["bytes"] == 64) == 256
    assert reads and puts
    # each into a staging chunk the ring made on the pool's threads
    rings = finished(SpanName.CKPT_RESTORE_RING)
    assert rings and all(sp.attrs["staged"] for sp in reads)
    assert all(sp.parent_id == shm.span_id and sp.trace_id == restore.trace_id
               for sp in reads + puts + rings)
    pool_threads = {ident for kind, name, ident in FakeAnnotation.log
                    if name == "dlrover:ckpt.restore.read"}
    assert pool_threads and main not in pool_threads
    # what the readers take from it
    top, inside = program_spans.last_restore(
        sorted(finished(), key=lambda sp: sp.start_t))
    assert top is restore
    assert {sp.span_id for sp in rungs + reads + puts + rings} == {
        sp.span_id for sp in inside}


def test_tracing_off_makes_no_span_and_no_annotation(tmp_path, monkeypatch):
    monkeypatch.setenv(ConfigKey.TRACE, "0")
    tracing.reset_tracer()
    tracing.install_bridge(FakeAnnotation)
    engine = engine_for(tmp_path)
    state = tiny_state()
    assert engine.save_to_memory(7, state)
    assert engine.wait_drained(30)
    _, step = engine.load(jax.tree.map(lambda x: x, state))
    assert step == 7
    trainer, train_state, batch = tiny_trainer()
    trainer.train_step(train_state, batch)
    counts = tracing.get_tracer().counts()
    assert counts["started"] == 0 and counts["ring"] == 0
    assert FakeAnnotation.log == []


# -- the train step and the compile counter -----------------------------------


def tiny_trainer():
    def loss_fn(params, microbatch):
        return jnp.mean((microbatch @ params["w"]) ** 2)

    optimizer = optax.sgd(0.1)
    trainer = ElasticTrainer(loss_fn=loss_fn, optimizer=optimizer,
                             global_batch_size=4, micro_batch_per_replica=2)
    trainer.configure_for_world(plan_mesh(1))
    state = make_train_state(
        {"w": jnp.full((4, 4), 0.5, jnp.float32)}, optimizer)
    batch = jnp.ones((2, 2, 4), jnp.float32)
    return trainer, jax.block_until_ready(state), batch


@pytest.fixture()
def fresh_watcher():
    reset_registry()
    compile_watch.reset_watcher()
    yield compile_watch.get_watcher()
    reset_registry()
    compile_watch.reset_watcher()


def test_train_step_spans_and_compile_requests(fresh_watcher):
    watcher = fresh_watcher
    trainer, state, batch = tiny_trainer()
    before = watcher.compile_requests()
    state, _ = trainer.train_step(state, batch)
    jax.block_until_ready(state)
    after_first = watcher.compile_requests()
    state, result = trainer.train_step(state, batch)
    assert np.isfinite(float(result.loss))
    first, second = finished(SpanName.TRAIN_STEP)
    assert after_first > before                     # the step compiled
    assert first.attrs["compiles"] == after_first - before
    assert first.attrs["accum"] == second.attrs["accum"] == 2
    assert "compiles" not in second.attrs           # and only once
    assert watcher.compile_requests() == after_first
    text = get_registry().render()
    assert f"dlrover_compile_requests_total {after_first}" in text
    assert f"dlrover_compile_seconds_count {after_first}" in text
    # the reader takes the same number, and nothing from an empty context
    reader = bench_run.load_reader("train.compile_requests")
    assert reader.read({"job": {"steps": 2}}) == float(after_first)
    assert reader.read({"job": {}}) is None


def test_tracing_off_reads_no_compile_counter_in_the_step(
        fresh_watcher, monkeypatch):
    """The two reads of the requests counter fill a span attribute:
    with tracing off the step makes neither, and still notes its
    signature with the watcher."""
    monkeypatch.setattr(
        tracing, "_tracer", tracing.Tracer(enabled=False))
    reads = []
    monkeypatch.setattr(fresh_watcher, "compile_requests",
                        lambda: reads.append(1) or 0)
    trainer, state, batch = tiny_trainer()
    state, result = trainer.train_step(state, batch)
    assert np.isfinite(float(result.loss))
    assert reads == [] and finished(SpanName.TRAIN_STEP) == []
    assert fresh_watcher.compile_count("trainer.train_step") == 1


# -- the benchmark's readers on hand-made data --------------------------------


def put(tracer, name, start, end, parent=None, **attrs):
    sp = tracer.span(name, parent=parent, **attrs)
    sp.start_t = start
    sp.end()
    sp.end_t = end
    return sp


@pytest.fixture()
def hand_made_ring(monkeypatch):
    """A run as the flash-save cell makes it, seconds on a made-up clock:
    two warm-up steps, the set-up save and its quiet drain, the save that
    opens the window, seven steps with one save among them, two
    restores."""
    tracer = tracing.Tracer(enabled=True, ring_size=1000)
    monkeypatch.setattr(tracing, "_tracer", tracer)
    S = SpanName
    for start in (0.0, 0.2):                          # warm-up, 50 ms each
        put(tracer, S.TRAIN_STEP, start, start + 0.05)

    def save(at, plan_s, d2h, write, checksum_s):
        top = put(tracer, S.CKPT_SAVE_MEMORY, at, at + 0.1)
        put(tracer, S.CKPT_SAVE_PLAN, at + 0.01, at + 0.01 + plan_s,
            parent=top.context, leaves=3, bytes=100)
        drain = put(tracer, S.CKPT_DRAIN, d2h[0], write[1],
                    parent=top.context, bytes=100)
        put(tracer, S.CKPT_DRAIN_D2H_WAIT, *d2h, parent=drain.context)
        put(tracer, S.CKPT_DRAIN_SHM_WRITE, *write, parent=drain.context,
            copy_s=write[1] - write[0] - checksum_s, checksum_s=checksum_s)

    save(1.0, 0.030, (1.1, 2.0), (2.0, 3.0), 0.5)     # set-up, quiet
    save(3.9, 0.020, (4.05, 5.0), (5.0, 6.78), 0.9)   # opens the window
    starts = (4.06, 4.26, 5.10, 5.40, 6.60, 6.75, 6.95)
    for i, start in enumerate(starts):                # 1..7 ms of dispatch
        put(tracer, S.TRAIN_STEP, start, start + 0.001 * (i + 1), accum=2)
    save(6.80, 0.040, (6.90, 7.5), (7.5, 8.0), 0.3)   # the window's save

    def restore(at, verify_s, reads, puts):
        top = put(tracer, S.CKPT_RESTORE, at, at + 3.0)
        put(tracer, S.CKPT_RESTORE_VERIFY, at + 0.2, at + 0.2 + verify_s,
            parent=top.context)
        shm = put(tracer, S.CKPT_RESTORE_SHM, at + 1.2, at + 3.0,
                  parent=top.context)
        for a, b in reads:
            put(tracer, S.CKPT_RESTORE_READ, at + a, at + b,
                parent=shm.context, bytes=50)
        for a, b in puts:
            put(tracer, S.CKPT_RESTORE_H2D, at + a, at + b,
                parent=shm.context, bytes=50)

    restore(9.0, 0.1, [(1.3, 1.4)], [(1.4, 1.5)])
    restore(13.0, 1.0, [(1.3, 1.8), (1.3, 1.9)], [(1.8, 1.9), (1.9, 2.1)])
    return {"job": {"steps": 7, "saves": 1}, "trace_raw": None, "trace": []}


RING_EXPECT = {
    # the window's own save only (the 30 and 20 ms ones come before it)
    "ckpt.save_plan_ms": 40.0,
    # drains over the window's steps: 4.05-5.0 and 6.90-7.5
    "ckpt.drain_d2h_s": (0.95 + 0.6) / 2,
    # 5.0-6.78 only: the last write (7.5-8.0) has a quiet device
    "ckpt.drain_write_s": 1.78,
    "ckpt.drain_checksum_s": 0.9,
    # steps starting at 4.06 and 4.26 (d2h open 4.05-5.0): 0.20 and 0.84 s;
    # the one at 6.95 has no next. The mean of them
    "ckpt.step_ms_in_d2h": 520.0,
    # 5.10 -> 0.30, 5.40 -> 1.20, 6.60 -> 0.15 (mean 550, median 300: one
    # long hold moves the mean); 6.75 -> 6.95 holds a save
    "ckpt.step_ms_in_write": 550.0,
    "ckpt.restore_verify_s": 1.0,       # the last restore's
    "ckpt.restore_read_s": 0.5 + 0.6,
    "ckpt.restore_h2d_s": 0.1 + 0.2,
    "train.dispatch_ms": 4.0,           # median of 1..7 ms, warm-up left out
}


@pytest.mark.parametrize("metric", sorted(RING_EXPECT))
def test_reader_on_a_hand_made_ring(hand_made_ring, metric):
    value = bench_run.load_reader(metric).read(hand_made_ring)
    assert isinstance(value, float)
    assert value == pytest.approx(RING_EXPECT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(RING_EXPECT))
def test_reader_gives_nothing_once_the_ring_dropped_spans(
        hand_made_ring, metric, monkeypatch):
    tracer = tracing.Tracer(enabled=True, ring_size=4)
    monkeypatch.setattr(tracing, "_tracer", tracer)
    for i in range(6):
        put(tracer, SpanName.TRAIN_STEP, i, i + 0.5)
    assert tracer.dropped() == 2
    assert bench_run.load_reader(metric).read(hand_made_ring) is None


HLO = ' = bf16[1]{0} custom-call(%x), custom_call_target="tpu_custom_call"'
HAND_MADE_TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step_fn(1)", 0, 300], ["jit_step_fn(1)", 400, 400]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[] fusion()", 0, 100],
            ["%flash_fwd.3" + HLO, 200, 40],
            ["%flash_bwd_dq.2" + HLO, 240, 30],
            ["%flash_bwd_dkv.2" + HLO, 270, 30],
            # names the kernel as its operand: not the kernel
            ["%slice-start.9 = bf16[1]{0} slice-start(%flash_fwd.3)",
             400, 100],
            ["%flash_fwd.3" + HLO, 700, 40],
            ["%flash_bwd_dq.2" + HLO, 740, 30],
            ["%flash_bwd_dkv.2" + HLO, 770, 30],
            # after the last whole step: not counted
            ["%flash_fwd.3" + HLO, 900, 40]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ["dlrover:train.step", 0, 600], ["bench:wait_loss", 0, 900]]},
        {"name": "ckpt-drain", "events": [
            ["dlrover:ckpt.drain", 90, 360],
            ["dlrover:ckpt.drain.d2h_wait", 90, 160],
            ["dlrover:ckpt.drain.shm_write", 250, 200]]}]},
]}


def test_idle_by_program_span_on_a_hand_made_trace(capsys):
    # busy 0-100, 200-300, 400-500, 700-800, 900-940: gaps 100-200 (the
    # drain waits for D2H), 300-400 (it writes), 500-700 (the step's
    # dispatch is open until 600, then nothing), 800-900 (nothing)
    gaps = program_spans.idle_gaps(HAND_MADE_TRACE)
    assert gaps == [(100, 200), (300, 400), (500, 700), (800, 900)]
    by_span = program_spans.idle_by_span(
        gaps, program_spans.annotations(HAND_MADE_TRACE))
    assert by_span == pytest.approx({
        "ckpt.drain.d2h_wait": 100e-9, "ckpt.drain.shm_write": 100e-9,
        "train.step": 100e-9, "none": 200e-9})
    reader = bench_run.load_reader("device.idle_in_program_span_pct")
    value = reader.read({"trace_raw": HAND_MADE_TRACE})
    assert value == pytest.approx(100.0 * 200 / 500)
    note = capsys.readouterr().out
    assert '"note": "idle_by_program_span"' in note
    # a program without the bridge (this PR's parent) has nothing to read
    bare = {"planes": [p for p in HAND_MADE_TRACE["planes"]
                       if p["name"] != "/host:CPU"]}
    assert reader.read({"trace_raw": bare}) is None
    assert reader.read({"trace_raw": None}) is None


def test_a_span_the_profile_cut_off_is_placed_from_the_ring(
        hand_made_ring, capsys):
    """The profile ends at 4.7 s, inside the drain's wait for D2H (4.05 to
    5.0 s): that span has no annotation, the two steps before it have,
    each entered 5 us after the tracer read its clock and left 5 us
    before."""

    def ns(t):  # a profile counts from its own start, here 4 s
        return int((t - 4.0) * 1e9) + 5000

    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step_fn(1)", ns(4.10), int(0.6e9)]]},
            {"name": "XLA Ops", "events": [     # idle from 4.2 to 4.3 s
                ["%a", ns(4.10), int(0.1e9)], ["%b", ns(4.30), int(0.4e9)]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["dlrover:train.step", ns(4.06), int(0.001e9) - 10000],
            ["dlrover:train.step", ns(4.26), int(0.002e9) - 10000]]}]},
    ]}
    ctx = {**hand_made_ring, "trace_raw": trace, "step_module": "step_fn"}
    placed = {name: (a, b) for name, a, b
              in program_spans.on_profilers_clock(ctx)
              if ns(4.0) < a < ns(5.0)}
    assert len(placed) > 2  # the ring's spans, not the two annotations
    a, b = placed["ckpt.drain.d2h_wait"]
    assert a == pytest.approx(ns(4.05), abs=1000)
    assert b == pytest.approx(ns(5.0), abs=1000)
    reader = bench_run.load_reader("device.idle_in_program_span_pct")
    assert reader.read(ctx) == pytest.approx(100.0)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["seconds"] == pytest.approx({"ckpt.drain.d2h_wait": 0.1})
    assert note["inside_step_programs_s"] == pytest.approx(0.1)
    # without the ring only what the profile holds: the step's dispatch
    # covers 2 ms of the gap, nothing the rest
    assert reader.read({**ctx, "job": {}}) == pytest.approx(0.0)


@pytest.mark.parametrize("missed", [0, 9])
def test_clock_offset_tie_goes_to_the_offset_other_spans_vote_for(missed):
    """Ten steps of equal length 100 ms apart, and a save's plan. One
    step's annotation, the first or the last, is off by 150 us: its pair
    misses the test, so the true offset gets nine step votes, and so does
    the offset one step away on that side (every other annotation taken
    for its neighbour). The plan's vote decides."""
    tracer = tracing.Tracer(enabled=True, ring_size=100)
    starts = [1.0 + 0.1 * i for i in range(10)]
    for start in starts:
        put(tracer, SpanName.TRAIN_STEP, start, start + 0.001)
    put(tracer, SpanName.CKPT_SAVE_PLAN, 1.25, 1.27)
    spans = tracer.finished_spans()
    true = 5e9

    def at(t, late=0):
        return int(t * 1e9 + true) + late

    notes = [("train.step", at(start, 150_000 * (i == missed)),
              at(start + 0.001, 150_000 * (i == missed)))
             for i, start in enumerate(starts)]
    notes.append(("ckpt.save.plan", at(1.25), at(1.27)))
    for order in (notes, notes[::-1]):
        offset = program_spans.clock_offset_ns(order, spans)
        assert offset == pytest.approx(true, abs=1000)


def test_waterfalls_of_the_hand_made_run(hand_made_ring):
    spans = program_spans.ring(hand_made_ring)
    drains = program_spans.drain_waterfall(hand_made_ring, spans)
    assert [d["under_steps"] for d in drains] == [False, True, True]
    assert drains[1]["d2h_wait_s"] == pytest.approx(0.95)
    assert drains[1]["shm_write_s"] == pytest.approx(1.78)
    assert drains[1]["checksum_s"] == 0.9
    assert drains[1]["copy_s"] == pytest.approx(0.88)
    restore = program_spans.restore_waterfall(spans)
    assert restore["load_s"] == pytest.approx(3.0)
    assert restore["rungs"] == pytest.approx({
        "ckpt.restore.verify": 1.0, "ckpt.restore.shm": 1.8})
    assert restore["read"]["spans"] == 2 and restore["read"]["bytes"] == 100
    assert restore["read"]["thread_s"] == pytest.approx(1.1)
    assert restore["h2d"]["first_start_s"] == pytest.approx(1.8)
    assert restore["h2d"]["last_end_s"] == pytest.approx(2.1)


@pytest.mark.parametrize("metric,kernel_ns,matmuls", [
    ("flash_fwd_roofline", 2 * 40, 2),
    ("flash_bwd_roofline", 2 * 60, 5),
])
def test_named_kernel_roofline_on_a_hand_made_trace(
        metric, kernel_ns, matmuls):
    fields = {"hidden_size": 256, "num_attention_heads": 2,
              "num_hidden_layers": 1}
    ctx = {"trace_raw": HAND_MADE_TRACE, "step_module": "step_fn",
           "peaks": {"bf16_flops_per_s": 1e15}, "fields": fields,
           "job": {"grad_accum": 2, "seq": 128, "rows_per_replica": 1}}
    # one causal score-sized matmul: 2 heads x 2 x 128 x 128 x 129 / 2
    one = 2 * 2.0 * 128 * 128 * 129 / 2
    least_s = 2 * (2 * 1 * matmuls * one) / 1e15    # two whole steps
    value = bench_run.load_reader(metric).read(ctx)
    assert value == pytest.approx(100.0 * least_s / (kernel_ns * 1e-9))
    unnamed = {"planes": [{"name": "/device:TPU:0", "lines": [
        HAND_MADE_TRACE["planes"][0]["lines"][0],
        {"name": "XLA Ops", "events": [["%closed_call.6" + HLO, 200, 40]]},
    ]}]}
    assert bench_run.load_reader(metric).read(
        {**ctx, "trace_raw": unnamed}) is None


def test_a_step_program_the_profile_cut_is_left_out_of_the_roofline():
    """A third step program whose module event is whole but whose second
    forward call fell outside the profile: one call where the others
    have two. Counted as a whole step it would raise the share by 6/5."""
    from benchmarks.harness import named_kernels
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step_fn(1)", 0, 300], ["jit_step_fn(1)", 400, 300],
            ["jit_step_fn(1)", 800, 300]]},
        {"name": "XLA Ops", "events": [
            ["%flash_fwd.3" + HLO, 10, 40], ["%flash_fwd.3" + HLO, 110, 40],
            ["%flash_fwd.3" + HLO, 410, 40], ["%flash_fwd.3" + HLO, 510, 40],
            ["%flash_fwd.3" + HLO, 810, 40]]}]}
    assert named_kernels.kernel_seconds(
        plane, ("flash_fwd.",), "step_fn") == (pytest.approx(160e-9), 2, 2)
    assert named_kernels.kernel_seconds(
        plane, ("flash_bwd_",), "step_fn") == (0, 0, 0)

