"""Device-plane observability: HBM ledger algebra, pressure episodes,
OOM-forensics bundles, recompile-storm detection/attribution, and the
fleet memory monitor (ISSUE 20).

Everything here runs on CPU: accountants take an explicit
``limit_bytes`` (the synthetic-HBM path) and ``device_bytes`` is
monkeypatched where the device view must be deterministic. Clocks are
fake wherever windows/staleness matter.
"""

import json
import os
import threading
from types import SimpleNamespace

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.common.constants import ChaosSite, MetricLabel
from dlrover_tpu.observability import memory as mem
from dlrover_tpu.observability.compile_watch import CompileWatcher
from dlrover_tpu.observability.flight_recorder import (
    REASON_MEMORY,
    FlightRecorder,
)
from dlrover_tpu.observability.journal import EventJournal, JournalEvent
from dlrover_tpu.observability.memory import (
    FleetMemoryMonitor,
    MemoryAccountant,
    kv_bytes_per_slot_theoretical,
    max_slots_ceiling,
)
from dlrover_tpu.observability.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _reset_injector():
    yield
    chaos.reset_injector()


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _kinds(journal):
    return [e["kind"] for e in journal.events()]


def _pressure_events(journal):
    return [e for e in journal.events()
            if e["kind"] == JournalEvent.MEMORY_PRESSURE]


def _acct(monkeypatch=None, device=(0, 0), **kw):
    """Accountant on a private registry with a deterministic device view."""
    if monkeypatch is not None:
        monkeypatch.setattr(mem, "device_bytes", lambda: device)
    kw.setdefault("registry", MetricsRegistry())
    return MemoryAccountant(**kw)


# -- ledger algebra ---------------------------------------------------------


def test_register_rejects_unknown_category():
    acct = _acct()
    with pytest.raises(ValueError):
        acct.register("vram", "buf", 1024)
    with pytest.raises(ValueError):
        acct.release("vram", "buf")


def test_register_replaces_release_idempotent():
    acct = _acct()
    acct.register(MetricLabel.MEM_KV_CACHE, "kv", 100)
    # re-register replaces the claim (buffers resize, never double-count)
    acct.register(MetricLabel.MEM_KV_CACHE, "kv", 40)
    assert acct.bytes_for(MetricLabel.MEM_KV_CACHE) == 40
    assert acct.release(MetricLabel.MEM_KV_CACHE, "kv") == 40
    # idempotent: a second release of the same name is 0 bytes, no error
    assert acct.release(MetricLabel.MEM_KV_CACHE, "kv") == 0
    assert acct.total_bytes() == 0


def test_adjust_registers_and_drops():
    acct = _acct()
    acct.adjust(MetricLabel.MEM_PREFIX_CACHE, "pool", 256)
    assert acct.bytes_for(MetricLabel.MEM_PREFIX_CACHE) == 256
    acct.adjust(MetricLabel.MEM_PREFIX_CACHE, "pool", 0)
    assert acct.bytes_for(MetricLabel.MEM_PREFIX_CACHE) == 0


def test_watermarks_survive_release_and_step_marks():
    acct = _acct()
    acct.register(MetricLabel.MEM_ACTIVATIONS, "a", 500)
    acct.step_mark(1)
    acct.release(MetricLabel.MEM_ACTIVATIONS, "a")
    acct.register(MetricLabel.MEM_ACTIVATIONS, "b", 200)
    acct.step_mark(2)
    snap = acct.snapshot()
    assert snap["watermarks"][MetricLabel.MEM_ACTIVATIONS] == 500
    assert snap["peak_total_bytes"] == 500
    rows = snap["step_watermarks"]
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0][MetricLabel.MEM_ACTIVATIONS] == 500
    assert rows[1][MetricLabel.MEM_ACTIVATIONS] == 200


def test_snapshot_top_buffers_sorted_and_gauges_render():
    reg = MetricsRegistry()
    acct = _acct(registry=reg)
    acct.register(MetricLabel.MEM_PARAMS, "small", 10)
    acct.register(MetricLabel.MEM_KV_CACHE, "big", 900)
    snap = acct.snapshot()
    assert snap["top_buffers"][0] == {
        "category": MetricLabel.MEM_KV_CACHE, "name": "big", "bytes": 900}
    assert snap["categories"][MetricLabel.MEM_PARAMS] == 10
    text = reg.render()
    assert 'dlrover_memory_bytes{category="kv_cache"} 900' in text
    assert 'dlrover_memory_watermark_bytes{category="kv_cache"} 900' in text


# -- reconciliation ---------------------------------------------------------


def test_reconcile_headroom_and_unattributed(monkeypatch):
    acct = _acct(monkeypatch, device=(700, 0), limit_bytes=1000)
    acct.register(MetricLabel.MEM_PARAMS, "w", 600)
    out = acct.reconcile()
    # device in-use (700) exceeds the ledger (600): used = max of both
    assert out["limit_bytes"] == 1000
    assert out["headroom_bytes"] == 300
    assert out["headroom_frac"] == 0.3
    assert out["unattributed_bytes"] == 100
    assert out["degraded"] is False


def test_reconcile_synthetic_env_limit(monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_HBM_LIMIT_BYTES", "2000")
    acct = _acct(monkeypatch, device=(0, 0))
    acct.register(MetricLabel.MEM_STAGING, "frame", 500)
    out = acct.reconcile()
    assert out["limit_bytes"] == 2000
    assert out["headroom_frac"] == 0.75
    assert acct.limit_bytes() == 2000


def test_degraded_journaled_once_per_episode(monkeypatch):
    journal = EventJournal()
    acct = _acct(monkeypatch, device=None, journal=journal)
    acct.reconcile()
    acct.reconcile()
    assert _kinds(journal).count(JournalEvent.MEMORY_DEGRADED) == 1
    # device view returns: episode closes, the next outage journals again
    monkeypatch.setattr(mem, "device_bytes", lambda: (0, 0))
    assert acct.reconcile()["degraded"] is False
    monkeypatch.setattr(mem, "device_bytes", lambda: None)
    acct.reconcile()
    assert _kinds(journal).count(JournalEvent.MEMORY_DEGRADED) == 2


# -- pressure episodes ------------------------------------------------------


def test_pressure_episode_hysteresis(monkeypatch):
    journal = EventJournal()
    reg = MetricsRegistry()
    captured = []
    acct = _acct(monkeypatch, registry=reg, journal=journal,
                 limit_bytes=1000, pressure_frac=0.2,
                 breach_hook=captured.append)
    acct.register(MetricLabel.MEM_PARAMS, "w", 850)  # frac 0.15 < 0.2
    acct.reconcile()
    acct.reconcile()  # still breached: same episode, no second event
    assert len(_pressure_events(journal)) == 1
    data = _pressure_events(journal)[0]["data"]
    assert data["category"] == MetricLabel.MEM_PARAMS
    assert data["headroom_frac"] == 0.15
    assert data["forced"] is False
    assert captured and captured[0] == data  # hook sees the journal payload

    # recovery inside the hysteresis band does NOT re-arm
    acct.register(MetricLabel.MEM_PARAMS, "w", 790)  # frac 0.21 < 0.22
    acct.reconcile()
    acct.register(MetricLabel.MEM_PARAMS, "w", 850)
    acct.reconcile()
    assert len(_pressure_events(journal)) == 1

    # recovery past threshold + margin re-arms; the next breach journals
    acct.register(MetricLabel.MEM_PARAMS, "w", 700)  # frac 0.3 >= 0.22
    acct.reconcile()
    acct.register(MetricLabel.MEM_PARAMS, "w", 900)
    acct.reconcile()
    assert len(_pressure_events(journal)) == 2
    assert 'dlrover_memory_pressure_total{category="params"} 2' in (
        reg.render())


def test_no_pressure_without_limit(monkeypatch):
    journal = EventJournal()
    acct = _acct(monkeypatch, journal=journal)  # limit 0 = unknown
    acct.register(MetricLabel.MEM_KV_CACHE, "kv", 10 ** 12)
    acct.reconcile()
    assert _pressure_events(journal) == []


# -- OOM forensics: memory.json bundle round-trip ---------------------------


def test_memory_json_bundle_roundtrip(tmp_path, monkeypatch):
    journal = EventJournal()
    reg = MetricsRegistry()
    acct = _acct(monkeypatch, registry=reg, journal=journal,
                 limit_bytes=1 << 20)
    acct.register(MetricLabel.MEM_KV_CACHE, "kv_pool", 4096)
    acct.step_mark(3)
    acct.reconcile()
    fr = FlightRecorder("worker_0", out_dir=str(tmp_path / "fr"),
                        journal=journal, registry=reg, cooldown_s=0.0,
                        memory_snapshot_fn=acct.snapshot)
    path = fr.capture(REASON_MEMORY, extra={"category": "kv_cache"})
    assert path is not None
    with open(os.path.join(path, "memory.json")) as f:
        snap = json.load(f)
    assert snap["categories"][MetricLabel.MEM_KV_CACHE] == 4096
    assert snap["reconcile"]["limit_bytes"] == 1 << 20
    assert snap["step_watermarks"][0]["step"] == 3
    assert any(b["name"] == "kv_pool" for b in snap["top_buffers"])


def test_breach_hook_captures_bundle(tmp_path, monkeypatch):
    """The wiring master.py/worker.py uses: breach_hook → capture →
    bundle whose memory.json replays the breach offline."""
    journal = EventJournal()
    reg = MetricsRegistry()
    acct = _acct(monkeypatch, registry=reg, journal=journal,
                 limit_bytes=1000, pressure_frac=0.5)
    fr = FlightRecorder("worker_0", out_dir=str(tmp_path / "fr"),
                        journal=journal, registry=reg, cooldown_s=0.0,
                        memory_snapshot_fn=acct.snapshot)
    acct.set_breach_hook(lambda data: fr.capture(REASON_MEMORY, extra=data))
    acct.register(MetricLabel.MEM_OPT_STATE, "adam", 900)
    acct.reconcile()
    bundles = os.listdir(str(tmp_path / "fr"))
    assert len(bundles) == 1 and REASON_MEMORY in bundles[0]
    bdir = os.path.join(str(tmp_path / "fr"), bundles[0])
    with open(os.path.join(bdir, "memory.json")) as f:
        snap = json.load(f)
    assert snap["categories"][MetricLabel.MEM_OPT_STATE] == 900
    with open(os.path.join(bdir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["category"] == MetricLabel.MEM_OPT_STATE


# -- chaos drill: the mem.pressure site -------------------------------------


@pytest.mark.chaos
def test_mem_pressure_chaos_drill(tmp_path, monkeypatch):
    """An injected error at ``mem.pressure`` forces the whole forensics
    arc — pressure journal + OOM bundle with parseable memory.json —
    without actually exhausting the device (DLR016 drill for the site)."""
    journal = EventJournal()
    reg = MetricsRegistry()
    # headroom is comfortable: only the injected fault can breach
    acct = _acct(monkeypatch, registry=reg, journal=journal,
                 limit_bytes=1 << 30, source="worker_0")
    fr = FlightRecorder("worker_0", out_dir=str(tmp_path / "fr"),
                        journal=journal, registry=reg, cooldown_s=0.0,
                        memory_snapshot_fn=acct.snapshot)
    acct.set_breach_hook(lambda data: fr.capture(REASON_MEMORY, extra=data))
    acct.register(MetricLabel.MEM_KV_CACHE, "kv", 1024)

    chaos.configure(f"{ChaosSite.MEM_PRESSURE}:error@times=1", seed=7)
    out = acct.reconcile()
    assert out["headroom_frac"] > 0.9  # the device was NOT actually full

    pressure = _pressure_events(journal)
    assert len(pressure) == 1
    assert pressure[0]["data"]["forced"] is True
    bundles = os.listdir(str(tmp_path / "fr"))
    assert len(bundles) == 1
    with open(os.path.join(str(tmp_path / "fr"), bundles[0],
                           "memory.json")) as f:
        snap = json.load(f)
    assert snap["categories"][MetricLabel.MEM_KV_CACHE] == 1024

    # the rule consumed itself (times=1): the next sweep is clean and the
    # episode hysteresis still applies — no event flood after the drill
    acct.reconcile()
    assert len(_pressure_events(journal)) == 1


# -- compile watch ----------------------------------------------------------


def test_compile_note_hit_miss_counters():
    reg = MetricsRegistry()
    w = CompileWatcher(registry=reg, storm_threshold=100)
    assert w.note("prefill", batch=8, seq_len=128) is True
    assert w.note("prefill", batch=8, seq_len=128) is False  # cache hit
    assert w.note("prefill", batch=8, seq_len=256) is True
    assert w.compile_count("prefill") == 2
    text = reg.render()
    assert 'dlrover_compile_total{fn="prefill"} 2' in text
    assert 'dlrover_compile_cache_hits_total{fn="prefill"} 1' in text
    assert 'dlrover_compile_distinct_signatures{fn="prefill"} 2' in text


@pytest.mark.parametrize("dims,first_seen", [
    ([{"batch": 4}, {"batch": 4}], [True, False]),
    ([{"batch": 4}, {"batch": 8}, {"batch": 4}], [True, True, False]),
])
def test_compile_note_says_whether_the_signature_is_first_seen(
        dims, first_seen):
    w = CompileWatcher(registry=MetricsRegistry(), storm_threshold=100)
    assert [w.note("step", **d) for d in dims] == first_seen


def test_storm_fires_once_and_rearms_after_drain():
    clock = FakeClock()
    journal = EventJournal()
    reg = MetricsRegistry()
    w = CompileWatcher(journal=journal, registry=reg, storm_threshold=4,
                       window_s=10.0, monotonic=clock)
    for b in range(4):
        w.note("decode", batch=b)
        clock.advance(1.0)
    storms = [e for e in journal.events()
              if e["kind"] == JournalEvent.RECOMPILE_STORM]
    assert len(storms) == 1
    assert storms[0]["data"]["dim"] == MetricLabel.STORM_DIM_BATCH
    assert storms[0]["data"]["count"] == 4
    assert storms[0]["data"]["fn"] == "decode"
    # episode open: further churn inside the window is the SAME storm
    w.note("decode", batch=99)
    assert len([e for e in journal.events()
                if e["kind"] == JournalEvent.RECOMPILE_STORM]) == 1
    # window drains (<= threshold // 2 left) -> episode closes -> a new
    # burst journals a second episode
    clock.advance(60.0)
    for b in range(100, 104):
        w.note("decode", batch=b)
        clock.advance(1.0)
    assert len([e for e in journal.events()
                if e["kind"] == JournalEvent.RECOMPILE_STORM]) == 2
    assert 'dlrover_compile_storms_total{dim="batch"} 2' in reg.render()


def test_storm_does_not_fire_below_threshold_or_on_hits():
    clock = FakeClock()
    journal = EventJournal()
    w = CompileWatcher(journal=journal, registry=MetricsRegistry(),
                       storm_threshold=4, window_s=10.0, monotonic=clock)
    w.note("decode", batch=1)
    w.note("decode", batch=2)
    w.note("decode", batch=3)
    # hammering cached signatures is hits, not compiles — never a storm
    for _ in range(50):
        w.note("decode", batch=1)
    assert [e for e in journal.events()
            if e["kind"] == JournalEvent.RECOMPILE_STORM] == []


def test_storm_attribution_seq_len_and_unknown():
    clock = FakeClock()
    journal = EventJournal()
    w = CompileWatcher(journal=journal, registry=MetricsRegistry(),
                       storm_threshold=3, window_s=100.0, monotonic=clock)
    for bucket in (128, 256, 512):
        w.note("prefill", batch=8, bucket=bucket)
    storms = [e["data"] for e in journal.events()
              if e["kind"] == JournalEvent.RECOMPILE_STORM]
    assert storms[-1]["dim"] == MetricLabel.STORM_DIM_SEQ_LEN

    # a varying dim outside the vocabulary maps to "unknown", never a
    # new label value (the STORM_DIMS contract)
    for i in range(3):
        w.note("other_fn", weird=i)
    storms = [e["data"] for e in journal.events()
              if e["kind"] == JournalEvent.RECOMPILE_STORM]
    assert storms[-1]["dim"] == MetricLabel.STORM_DIM_UNKNOWN


def test_ragged_occupancy_sweep_journals_attributed_storm():
    """The serving pathology the watcher exists for: ragged decode
    occupancy (slots draining unevenly) feeds a different ``rows`` width
    every step, each a fresh trace — the sweep must journal at least one
    storm attributed to the batch dimension."""
    clock = FakeClock()
    journal = EventJournal()
    w = CompileWatcher(journal=journal, registry=MetricsRegistry(),
                       storm_threshold=6, window_s=120.0, monotonic=clock)
    for rows in (8, 7, 5, 4, 3, 2, 1, 6):  # ragged occupancy sweep
        w.note("decode_step", rows=rows, dtype="bf16")
        clock.advance(2.0)
    storms = [e["data"] for e in journal.events()
              if e["kind"] == JournalEvent.RECOMPILE_STORM]
    assert len(storms) >= 1
    assert storms[0]["dim"] == MetricLabel.STORM_DIM_BATCH
    assert storms[0]["count"] >= 6
    assert w.snapshot()["storms"][0]["dim"] == MetricLabel.STORM_DIM_BATCH


# -- fleet monitor ----------------------------------------------------------


def _wire(headroom_frac, headroom_bytes, kv=0, limit=1000):
    return {
        "seq": 1,
        "categories": {MetricLabel.MEM_KV_CACHE: kv},
        "total_bytes": kv,
        "limit_bytes": limit,
        "headroom_bytes": headroom_bytes,
        "headroom_frac": headroom_frac,
    }


def test_fleet_monitor_verdict_staleness_and_projection_units():
    clock = FakeClock()
    journal = EventJournal()
    mon = FleetMemoryMonitor(event_journal=journal,
                             registry=MetricsRegistry(),
                             pressure_frac=0.2, stale_s=30.0,
                             monotonic=clock)
    mon.observe(0, {"0": _wire(0.5, 500, kv=100)})
    mon.observe(1, {"1": _wire(0.1, 100, kv=300)})
    events = _pressure_events(journal)
    assert len(events) == 1
    assert events[0]["data"]["rank"] == 1
    assert events[0]["data"]["node_id"] == 1
    assert events[0]["data"]["category"] == MetricLabel.MEM_KV_CACHE

    # a rank STAYING under pressure is one event, not one per beat
    mon.observe(1, {"1": _wire(0.1, 100, kv=300)})
    assert len(_pressure_events(journal)) == 1

    # projection units for the brain's refusal arithmetic
    assert mon.fleet_headroom_bytes() == 100  # tightest fresh rank
    assert mon.kv_bytes_per_replica() == 300  # largest fresh KV ledger

    status = mon.status()
    assert set(status["ranks"]) == {"0", "1"}
    assert status["min_headroom_rank"] == 1
    assert status["min_headroom_frac"] == 0.1

    # stale ranks drop out of every aggregate
    clock.advance(31.0)
    status = mon.status()
    assert status["ranks"] == {} and status["stale_ranks"] == [0, 1]
    assert status["min_headroom_rank"] is None
    assert mon.fleet_headroom_bytes() is None
    assert mon.kv_bytes_per_replica() == 0


def test_fleet_monitor_journals_when_pressured_rank_changes():
    clock = FakeClock()
    journal = EventJournal()
    mon = FleetMemoryMonitor(event_journal=journal,
                             registry=MetricsRegistry(),
                             pressure_frac=0.2, stale_s=30.0,
                             monotonic=clock)
    mon.observe(0, {"0": _wire(0.15, 150)})
    mon.observe(0, {"2": _wire(0.05, 50)})  # a WORSE rank takes over
    events = _pressure_events(journal)
    assert [e["data"]["rank"] for e in events] == [0, 2]


def test_fleet_monitor_wire_snapshot_roundtrip(monkeypatch):
    """An actual accountant wire_snapshot rides observe() unmodified —
    the heartbeat payload and the monitor agree on the schema."""
    acct = _acct(monkeypatch, limit_bytes=1000)
    acct.register(MetricLabel.MEM_KV_CACHE, "kv", 900)
    acct.reconcile()
    journal = EventJournal()
    mon = FleetMemoryMonitor(event_journal=journal,
                             registry=MetricsRegistry(),
                             pressure_frac=0.2)
    mon.observe(3, {"12": acct.wire_snapshot()})
    assert mon.fleet_headroom_bytes() == 100
    assert mon.kv_bytes_per_replica() == 900
    events = _pressure_events(journal)
    assert len(events) == 1 and events[0]["data"]["rank"] == 12
    assert mon.status()["ranks"]["12"]["node_id"] == 3


def test_fleet_monitor_malformed_rank_key_is_skipped():
    mon = FleetMemoryMonitor(registry=MetricsRegistry())
    mon.observe(0, {"not-a-rank": _wire(0.5, 500), "4": _wire(0.9, 900)})
    assert set(mon.status()["ranks"]) == {"4"}


# -- race certification -----------------------------------------------------


def test_ledger_concurrency_is_race_free(monkeypatch, race_guard):
    """register/release from serving threads concurrently with reconcile
    sweeps and snapshot reads — the shared(...) ledger maps must show no
    happens-before violation."""
    monkeypatch.setattr(mem, "device_bytes", lambda: (0, 0))
    acct = MemoryAccountant(registry=MetricsRegistry(),
                            limit_bytes=1 << 20)
    w = CompileWatcher(registry=MetricsRegistry(), storm_threshold=1000)
    stop = threading.Event()

    def churn(i):
        for k in range(40):
            acct.register(MetricLabel.MEM_KV_CACHE, f"b{i}", 64 * (k + 1))
            w.note("decode", batch=(i, k))
            acct.release(MetricLabel.MEM_KV_CACHE, f"b{i}")

    def sweep():
        while not stop.is_set():
            acct.reconcile()
            acct.snapshot()
            acct.wire_snapshot()
            w.snapshot()

    sweeper = threading.Thread(target=sweep, name="mem-sweeper")
    workers = [threading.Thread(target=churn, args=(i,), name=f"churn-{i}")
               for i in range(4)]
    sweeper.start()
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    stop.set()
    sweeper.join()
    assert race_guard.tracked_created > 0, (
        "race certification vacuous: no shared() containers tracked")
    assert race_guard.races == [], race_guard.report()


# -- report CLI: OOM-forensics section --------------------------------------


def test_report_cli_memory_section_golden(tmp_path, monkeypatch, capsys):
    """``report <bundle>`` renders the memory.json waterfall + watermark
    table — golden output, end-to-end through a real bundle capture."""
    journal = EventJournal()
    reg = MetricsRegistry()
    acct = _acct(monkeypatch, registry=reg, journal=journal,
                 limit_bytes=1 << 30, monotonic=FakeClock())
    acct.register(MetricLabel.MEM_KV_CACHE, "kv_pool", 256 << 20)
    acct.step_mark(1)
    acct.register(MetricLabel.MEM_KV_CACHE, "kv_pool", 768 << 20)
    acct.register(MetricLabel.MEM_PARAMS, "weights", 100 << 20)
    acct.register(MetricLabel.MEM_STAGING, "frame", 512 << 10)
    acct.step_mark(2)
    acct.reconcile()
    fr = FlightRecorder("worker_0", out_dir=str(tmp_path / "fr"),
                        journal=journal, registry=reg, cooldown_s=0.0,
                        memory_snapshot_fn=acct.snapshot)
    bundle = fr.capture(REASON_MEMORY)

    from dlrover_tpu.observability import report

    assert report.main([bundle]) == 0
    out = capsys.readouterr().out
    assert out.endswith("""\
device memory (HBM ledger at capture):
  kv_cache        768.0MiB  (peak 768.0MiB)  ########################
  params          100.0MiB  (peak 100.0MiB)  ###
  staging         512.0KiB  (peak 512.0KiB)  #
  limit 1.0GiB, headroom 155.5MiB (15.2%), unattributed 0B

step watermarks (last 2 step(s)):
    step      kv_cache        params       staging
       1      256.0MiB            0B            0B
       2      768.0MiB      100.0MiB      512.0KiB
""")


def test_report_cli_no_memory_section_without_snapshot(tmp_path, capsys):
    """Journal-only sources (and bundles without memory.json) render the
    incident report exactly as before — no empty memory section."""
    path = tmp_path / "journal.json"
    path.write_text(json.dumps({"events": [], "now_t": 5.0}))

    from dlrover_tpu.observability import report

    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "device memory" not in out
    assert "fault-free" in out


# -- KV ceiling arithmetic --------------------------------------------------


def test_kv_theoretical_bytes_and_ceiling():
    config = SimpleNamespace(n_layers=4, n_kv_heads=2, head_dim=8)
    bf16 = kv_bytes_per_slot_theoretical(config, cache_len=16)
    assert bf16 == 4 * 2 * 2 * 16 * 8 * 2
    int8 = kv_bytes_per_slot_theoretical(config, cache_len=16,
                                         quantize=True)
    assert int8 == 4 * 2 * 2 * 16 * 8 * 1 + 4 * 2 * 2 * 16 * 4
    assert max_slots_ceiling(bf16, headroom_bytes=10 * bf16 + 5) == 10
    assert max_slots_ceiling(bf16, headroom_bytes=-1) == 0
    assert max_slots_ceiling(0, headroom_bytes=1 << 30) == 0
