"""Bench resilience (VERDICT r4 item 5): a wedged device must
still yield ONE structured JSON line carrying every phase that DID
complete — simulated here by hanging the main thread under a short
watchdog, and by a chip probe that never returns."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(prog: str, timeout: int = 60):
    return subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, cwd=HERE,
    )


def test_watchdog_emits_partial_rows_on_hang():
    # last_chance=True is the watchdog the bench re-arms after one
    # supervisor-driven restore: a SECOND wedge skips the re-probe and
    # takes the abort path directly (the contract under test here).
    prog = textwrap.dedent("""
        import time
        import bench
        bench._record("headline_gbps", 123.4)
        bench._record("headline_vs_baseline", 9.9)
        bench._record("sweep", [{"bytes": 4, "device_gbps": 1.0}])
        bench._set_phase("pallas ring proof")
        bench._watchdog(0.5, "allreduce_sum_reduce_512MiB_f32",
                        last_chance=True)
        time.sleep(30)   # the simulated wedge: never returns on its own
    """)
    r = _run(prog)
    assert r.returncode == 2, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    # headline recovered from the completed phase, not zeroed
    assert out["value"] == 123.4 and out["vs_baseline"] == 9.9
    assert out["metric"] == "allreduce_sum_reduce_512MiB_f32"
    assert "watchdog" in out["detail"]["error"]
    assert out["detail"]["phase"] == "pallas ring proof"
    assert out["detail"]["partial"]["sweep"][0]["device_gbps"] == 1.0


def test_watchdog_zero_value_before_any_phase():
    prog = textwrap.dedent("""
        import time
        import bench
        bench._set_phase("probe (trivial op on the device)")
        bench._watchdog(0.5, "allreduce_sum_reduce_512MiB_f32",
                        last_chance=True)
        time.sleep(30)
    """)
    r = _run(prog)
    assert r.returncode == 2
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["value"] == 0 and out["vs_baseline"] == 0
    assert out["detail"]["phase"].startswith("probe")


def test_watchdog_first_fire_restores_and_continues():
    """The first wedge no longer aborts the run: the watchdog routes
    it through the health supervisor (quarantine device -> re-probe ->
    restore), records the quarantine window, and later rows come out
    tagged degraded=true instead of being discarded."""
    prog = textwrap.dedent("""
        import json, time
        import bench
        bench._set_phase("sweep (allreduce)")
        bench._watchdog(0.5, "allreduce_sum_reduce_512MiB_f32")
        time.sleep(20)   # wedge long enough for fire + restore cycle
        bench._record("post_restore", {"gbps": 1.0})
        print("Q " + json.dumps(bench._PARTIAL["rows"]["tier_quarantine"]))
        print("R " + json.dumps(bench._PARTIAL["rows"]["post_restore"]))
    """)
    r = _run(prog)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l[:2] in ("Q ", "R ")]
    quarantine = json.loads(lines[0][2:])
    assert quarantine["restored"] is True
    assert quarantine["tier"] == "device"
    assert quarantine["quarantine_window_ms"] >= 0
    after = json.loads(lines[1][2:])
    assert after["degraded"] is True
    assert after["quarantine_window_ms"] == \
        quarantine["quarantine_window_ms"]


def test_probe_device_times_out_on_stuck_device():
    """_probe_device must bound a trivial-op that never returns (a
    device call stuck forever) and report failure fast."""
    prog = textwrap.dedent("""
        import threading, time, sys
        import bench
        # simulate the wedge: the worker thread blocks inside 'jax'
        import types
        fake = types.ModuleType("jax")
        def _hang(*a, **k):
            time.sleep(60)
        class _NumpyShim(types.ModuleType):
            def __getattr__(self, name):
                return _hang
        fake.numpy = _NumpyShim("jax.numpy")
        fake.devices = _hang
        sys.modules["jax"] = fake
        sys.modules["jax.numpy"] = fake.numpy
        t0 = time.monotonic()
        ok = bench._probe_device(1.0)
        dt = time.monotonic() - t0
        assert not ok and dt < 10, (ok, dt)
        print("PROBE-TIMEOUT-OK")
    """)
    r = _run(prog)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PROBE-TIMEOUT-OK" in r.stdout


def test_partial_live_file_flushes():
    prog = textwrap.dedent("""
        import json, os
        import bench
        bench._PARTIAL["rows"].clear()
        bench._record("headline_gbps", 7.5)
        here = os.path.dirname(os.path.abspath(bench.__file__))
        with open(os.path.join(here, "docs", "BENCH_PARTIAL_LIVE.json")) as f:
            live = json.load(f)
        assert live["rows"]["headline_gbps"] == 7.5
        print("LIVE-FLUSH-OK")
    """)
    r = _run(prog)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LIVE-FLUSH-OK" in r.stdout


def test_revival_sequencing_probe_fail_then_succeed():
    """CPU-only drill of the device-recovery path: first chip probe
    fails -> host-only fabric rows run -> re-probe succeeds -> the
    full device sweep + pallas proofs + persistent row still emit in
    ONE final JSON line with exit code 0."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""   # single CPU device: single-chip path
        import bench

        probes = []
        def fake_probe(timeout_s=180.0):
            probes.append(timeout_s)
            return len(probes) >= 2   # dead first, revived on re-probe
        bench._probe_device = fake_probe
        bench._require_tpu = lambda device: None  # drill on the CPU
        bench._device_seconds_per_iter = lambda *a, **k: 0.01
        bench._cpu_reduce_gbps = lambda *a, **k: 1.0
        bench._reduce_gbps = lambda *a, **k: 2.0
        bench._dispatch_latency_us = lambda *a, **k: 3.0
        bench._persistent_start_us = lambda *a, **k: 55.5
        bench._pallas_proof = lambda device: {"compiled": True}
        bench._pallas_attn_proof = lambda device: {"compiled": True}
        bench._host_rows = lambda: {"host_stub": {"ok": True}}
        bench.main()
        assert len(probes) == 2, probes
    """)
    r = _run(prog, timeout=240)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "allreduce_sum_reduce_512MiB_f32"
    detail = out["detail"]
    # host rows captured while the device was down survive into the
    # final emission alongside the post-revival device phases
    assert detail["host_stub"] == {"ok": True}
    assert len(detail["sweep"]) == 9
    assert detail["pallas"]["compiled"] is True
    assert detail["persistent_start_us"] == 55.5
    assert out["value"] > 0
    # the multi-ranks-per-chip staging row rides the device phase:
    # partitioned HBM staging vs serialized per-rank puts
    mr = detail["multirank_chip"]
    assert "error" not in mr, mr
    assert mr["ranks_per_chip"] == 8 and mr["bytes_per_rank"] > 0
    assert mr["partitioned_gbps"] > 0 and mr["serialized_gbps"] > 0
    assert mr["speedup_ratio_x"] > 0


def test_new_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR3 satellite 5: the quant_allreduce_sweep and
    dp_bucket_fusion rows run END-TO-END (real 8-rank subprocess
    workers, shrunk workload via env) inside the probe-failed host-only
    path, and the abort emission carries schema-complete JSON for
    both."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the workers so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_QUANT_SIZES"] = "65536"
        os.environ["OMPI_TPU_BENCH_FUSE_LEAVES"] = "8"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    sweep = rows["quant_allreduce_sweep"]
    assert "error" not in sweep, sweep
    band = sweep["64KiB"]
    assert band["exact_p50_ms"] > 0 and band["exact_gbps"] > 0
    for wire, floor in (("int8", 3.8), ("bf16", 2.0)):
        w = band[wire]
        for key in ("p50_ms", "effective_gbps", "wire_ratio",
                    "max_abs_err", "bound_min", "within_bound"):
            assert key in w, (wire, key)
        assert w["wire_ratio"] >= 1.9 and w["wire_ratio"] >= floor - 0.1
        assert w["within_bound"] is True

    fuse = rows["dp_bucket_fusion"]
    assert "error" not in fuse, fuse
    for key in ("leaves", "leaf_bytes", "dispatches_per_leaf",
                "dispatches_fused", "dispatch_reduction", "per_leaf_ms",
                "fused_ms", "speedup", "max_abs_diff_vs_exact"):
        assert key in fuse, key
    assert fuse["dispatches_per_leaf"] == fuse["leaves"] == 8
    assert fuse["dispatch_reduction"] >= 2.0
    assert fuse["max_abs_diff_vs_exact"] == 0.0


def test_sched_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR9 satellite 4: the sched_autotune and
    schedule_cache_warm_start rows run end-to-end (real 8-rank
    subprocess workers, shrunk sweep via env) inside the probe-failed
    host-only path — the autotune row carrying the tuned>=static
    verdict and cache hit rate, the warm-start row proving a second
    process dispatches from the persisted cache without tuning at
    <=5% p50 overhead."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the measure-mode sweep so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_SCHED_SIZES"] = "1024,16384"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    tune = rows["sched_autotune"]
    assert "error" not in tune, tune
    for key in ("mode", "tune_ms", "keys_tuned", "cache_hits",
                "cache_misses", "cache_hit_rate", "tuned_ge_static_all",
                "sweep", "digest"):
        assert key in tune, key
    assert tune["mode"] == "measure"
    assert tune["keys_tuned"] == len(tune["sweep"]) == 2
    assert tune["cache_hit_rate"] == 1.0 and tune["cache_misses"] == 0
    # the winner is min over candidates including the static pick:
    # tuned >= static at every sweep point, by construction
    assert tune["tuned_ge_static_all"] is True
    for pt in tune["sweep"]:
        assert pt["tuned_p50_us"] > 0 and pt["tuned_gbps"] > 0
        if "static_p50_us" in pt:
            assert pt["tuned_p50_us"] <= pt["static_p50_us"]

    warm = rows["schedule_cache_warm_start"]
    assert "error" not in warm, warm
    assert warm["warm"]["keys"] > 0 and warm["warm"]["path"]
    second = warm["second_process"]
    assert second["warm_entries_loaded"] == warm["warm"]["keys"]
    assert second["tuned_in_this_process"] is False
    assert second["cache_hits"] > 0
    # the <=5% acceptance bound lives in the row's own "pass" verdict
    # (the recorded bench run ratchets it); the schema check runs on a
    # loaded CI box where paired-median dispatch noise spikes past 10%
    # while the rest of the suite is churning, so assert only a sanity
    # bound here rather than re-litigating the ratchet
    assert second["overhead_pct"] <= 20.0, second
    assert isinstance(second["pass"], bool)


def test_trace_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR7 satellite 5: the trace_overhead and
    latency_histograms rows run end-to-end inside the probe-failed
    host-only path and emit schema-complete JSON — the overhead row
    carrying the <5% always-on verdict, the histogram row carrying
    log-bucketed p50/p99 snapshots from the new pvar class."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    from ompi_tpu.native import build
    tr = rows["trace_overhead"]
    if build.available():
        assert "error" not in tr, tr
        for key in ("p50_off_us", "p50_on_us", "overhead_pct",
                    "blocks", "pass"):
            assert key in tr, key
        assert tr["p50_off_us"] > 0 and tr["p50_on_us"] > 0
        # the always-on acceptance bound (generous noise margin in CI:
        # the dedicated ratchet in test_trace.py uses min-of-blocks)
        assert tr["overhead_pct"] < 5.0, tr
        assert tr["pass"] is True
    else:
        assert tr == {"error": "native library unavailable"}

    hist = rows["latency_histograms"]
    assert "error" not in hist, hist
    assert hist["samples"] == 20000
    assert 0 < hist["emit_p50_ns"] <= hist["emit_p99_ns"]
    emit = hist["histograms"]["trace_emit"]
    for key in ("count", "mean", "min", "max", "p50", "p99"):
        assert key in emit, key
    assert emit["count"] == 20000


def test_telemetry_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR10 satellite 6: the telemetry_overhead and
    straggler_detect rows run end-to-end inside the probe-failed
    host-only path and emit schema-complete JSON — the overhead row
    carrying the <1% always-on sampler verdict, the straggler row
    proving the faultline-delayed rank is flagged and the fabric tier
    lands SUSPECT in the ledger."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    from ompi_tpu.native import build
    ov = rows["telemetry_overhead"]
    if build.available():
        assert "error" not in ov, ov
        for key in ("p50_off_us", "p50_on_us", "overhead_pct",
                    "blocks", "ticks_sampled", "pass"):
            assert key in ov, key
        assert ov["p50_off_us"] > 0 and ov["p50_on_us"] > 0
        assert ov["ticks_sampled"] > 0, ov
        # the always-on acceptance bound (generous noise margin in CI;
        # the recorded bench run ratchets the <1% claim via "pass")
        assert ov["overhead_pct"] < 5.0, ov
        assert isinstance(ov["pass"], bool)
    else:
        assert ov == {"error": "native library unavailable"}

    st = rows["straggler_detect"]
    assert "error" not in st, st
    for key in ("cycles", "delay_ms", "detect_p50_ms", "detect_max_ms",
                "straggler_z_min", "suspect_tier", "suspect_marked",
                "ledger_digest"):
        assert key in st, key
    assert st["suspect_tier"] == "fabric"
    assert st["suspect_marked"] is True
    assert 0 < st["detect_p50_ms"] <= st["detect_max_ms"]
    # robust z of a 20 ms delay over a ~us-scale baseline is enormous;
    # anything past the 3.5 cut proves the detector saw the skew
    assert st["straggler_z_min"] >= 3.5


def test_elastic_recovery_row_emits_schema_complete_on_probe_fail():
    """ISSUE PR12 satellite 4: the elastic_recovery row runs
    end-to-end (real 8-rank subprocess drill: rank_kill mid-allreduce
    -> RevokedError -> revoke/agree/shrink -> first survivor
    allreduce) inside the probe-failed host-only path and emits
    schema-complete JSON — p50 ms end-to-end plus the per-phase
    breakdown, every key *_ms so the benchgate ratchet direction is
    lower-is-better automatically."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        os.environ["OMPI_TPU_BENCH_ELASTIC_TRIALS"] = "3"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new row
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    row = out["detail"]["partial"]["elastic_recovery"]
    assert "error" not in row, row
    for key in ("trials", "ranks", "survivors", "recovery_p50_ms",
                "detect_ms", "revoke_ms", "quiesce_ms", "agree_ms",
                "shrink_ms", "readmit_ms", "first_allreduce_ms"):
        assert key in row, key
    assert row["ranks"] == 8 and row["survivors"] == 7
    assert row["recovery_p50_ms"] > 0
    # phases nest inside the total
    assert row["recovery_p50_ms"] >= row["shrink_ms"]
    # every ratcheted key auto-maps to lower-is-better in benchgate
    from ompi_tpu.tools import benchgate
    for key in ("recovery_p50_ms", "detect_ms", "shrink_ms"):
        assert benchgate.direction(key) == "lower"

    # ISSUE PR20: the elastic_grow row rides the same host-only path —
    # the shrink drill's inverse (warm-spare rejoin through the medic
    # ladder, epoch bump, bounded catch-up) with per-phase ms, the
    # measured rejoin_steps, and the survivor step-time blip
    grow = out["detail"]["partial"]["elastic_grow"]
    assert "error" not in grow, grow
    for key in ("trials", "ranks", "grown_size", "grow_p50_ms",
                "agree_ms", "admit_ms", "expand_ms", "migrate_ms",
                "catchup_ms", "rejoin_steps", "catchup_chunks",
                "catchup_bytes", "cache_reused", "baseline_step_ms",
                "catchup_step_ms", "blip_x", "first_allreduce_ms",
                "pass"):
        assert key in grow, key
    assert grow["ranks"] == 8 and grow["grown_size"] == 8
    assert grow["grow_p50_ms"] > 0
    assert grow["rejoin_steps"] == grow["catchup_chunks"] > 0
    assert grow["catchup_bytes"] > 0
    assert grow["pass"] is True
    # every ratcheted grow key auto-maps to lower-is-better
    for key in ("grow_p50_ms", "catchup_ms", "rejoin_steps", "blip_x"):
        assert benchgate.direction(key) == "lower"


def test_daemon_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR13 satellite 6: the tenant_isolation and
    admission_eviction rows run end-to-end (real daemon subprocess
    workers, shrunk via env) inside the probe-failed host-only path and
    emit schema-complete JSON — the isolation row carrying the
    guaranteed-p50-under-scavenger-flood degradation verdict, the
    admission row carrying the reject -> retry-after -> admit cycle and
    evict-to-detach timings."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the workers so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_TENANT_ITERS"] = "10"
        os.environ["OMPI_TPU_BENCH_ADMIT_TRIALS"] = "4"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    iso = rows["tenant_isolation"]
    assert "error" not in iso, iso
    for key in ("iters", "baseline_p50_us", "flood_p50_us",
                "degradation_pct", "scavenger_rejects",
                "scavenger_served", "pass"):
        assert key in iso, key
    assert iso["baseline_p50_us"] > 0 and iso["flood_p50_us"] > 0
    # the ISSUE bound is <=10% guaranteed-class degradation; the
    # recorded bench run ratchets that via "pass" — assert the same
    # bound here (the drill is dispatcher-weight math, not wall-clock
    # noise: guaranteed weight 8 vs scavenger weight 1)
    assert iso["degradation_pct"] <= 10.0, iso
    # the flood must actually have pressured admission, not vanished
    assert iso["scavenger_rejects"] > 0
    assert iso["scavenger_served"] > 0
    assert iso["pass"] is True

    adm = rows["admission_eviction"]
    assert "error" not in adm, adm
    for key in ("trials", "admit_p50_us", "retry_after_p50_ms",
                "reject_to_admit_p50_ms", "evict_to_detach_ms",
                "evict_answered", "rejects_counted", "pass"):
        assert key in adm, key
    assert adm["admit_p50_us"] > 0
    assert adm["retry_after_p50_ms"] > 0
    assert adm["reject_to_admit_p50_ms"] > 0
    assert adm["evict_to_detach_ms"] > 0
    # every queued request on the evicted tenant got an EVICTED answer
    assert adm["evict_answered"] == 16
    assert adm["rejects_counted"] >= adm["trials"]
    assert adm["pass"] is True

    # the ratchet directions resolve automatically from the key names
    from ompi_tpu.tools import benchgate
    for key in ("degradation_pct", "flood_p50_us",
                "reject_to_admit_p50_ms", "evict_to_detach_ms"):
        assert benchgate.direction(key) == "lower"


def test_medic_probe_cycle_drill_records_row():
    """ISSUE PR14 tentpole: the bench preflight is a full medic
    re-probe cycle, not a one-shot probe — QUARANTINE the device
    tiers, drive the supervisor's tick schedule through the PROBATION
    walk, confirm both restore to HEALTHY. A failed device probe still
    short-circuits (no drill against a dead device)."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        assert bench._medic_probe_cycle(30.0) is False
        assert "medic_probe_cycle" not in bench._PARTIAL["rows"]

        bench._probe_device = lambda timeout_s=180.0: True
        assert bench._medic_probe_cycle(30.0) is True
        print("ROW " + json.dumps(
            bench._PARTIAL["rows"]["medic_probe_cycle"]))
    """)
    r = _run(prog, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("ROW ")][0]
    row = json.loads(line[4:])
    assert "error" not in row, row
    assert row["tiers"] == ["device", "device_pallas"]
    assert row["full_restore"] is True
    assert sorted(row["restored"]) == ["device", "device_pallas"]
    # the restore walked through PROBATION — no straight-to-healthy jump
    assert row["probation_walk"] == ["device", "device_pallas"]
    assert row["cycle_ms"] >= 0


def test_pallas_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR14 satellite 3: the pallas_sched_allreduce and
    device_resurrection rows run end-to-end (real 8-rank subprocess
    worker for the sched sweep, real supervisor drill for the
    resurrection) inside the probe-failed host-only path and emit
    schema-complete JSON — off TPU both carry degraded=true loudly
    (the gate excuses them, never silently)."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the sweep so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_PALLAS_SIZES"] = "1024,65536"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    ps = rows["pallas_sched_allreduce"]
    assert "error" not in ps, ps
    # bit-identity evidence: 3 generators x f32/bf16, all identical
    assert ps["bit_identity"] == {"checked": 6, "ok": True}
    on_tpu = ps["backend"] == "tpu"
    if not on_tpu:
        # the kernels are not timed off the chip: the row says so
        assert ps["degraded"] is True
        assert "interpret" in ps["degraded_reason"]
    assert len(ps["sweep"]) == 2
    for pt in ps["sweep"]:
        assert pt["interpret_gbps"] > 0 and pt["interpret_p50_us"] > 0
        assert ("compiled_gbps" in pt) == on_tpu

    dr = rows["device_resurrection"]
    assert "error" not in dr, dr
    assert dr["tiers"] == ["device", "device_pallas"]
    assert dr["restored"] is True
    assert dr["restore_ms"] > 0 and dr["first_good_row_ms"] > 0
    assert dr["first_good_value_ok"] is True
    assert dr["probation_walk"] == ["device", "device_pallas"]
    # off TPU the row is degraded, never silently dropped
    assert dr["degraded"] is True

    # ratchet directions resolve from the key names: timings lower,
    # throughputs higher
    from ompi_tpu.tools import benchgate
    for key in ("restore_ms", "first_good_row_ms", "interpret_p50_us"):
        assert benchgate.direction(key) == "lower"
    for key in ("interpret_gbps", "compiled_gbps", "speedup_ratio_x"):
        assert benchgate.direction(key) == "higher"


def test_overlap_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR15 satellite 4: the transformer-scale part_overlap row
    (threaded backward/reduce/apply pipeline over a real 8-rank
    DpOverlapSession) and the dp_step_overlap_pct row run inside the
    probe-failed host-only path and emit schema-complete JSON — the
    overlap fraction, the exposed tail, and the vs-blocking ratchet."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the pipeline so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_OVERLAP_LAYERS"] = "3"
        os.environ["OMPI_TPU_BENCH_OVERLAP_LAYER_KB"] = "256"
        os.environ["OMPI_TPU_BENCH_OVERLAP_TRIALS"] = "1"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._pallas_sched_row = lambda: {"stub": True}
        bench._device_resurrection_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    po = rows["part_overlap"]
    assert "error" not in po, po
    assert po["layers"] == 3 and po["bytes"] == 3 * 256 * 1024
    assert po["buckets"] >= 1 and po["tiles"] >= po["buckets"]
    assert po["comm_only_ms"] > 0 and po["blocking_s"] > 0
    assert po["overlapped_s"] > 0 and po["speedup"] > 0
    assert po["ratchet_min_speedup"] == 2.0
    # the shrunken 3-layer drill still pipelines: overlapped strictly
    # beats blocking (the 2.0 ratchet itself rides the full-size run
    # via the "pass" field + benchgate's speedup series)
    assert po["speedup"] > 1.0, po

    ov = rows["dp_step_overlap_pct"]
    assert "error" not in ov, ov
    assert 0.0 <= ov["overlap_pct"] <= 100.0
    assert ov["exposed_comm_ms"] >= 0.0
    assert ov["comm_window_s"] > 0 and ov["backward_window_s"] > 0
    assert ov["tiles"] == po["tiles"] and ov["buckets"] == po["buckets"]
    assert ov["bwd_order_replayed"] is True

    # ratchet directions resolve from the key names: the overlap
    # fraction and speedup ratchet higher, the exposed tail and comm
    # cost lower; calibration-dependent *_s fields carry no direction
    from ompi_tpu.tools import benchgate
    for key in ("speedup", "overlap_pct"):
        assert benchgate.direction(key) == "higher"
    for key in ("exposed_comm_ms", "comm_only_ms",
                "monolithic_allreduce_ms"):
        assert benchgate.direction(key) == "lower"
    for key in ("blocking_s", "overlapped_s", "comm_window_s",
                "backward_window_s"):
        assert benchgate.direction(key) is None


def test_step_program_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR16 satellite 5: the whole-step comm program rows — the
    compiled-vs-per-bucket ratchet row (step_program_allreduce) and the
    compile-cost row (step_program_compile_ms) — run inside the
    probe-failed host-only path and emit schema-complete JSON."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the drill so the schema check stays fast
        os.environ["OMPI_TPU_BENCH_STEPPROG_LAYERS"] = "6"
        os.environ["OMPI_TPU_BENCH_STEPPROG_LAYER_KB"] = "32"
        os.environ["OMPI_TPU_BENCH_STEPPROG_TRIALS"] = "1"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._pallas_sched_row = lambda: {"stub": True}
        bench._device_resurrection_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    sp = rows["step_program_allreduce"]
    assert "error" not in sp, sp
    assert sp["layers"] == 6 and sp["bytes"] == 6 * 32 * 1024
    assert sp["buckets"] >= 2 and sp["nodes"] >= sp["buckets"]
    # the program digest is the 16-hex schedule-IR identity
    assert len(sp["program_digest"]) == 16
    int(sp["program_digest"], 16)
    # tune_step seeded the winner cache first: every bucket's geometry
    # resolves as a cache override, never the static default
    assert set(sp["tile_sources"].split(",")) == {"cache"}, sp
    # the cache winner never splits finer than the static 128K arm
    assert sp["tiles_program_arm"] <= sp["tiles_bucket_arm"], sp
    assert sp["per_bucket_s"] > 0 and sp["program_s"] > 0
    assert sp["blocking_s"] > 0 and sp["overlapped_s"] > 0
    assert sp["speedup_vs_bucket"] > 0 and sp["speedup_vs_blocking"] > 0
    assert sp["ratchet_min_vs_bucket"] == 1.1
    assert sp["ratchet_min_vs_blocking"] == 2.2
    # the shrunken drill still pipelines: overlapped strictly beats
    # blocking (the ratchets themselves ride the full-size run via the
    # "pass" field + benchgate's speedup series)
    assert sp["speedup_vs_blocking"] > 1.0, sp

    cm = rows["step_program_compile_ms"]
    assert "error" not in cm, cm
    assert cm["buckets"] == sp["buckets"]
    assert cm["nodes"] == sp["nodes"]
    assert cm["compile_ms"] > 0 and cm["session_compile_ms"] > 0

    # ratchet directions resolve from the key names: the two speedups
    # ratchet higher, the compile cost lower; calibration-dependent
    # *_s fields carry no direction
    from ompi_tpu.tools import benchgate
    for key in ("speedup_vs_bucket", "speedup_vs_blocking"):
        assert benchgate.direction(key) == "higher"
    for key in ("compile_ms", "session_compile_ms"):
        assert benchgate.direction(key) == "lower"
    for key in ("per_bucket_s", "program_s", "blocking_s",
                "overlapped_s"):
        assert benchgate.direction(key) is None


def test_fleet_sim_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR17 satellite 5: the fleet_sim_scale and
    fleet_sim_determinism rows run end-to-end (real armada subprocess
    workers driving the real control planes, shrunk via env) inside
    the probe-failed host-only path and emit schema-complete JSON —
    the scale row carrying pod-scale engine/admission throughput plus
    recovery and retune-convergence ratchets, the determinism row the
    two-subprocess byte-identical digest verdict."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the simulated pod so the schema check stays fast;
        # tenants/rps stay at the ISSUE floor (>=100 tenants, 10k rps)
        os.environ["OMPI_TPU_BENCH_SIM_RANKS"] = "256"
        os.environ["OMPI_TPU_BENCH_SIM_TENANTS"] = "100"
        os.environ["OMPI_TPU_BENCH_SIM_RPS"] = "10000"
        os.environ["OMPI_TPU_BENCH_SIM_DURATION"] = "6"
        os.environ["OMPI_TPU_BENCH_SIM_DET_RANKS"] = "64"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._step_pipeline_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._pallas_sched_row = lambda: {"stub": True}
        bench._device_resurrection_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    scale = rows["fleet_sim_scale"]
    assert "error" not in scale, scale
    for key in ("ranks", "tenants", "virtual_s", "wall_s", "events",
                "events_per_s", "offered_rps", "submits", "admits",
                "rejects", "admission_handle_per_s", "recoveries",
                "recovery_p50_ms", "retunes",
                "retune_convergence_ticks", "world_size_after",
                "pass"):
        assert key in scale, key
    assert scale["ranks"] == 256 and scale["tenants"] == 100
    assert scale["offered_rps"] == 10000.0
    assert scale["events"] > 0 and scale["events_per_s"] > 0
    assert scale["admission_handle_per_s"] > 0
    assert scale["admits"] + scale["rejects"] <= scale["submits"]
    # the chaos drills actually landed: host loss shrank the world
    # (4 ranks of one host) and the straggler forced retunes
    assert scale["world_size_after"] == 252
    assert scale["recoveries"] > 0 and scale["recovery_p50_ms"] > 0
    assert scale["retunes"] > 0
    assert scale["retune_convergence_ticks"] >= 1
    assert scale["pass"] is True

    det = rows["fleet_sim_determinism"]
    assert "error" not in det, det
    for key in ("ranks", "runs", "digest_a", "digest_b",
                "digests_match", "replay_match_ratio_x", "events",
                "pass"):
        assert key in det, key
    assert det["runs"] == 2
    assert det["digests_match"] is True
    assert det["digest_a"] == det["digest_b"]
    assert len(det["digest_a"]) == 64
    assert det["replay_match_ratio_x"] == 1.0
    assert det["pass"] is True

    # ratchet directions resolve from the key names: throughputs
    # higher, recovery latency + convergence lower; raw wall/virtual
    # seconds carry no direction (scale-dependent, never ratcheted)
    from ompi_tpu.tools import benchgate
    for key in ("events_per_s", "admission_handle_per_s",
                "replay_match_ratio_x"):
        assert benchgate.direction(key) == "higher"
    for key in ("recovery_p50_ms", "retune_convergence_ticks"):
        assert benchgate.direction(key) == "lower"
    for key in ("wall_s", "virtual_s"):
        assert benchgate.direction(key) is None

    # ISSUE PR20: the fleet_grow_sim row — armada spare_join drill
    # (kill -> shrink -> warm rejoin -> tenants regrow) with the
    # two-subprocess replay verdict over the lazarus log included
    gs = rows["fleet_grow_sim"]
    assert "error" not in gs, gs
    for key in ("ranks", "tenants", "events", "events_per_s",
                "grows", "grow_p50_ms", "recoveries",
                "world_size_after", "dead_after", "digest_a",
                "digest_b", "digests_match", "pass"):
        assert key in gs, key
    assert gs["ranks"] == 256
    assert gs["grows"] >= 1 and gs["grow_p50_ms"] > 0
    assert gs["world_size_after"] == 256 and gs["dead_after"] == 0
    assert gs["digests_match"] is True
    assert gs["digest_a"] == gs["digest_b"]
    assert gs["pass"] is True
    assert benchgate.direction("grow_p50_ms") == "lower"


def test_step_pipeline_rows_emit_schema_complete_on_probe_fail():
    """ISSUE PR18 satellite 6: the step-boundary pipeline rows — the
    two-step slipstream window vs PR 16 barrier ratchet row
    (step_pipeline_2step, with the residency elision count and the
    tail-overlap fraction) and the window compile-cost row
    (step_window_compile_ms) — run inside the probe-failed host-only
    path and emit schema-complete JSON."""
    prog = textwrap.dedent("""
        import json, os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        # shrink the drill: 16 buckets keeps runtime down while still
        # crossing the 256KB/8-rank residency threshold (deadline ~11)
        os.environ["OMPI_TPU_BENCH_STEPPIPE_BUCKETS"] = "16"
        os.environ["OMPI_TPU_BENCH_STEPPIPE_TRIALS"] = "1"
        import bench

        bench._probe_device = lambda timeout_s=180.0: False
        # stub every OTHER host row: this drill is about the new rows
        bench._fabric_loopback = lambda: {"stub": True}
        bench._shm_2proc = lambda: {"stub": True}
        bench._fabric_2proc = lambda: {"stub": True}
        bench._osc_epoch_2proc = lambda: {"stub": True}
        bench._d2d_2proc = lambda: {"stub": True}
        bench._cpu_mesh_dispatch = lambda: {"stub": True}
        bench._part_overlap_row = lambda: {"stub": True}
        bench._step_program_row = lambda: {"stub": True}
        bench._quant_sweep_row = lambda: {"stub": True}
        bench._bucket_fusion_row = lambda: {"stub": True}
        bench._commlint_row = lambda: {"stub": True}
        bench._locksmith_row = lambda: {"stub": True}
        bench._degraded_allreduce_row = lambda: {"stub": True}
        bench._fault_drill_row = lambda: {"stub": True}
        bench._trace_overhead_row = lambda: {"stub": True}
        bench._latency_hist_row = lambda: {"stub": True}
        bench._tier_restore_row = lambda: {"stub": True}
        bench._health_overhead_row = lambda: {"stub": True}
        bench._telemetry_overhead_row = lambda: {"stub": True}
        bench._watchtower_overhead_row = lambda: {"stub": True}
        bench._straggler_detect_row = lambda: {"stub": True}
        bench._sched_autotune_row = lambda: {"stub": True}
        bench._sched_warm_start_row = lambda: {"stub": True}
        bench._pallas_sched_row = lambda: {"stub": True}
        bench._device_resurrection_row = lambda: {"stub": True}
        bench._elastic_recovery_row = lambda: {"stub": True}
        bench._elastic_grow_row = lambda: {"stub": True}
        bench._tenant_isolation_row = lambda: {"stub": True}
        bench._admission_eviction_row = lambda: {"stub": True}
        bench._fleet_sim_scale_row = lambda: {"stub": True}
        bench._fleet_sim_determinism_row = lambda: {"stub": True}
        bench._fleet_grow_sim_row = lambda: {"stub": True}
        bench.main()
    """)
    r = _run(prog, timeout=420)
    assert r.returncode == 2, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = out["detail"]["partial"]

    sp = rows["step_pipeline_2step"]
    assert "error" not in sp, sp
    assert sp["buckets"] == 16
    assert sp["bytes"] == 2 * 16 * 256 * 1024
    # the residency model elided at least one allgather, and the
    # elision is visible in the window program's digest identity
    assert sp["ag_elided_count"] >= 1
    assert sp["elided_in_digest"] is True
    assert sp["spc_ag_elided"] >= sp["ag_elided_count"]
    assert len(sp["window_digest"]) == 16
    int(sp["window_digest"], 16)
    assert sp["nodes"] > 2 * sp["buckets"]   # two steps + tail
    assert sp["barrier_s"] > 0 and sp["window_s"] > 0
    # the shrunken drill still pipelines: the window strictly beats
    # the barrier (the 1.15x ratchet itself rides the full-size run
    # via the "pass" field + benchgate's ratio_x series)
    assert sp["ratio_x"] > 1.0, sp
    assert sp["ratchet_min"] == 1.15
    assert 0.0 <= sp["tail_overlap_pct"] <= 100.0
    assert sp["tail_total_s"] >= 0.0

    cm = rows["step_window_compile_ms"]
    assert "error" not in cm, cm
    assert cm["buckets"] == sp["buckets"]
    assert cm["nodes"] == sp["nodes"]
    assert cm["compile_ms"] > 0 and cm["session_compile_ms"] > 0

    # ratchet directions resolve from the key names: the window ratio
    # and the elision count ratchet higher, compile cost lower;
    # calibration-dependent *_s fields carry no direction
    from ompi_tpu.tools import benchgate
    for key in ("ratio_x", "ag_elided_count", "tail_overlap_pct"):
        assert benchgate.direction(key) == "higher"
    for key in ("compile_ms", "session_compile_ms"):
        assert benchgate.direction(key) == "lower"
    for key in ("barrier_s", "window_s", "tail_total_s"):
        assert benchgate.direction(key) is None


def test_single_chip_bench_refuses_a_cpu_device():
    """The device rows time a TPU; on any other device the bench fails
    instead of timing the CPU under a device metric's name."""
    import importlib.util

    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(root, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(RuntimeError, match="times a TPU"):
        bench._require_tpu(jax.devices()[0])
