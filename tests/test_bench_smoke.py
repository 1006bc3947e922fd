"""bench.py --smoke as a tier-1 gate: cache and pipeline regressions
fail tests here instead of waiting for the next BENCH round."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_bench_smoke_hot_path(capsys):
    import bench

    t0 = time.monotonic()
    out = bench.bench_smoke(duration_s=1.5)
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, f"smoke bench took {elapsed:.0f}s (budget 60)"

    # Throughput through the full app at smoke scale.
    assert out["value"] > 0
    # Acceptance path: a repeated identical request answers from the
    # byte cache with ZERO new device dispatches.
    assert out["warm_repeat_cached"] is True
    # The single-flight probe ran (the rate itself is timing-dependent;
    # determinism for the mechanism lives in test_singleflight.py).
    assert out["dedup_hit_rate"] is not None
    assert 0.0 <= out["dedup_hit_rate"] <= 1.0
    # The two-stage pipeline recorded device-execute coverage.
    assert out["overlap_efficiency"] is not None
    assert out["overlap_efficiency"] > 0
    # Plane-digest staging accounting is live.
    assert out["planecache_misses"] is not None
    assert out["planecache_misses"] > 0
    # Per-request cost attribution is live: the most expensive request
    # of the window carries a ledger that says where its time went.
    assert "device_ms" in out["cost_ledger_keys"]
    assert "queue_ms" in out["cost_ledger_keys"]
    assert "wire_bytes" in out["cost_ledger_keys"]

    # Pay-for-what-you-use: every cross-cutting feature's hot-path
    # guard (trace span, cost-ledger flush, deadline check, admission
    # admit+release, write-behind enqueue) stays micro-seconds scale.
    # The budget is deliberately loose for CI-host jitter — the class
    # it catches is a lock round-trip becoming a directory scan or a
    # JSON encode (100x-1000x moves), not a 2x wobble.
    overhead = out["overhead_ns_per_op"]
    assert set(overhead) == {"trace", "ledger", "deadline",
                             "admission", "write_behind", "sentinel"}
    for name, ns in overhead.items():
        assert ns < 100_000, \
            f"hot-path overhead {name} = {ns:.0f} ns/op (budget 100µs)"
    # The perf sentinel's named top-level copy (the record-diff key)
    # matches the table and meets the per-op budget on its own.
    assert out["sentinel_overhead_ns_per_op"] == overhead["sentinel"]
    assert out["sentinel_overhead_ns_per_op"] < 100_000

    # Wire v3 gates (the probes ran the real split posture over a unix
    # socket with streaming + coalescing + shm ring live):
    # * first BODY byte lands strictly before the burst's batch
    #   completion — the first-tile-out + chunk-frame path is alive;
    assert out["p50_first_tile_byte_ms"] is not None
    assert out["p50_batch_complete_ms"] is not None
    assert out["p50_first_tile_byte_ms"] < out["p50_batch_complete_ms"]
    # * the coalescer amortized frames under concurrent load;
    assert out["wire_frames_per_flush"] > 1.0, \
        f"no frame coalescing: {out['wire_frames_per_flush']}"
    # * ring negotiation happened, eligible bodies actually rode it
    #   (upload bodies + tile chunks), and the ring's isolated wire
    #   leg beat the socket path (interleaved best-of-3 per path; the
    #   measured margin is ~2.5-3x on an idle host, so a same-or-worse
    #   reading means the ring is broken, not that CI was noisy).
    assert out["wire_ring_negotiated"] >= 1
    assert out["shm_ring_hit_rate"] is not None
    assert out["shm_ring_hit_rate"] > 0.5
    assert out["shm_upload_mb_per_sec"] > out["socket_upload_mb_per_sec"]
    # Streamed responses really went out as chunk frames.
    assert out["wire_streams"] >= 1

    # Fleet gates (N=4 virtual members served a mixed-digest burst
    # through the real router + member stacks):
    # * the routing layer scales — aggregate throughput >= 2.5x one
    #   member (measured ~3.5x; the virtual exec occupancy makes the
    #   ratio a property of the ROUTER, not of CI core count);
    assert out["fleet_members"] == 4
    assert out["fleet_speedup"] >= 2.5, \
        f"fleet does not scale: {out['fleet_speedup']}x"
    # * the HBM tier SHARDS: total fleet plane residency ~= 1x the
    #   working set, every resident plane on exactly ONE member.
    #   Slightly under is legal — a plane whose every render of the
    #   burst was STOLEN stays unstaged (stealing is cache-neutral by
    #   design) — but over would mean duplication, which never is.
    ws = out["fleet_working_set_planes"]      # 2 channel planes a tile
    assert ws - 6 <= out["fleet_resident_planes"] <= ws, \
        f"sharded residency {out['fleet_resident_planes']}/{ws}"
    assert out["fleet_duplicate_staged_planes"] == 0, \
        f"HBM duplicated: {out['fleet_duplicate_staged_planes']} " \
        f"planes staged on >1 member"
    # * every request was routed, and membership spans the fleet.
    assert out["fleet_routed_total"] >= \
        out["fleet_working_set_planes"]
    assert set(out["fleet_member_planes"]) == {"m0", "m1", "m2", "m3"}

    # The printed line is the machine-readable contract.
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["metric"] == "smoke_hotpath_tiles_per_sec"


def test_bench_smoke_sessions(capsys):
    """The multi-user serving gate (bench.py --smoke --sessions):
    N panning viewer sessions + ONE hostile bulk client over a real
    2-member fleet.  With the session tier live (token buckets +
    weighted QoS dequeue), the hostile must not move interactive
    per-session p99 past 2x the no-bulk baseline and Jain's fairness
    index must hold >= 0.8; the A/B leg with QoS OFF must regress
    BOTH (the mechanism, proven, not assumed).  The prefetch leg
    replays a deterministic pan trace: predictive hit rate >= 0.5,
    zero duplicate-staged planes (digest dedup preserved)."""
    import bench
    from omero_ms_image_region_tpu.utils import telemetry

    telemetry.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_sessions_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 120.0, \
            f"sessions smoke took {elapsed:.0f}s (budget 120)"

        # QoS on: the hostile is contained.  The p99 bound is judged
        # against max(baseline, one bulk render of head-of-line
        # blocking) — below that floor the comparison is CI noise.
        baseline = out["sessions_baseline_p99_ms"]
        floor = max(2 * baseline, out["sessions_bulk_exec_ms"])
        assert out["sessions_interactive_p99_ms"] <= floor, \
            f"interactive p99 {out['sessions_interactive_p99_ms']} " \
            f"vs no-bulk baseline {baseline}"
        assert out["sessions_fairness_index"] >= 0.8
        # The hostile's overrun really shed with the fairness reason.
        assert out["sessions_bulk_shed"] > 0
        assert out["sessions_fairness_sheds"] > 0
        # ...but was never starved outright: its in-budget trickle
        # (burst + refill) still served.
        assert out["sessions_bulk_served"] + \
            out["sessions_bulk_shed"] > 0

        # A/B leg, QoS off: the identical hostile convoys the fleet —
        # both gates REGRESS to failure, proving the mechanism.
        assert out["sessions_qos_off_p99_ms"] > floor
        assert out["sessions_fairness_index_off"] < 0.8

        # Predictive prefetch over the deterministic pan trace.
        assert out["prefetch_hit_rate"] is not None
        assert out["prefetch_hit_rate"] >= 0.5
        assert out["prefetch_staged_planes"] > 0
        assert out["prefetch_duplicate_staged_planes"] == 0

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "sessions_smoke"
    finally:
        telemetry.reset()


def test_bench_smoke_overload_brownout(capsys):
    """The worst-hour gate (bench.py --smoke --overload): a 10x
    capacity burst with the pressure governor live must brown out in
    ORDER, serve-or-shed everything (zero 5xx-without-shed), keep p99
    bounded, and recover with hysteresis — engage/release exactly once
    per step, release in exact reverse."""
    import bench
    from omero_ms_image_region_tpu.server import pressure
    from omero_ms_image_region_tpu.utils import telemetry

    telemetry.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_overload_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 60.0, \
            f"overload smoke took {elapsed:.0f}s (budget 60)"

        # Zero 5xx-without-shed: every request served or shed 503.
        assert out["overload_unshed_failures"] == 0
        assert out["overload_served"] + out["overload_sheds"] == \
            out["burst"]
        assert out["overload_served"] > 0
        # The ladder actually walked (the burst is sized to make the
        # governor work, not to tickle one step).
        assert len(out["overload_steps_engaged"]) >= 3
        # Ordered engage, reverse release, full recovery, no flapping.
        assert out["overload_ladder_order_ok"] is True
        assert out["overload_release_reverse_ok"] is True
        assert out["overload_released_all"] is True
        assert out["overload_flapping"] is False
        # PR 10: the continuous prefetch budget scaled DOWN (the
        # level's cut, in (0,1)) strictly before the binary
        # pause_prefetch step floored it, and the release walk
        # restored it fully.
        assert out["overload_budget_scaled_before_pause"] is True
        assert out["overload_budget_restored"] is True
        # Bounded p99: the burst is ~1.6 s of virtual device time at
        # full parallelism; an order of magnitude covers CI jitter —
        # the class this catches is an UNBOUNDED tail (no shedding,
        # no brownout: p99 -> the whole burst behind one lane).
        assert out["overload_p99_ms"] is not None
        assert out["overload_p99_ms"] < 20_000.0

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "overload_smoke"
        # The governor uninstalled cleanly (no cross-test leakage).
        assert pressure.active() is None
    finally:
        telemetry.reset()


def test_bench_smoke_capacity(capsys):
    """The capacity-knee gate (bench.py --smoke --capacity): an
    OPEN-loop arrival process (services.loadmodel) swept across
    offered loads and fleet sizes must find a knee per size, the knee
    must scale with the fleet, and the closed-loop A/B on the same
    past-knee arrivals must report a LOWER (flattering) p99 — the
    regression test that keeps future bench legs from quietly
    reverting to closed-loop arrivals."""
    import bench
    from omero_ms_image_region_tpu.utils import telemetry

    telemetry.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_capacity_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 90.0, \
            f"capacity smoke took {elapsed:.0f}s (budget 90)"

        # A knee exists per fleet size, inside the measured sweep
        # (not censored: the top load factor must violate the SLO).
        for size in out["capacity_fleet_sizes"]:
            knee = out[f"capacity_knee_offered_tps_m{size}"]
            assert knee is not None and knee > 0, out
            points = out["capacity_curve"][f"m{size}"]
            assert len(points) >= 3
            offered = [p["offered_tps"] for p in points]
            assert offered == sorted(offered)
        assert out["capacity_knee_censored"] is False
        # The knee at the headline (widest) fleet, and its p99 meets
        # the SLO by construction.
        assert out["capacity_knee_offered_tps"] == \
            out["capacity_knee_offered_tps_m4"]
        assert out["p99_at_knee_ms"] <= out["capacity_slo_ms"]
        # Capacity SCALES with fleet size (the curve the autoscaler's
        # floor/ceiling sizing reads).  The bound is loose for small
        # CI hosts — the class it catches is a router that stopped
        # scaling at all.
        assert out["capacity_knee_offered_tps_m4"] >= \
            1.5 * out["capacity_knee_offered_tps_m1"], out
        # Open-loop honesty: the SAME past-knee offered load replayed
        # closed-loop must flatter (workers that wait self-throttle
        # to the service rate and never see the queueing collapse).
        assert out["openloop_p99_past_knee_ms"] is not None
        assert out["closedloop_p99_past_knee_ms"] is not None
        assert out["openloop_p99_past_knee_ms"] > \
            1.5 * out["closedloop_p99_past_knee_ms"], out
        # Mask-class arrivals really ran (the committed synthetic
        # fixtures under tests/data/masks through the real mask
        # endpoint) and every offered mask completed — a broken
        # fixture or mask path fails loudly here, never by silently
        # thinning the measured mix.
        assert out["capacity_mask_fraction"] > 0
        assert out["capacity_mask_offered"] > 0, out
        assert out["capacity_mask_completed"] == \
            out["capacity_mask_offered"], out

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "capacity_smoke"
    finally:
        telemetry.reset()


def test_bench_smoke_hotkey(capsys):
    """The hot-plane replication gate (bench.py --smoke --hotkey):
    a zipf storm on a 2-member fleet must retain >= 0.7x the uniform
    mix's throughput WITH replication, the replication-disabled A/B
    must measure LESS, replica staging must never duplicate-stage,
    and heat decay must demote the viral route back to R=1 — all
    read from live counters, not from the bench's own claims."""
    import bench
    from omero_ms_image_region_tpu.utils import decisions, telemetry

    telemetry.reset()
    decisions.LEDGER.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_hotkey_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 60.0, \
            f"hotkey smoke took {elapsed:.0f}s (budget 60)"

        # The storm survived: throughput under the viral-plane skew
        # held >= 0.7x the uniform mix on the SAME fleet.
        assert out["hotkey_storm_ratio"] >= 0.7, out
        # The replication-disabled A/B measured LESS — the honesty
        # leg that proves the tier earns its complexity (a storm a
        # plain ring absorbs equally means the drill measured
        # nothing).
        assert out["hotkey_disabled_tps"] < out["hotkey_storm_tps"], \
            out
        assert out["hotkey_replication_gain"] > 1.0, out
        # The lifecycle actually ran, from live counters: promotion,
        # balanced reads off the ring owner, replica staging with
        # ZERO duplicate stagings, and the shard report classifying
        # the hot plane as replicated — never duplicate.
        assert out["hotkey_promotions"] >= 1, out
        assert out["hotkey_balanced_reads"] >= 1, out
        assert out["hotkey_duplicate_staged"] == 0, out
        assert out["hotkey_shard_duplicates"] == 0, out
        # Decay demoted the viral route back to R=1 after the storm
        # (swept on the live dispatch path, not by the bench).
        assert out["hotkey_demoted_after_decay"] is True, out
        assert out["hotkey_hot_routes_after_decay"] == 0, out
        assert out["hotkey_demotions"] >= 1, out
        # The autoscaler read replica pressure as a scale signal: at
        # the fleet ceiling the want-up it forces is refused, and
        # that decision record carries the signal (the ledger line an
        # operator reads during a real storm).
        assert out["hotkey_autoscaler_signal"] is True, out
        assert out["hotkey_ledger_promotions"] >= 1, out
        assert out["hotkey_peak_replica_pressure"] > 0, out

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "hotkey_smoke"
    finally:
        decisions.LEDGER.reset()
        telemetry.reset()


def test_bench_smoke_partition(capsys):
    """The netsplit chaos gate (bench.py --smoke --partition): a
    3-host fleet (two REAL sidecar processes) driven through
    partition -> fence -> heal -> rejoin under sustained load, with a
    two-phase epoch roll committed mid-partition.  The majority side
    must fail NOTHING without counting it shed; the minority must
    fence (with counted refusals), restore, converge to the committed
    epoch with no operator action, and agree bit-exactly after heal."""
    import bench
    from omero_ms_image_region_tpu.utils import decisions, telemetry

    telemetry.reset()
    decisions.LEDGER.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_partition_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 120.0, \
            f"partition smoke took {elapsed:.0f}s (budget 120)"

        # Join-time manifest agreement (digest + the peers' OWN ring
        # math on the golden probe keys) before any chaos.
        assert out["part_manifest_agreed"] == 1, out
        # Majority availability: the load loop never saw a failure
        # that was not counted shed — the drill's headline contract.
        assert out["part_load_requests"] > 0, out
        assert out["part_majority_5xx"] == 0, out
        # The minority fenced within the drill's polling budget and
        # refused state-changing ops while dark (each one counted).
        assert out["part_fence_ms"] > 0, out
        assert out["part_minority_refusals"] >= 2, out
        # The mid-partition roll committed on strict-majority acks
        # (A + B of 3 hosts) — a dark minority cannot block an epoch.
        assert out["part_roll_committed"] == 1, out
        assert out["part_roll_acks"] == 2, out
        # Heal: restore, anti-entropy convergence to epoch 2, full
        # digest + probe-owner agreement, byte-identical round-trip,
        # and the fenced/restored pair in C's own decision ledger.
        assert out["part_restore_ms"] > 0, out
        assert out["part_rejoin_epoch"] == 2, out
        assert out["part_postheal_agree"] == 1, out
        assert out["part_byte_agree"] == 1, out
        assert out["part_quorum_ledger"] >= 2, out

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "partition_smoke"
    finally:
        decisions.LEDGER.reset()
        telemetry.reset()


def test_bench_smoke_sentinel(capsys):
    """The induced-drift sentinel gate (bench.py --smoke --sentinel):
    a deterministic latency step on a virtual clock through a real
    2-member fleet must yield EXACTLY ONE confirmed drift (on the
    stepped member, never its healthy peer), EXACTLY ONE complete
    incident bundle (manifest listing profile + flight + costs +
    sketch diff + exemplars), one kind=sentinel ledger record, and a
    recovery that clears the verdict — the whole confirm/capture/
    recover cycle, with the strong assertions living inside the
    drill itself."""
    import bench
    from omero_ms_image_region_tpu.utils import decisions, telemetry

    telemetry.reset()
    decisions.LEDGER.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_sentinel_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 60.0, \
            f"sentinel smoke took {elapsed:.0f}s (budget 60)"

        assert out["sentinel_drift_confirms"] == 1, out
        assert out["sentinel_drifting_member"] == "m1", out
        assert out["sentinel_bundles"] == 1, out
        assert set(out["sentinel_bundle_files"]) == {
            "profile", "flight", "costs", "sketch_diff",
            "exemplars"}, out
        assert out["sentinel_recovered"] is True, out
        assert out["sentinel_merged_members"] == ["m0", "m1"], out
        assert out["sentinel_drift_keys"], out

        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["metric"] == "sentinel_smoke"
    finally:
        decisions.LEDGER.reset()
        telemetry.reset()


def test_bench_smoke_offload(capsys):
    """The repeat-viewer offload gate (bench.py --smoke --offload):
    over a real 2-sidecar remote fleet, the edge ladder (warm-local
    byte hit -> warm-peer byte fetch -> If-None-Match 304) absorbs
    >= 0.8 of the repeat mix with zero device renders, 304s land at
    least 10x below the cold render p50, and the re-routed working
    set serves byte-identical peer bytes."""
    import bench

    t0 = time.monotonic()
    out = bench.bench_offload_smoke()
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, \
        f"offload bench took {elapsed:.0f}s (budget 60)"

    # THE acceptance gates (issue 11): repeat viewers mostly never
    # touch the renderer, and revalidation is an order of magnitude
    # cheaper than a render.
    assert out["origin_offload_ratio"] >= 0.8, out
    assert out["p50_304_ms"] * 10.0 <= out["p50_service_tile_ms"], out
    # The warm-peer leg really re-routed work and served it from the
    # draining owner's byte tier (byte-identity is asserted inside
    # the run; a zero peer_working_set would prove nothing).
    assert out["peer_working_set"] > 0
    assert out["peer_hit_rate"] >= 0.8, out
    assert out["warm_renders"] == 0
    assert out["n_304"] > 0

    # One parseable JSON line on stdout for the driver.
    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc["metric"] == "offload_smoke"
    assert doc["origin_offload_ratio"] == out["origin_offload_ratio"]


def test_bench_smoke_workloads(capsys):
    """The device-workloads gate (bench.py --smoke --workloads): the
    batched device mask path serves bytes IDENTICAL to the host
    rasterizer across the committed fixtures and flip lanes, the
    overlay composite matches the refimpl golden, the pyramid job
    commits a readable NGFF group, and the animation strip streams
    every frame in order then cancels cleanly on a mid-stream close
    — all asserted inside the run; the keys feed the WORKLOADS
    record family."""
    import bench
    from omero_ms_image_region_tpu.utils import telemetry

    telemetry.reset()
    try:
        t0 = time.monotonic()
        out = bench.bench_workloads_smoke()
        elapsed = time.monotonic() - t0
        assert elapsed < 60.0, \
            f"workloads bench took {elapsed:.0f}s (budget 60)"

        assert out["mask_parity_ok"] is True
        assert out["mask_renders"] >= 12, out
        assert out["overlay_parity_ok"] is True
        assert out["pyramid_levels"] >= 2, out
        assert out["pyramid_readable_levels"] == \
            out["pyramid_levels"], out
        assert out["anim_frames"] >= 8, out
        assert out["anim_first_frame_ms"] <= out["anim_total_ms"], out
        assert out["anim_cancel_ok"] is True

        line = capsys.readouterr().out.strip().splitlines()[-1]
        doc = json.loads(line)
        assert doc["metric"] == "workloads_smoke"
        assert doc["mask_renders"] == out["mask_renders"]
    finally:
        telemetry.reset()
