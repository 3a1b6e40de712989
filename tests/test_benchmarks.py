"""Benchmark-suite smoke: the F1 quality gate must hold (CPU, tiny)."""

import json

import pytest

import benchmarks.suite as suite


def test_golden_trace_f1_is_perfect(capsys):
    suite.main(["--small", "--config", "f1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"] == "f1-golden-trace"
    assert line["value"] == 1.0
    assert line["precision"] == 1.0 and line["recall"] == 1.0


def test_suite_config1_runs_small(capsys):
    suite.main(["--small", "--config", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "windows_per_sec"
    assert line["value"] > 0


def test_quality_benchmark_structured_beats_flat_on_seasonal(capsys):
    """Smoke the quality harness: fitted HW must dominate the global-mean
    default on the seasonal scenario, and the joint detectors must hold
    F1 >= 0.9 on their scenarios (VERDICT r1 item 5):

      * joint-bivariate   — off-ridge points, marginally in-range
      * joint-lstm        — all-metric spikes incl. seasonal troughs
                            (contextual: near the marginal mean there)
      * joint-lstm-break  — one metric deviating from the co-moving pack
    """
    import benchmarks.quality as quality

    quality.main(["--small"])
    rows = [
        json.loads(line) for line in capsys.readouterr().out.strip().splitlines()
    ]
    by = {(r["scenario"], r["algorithm"]): r for r in rows}
    f1 = lambda k: by[k]["f1"]
    assert f1(("seasonal", "holt_winters")) > 0.9
    assert f1(("seasonal", "moving_average_all")) < 0.5
    assert f1(("flat", "moving_average_all")) > 0.9
    assert f1(("joint-bivariate", "bivariate_normal")) >= 0.9
    # hybrid joint detector (VERDICT r2 item 4): precision >= 0.95 at
    # recall >= 0.98 — fail-fast + AutoRollback semantics price every
    # false point as a potential rollback
    for k in ("joint-lstm", "joint-lstm-break"):
        row = by[(k, "lstm_autoencoder")]
        assert row["precision"] >= 0.95, row
        assert row["recall"] >= 0.98, row
    # and CLEAN windows must not page at all (job-level false alarms)
    assert by[("joint-clean-windows", "lstm_autoencoder")]["job_false_alarms"] == 0
    # auto_univariate (VERDICT r1 item 6): structure screen routes
    # seasonal/trend series to the fitted model without regressing flat
    assert f1(("seasonal", "auto_univariate")) >= 0.95
    assert f1(("trend", "auto_univariate")) >= 0.95
    assert f1(("flat", "auto_univariate")) >= 0.95
    # level-shift scenario (VERDICT r2 item 7): the changepoint trend
    # (models/seasonal.py hinges) keeps the band centered through a
    # redeploy-style step; a global-band model drowns
    assert f1(("shift", "seasonal_p24")) >= 0.99
    assert f1(("shift", "auto_univariate")) >= 0.99
    assert f1(("shift", "moving_average_all")) < 0.5
    # the reference's REAL workload shape (VERDICT r2 item 1): daily
    # m=1440 cycle over the 7-day 10,080-pt history — the auto screen
    # must route it to a structured model and hold F1 >= 0.99, while the
    # global-mean default's band swallows the cycle
    assert f1(("daily-1440", "auto_univariate")) >= 0.99
    assert f1(("daily-1440", "seasonal")) >= 0.99
    assert f1(("daily-1440", "moving_average_all")) < 0.5
    # ONE mixed batch of every shape — auto must route per series inside
    # a single compiled program (the production condition)
    mix = by[("fleet-mix", "auto_univariate")]
    assert mix["f1"] >= 0.97, mix
    assert all(v >= 0.95 for v in mix["per_kind_f1"].values()), mix
    # sparse sharp cycle features (cron-style bursts): only the pooled
    # phase-means fit represents the shape, and the auto screen's
    # phase-significance gate must route to it (the SSE-ratio gate alone
    # is blind to features covering <1% of samples)
    assert f1(("daily-1440-sharp", "phase_means")) >= 0.99
    assert f1(("daily-1440-sharp", "auto_univariate")) >= 0.99
    assert f1(("daily-1440-sharp", "seasonal")) < 0.7  # Fourier can't
    assert f1(("daily-1440-sharp", "moving_average_all")) < 0.7


def test_worker_bench_churn_mode_small():
    """Churn machinery (VERDICT r4 #4): each warm tick retires and
    admits 10% of services; every tick must still process the full
    fleet, the columnar fast path must keep serving the warm majority
    (per-key admission revalidation — no wholesale re-walk), and no
    arena fallbacks may fire."""
    from benchmarks.worker_bench import run

    out = run(
        services=20,
        ticks=3,
        algorithm="moving_average_all",
        season=24,
        hist_len=256,
        cur_len=30,
        churn=0.1,
    )
    assert out["churn_per_tick"] == 2
    assert out["arena_fallbacks"] == 0
    assert out["warm_windows_per_sec"] > 0
    assert out["cold_first_verdict_seconds"] <= out["cold_tick_seconds"]


def test_pipeline_bench_small_smoke(capsys):
    """Shipped-tick pipeline benchmark, one iteration at CI shapes: the
    serial and pipelined cold ticks must both run, produce identical
    store writes (asserted inside run()), and report occupancy stats."""
    import benchmarks.pipeline_bench as pipeline_bench

    pipeline_bench.main(["--small"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"] == "p-pipelined-cold-tick"
    assert line["metric"] == "cold_tick_speedup"
    assert line["equivalent"] is True
    assert line["value"] and line["value"] > 0
    assert line["chunks"] == 3
    assert line["serial_cold_tick_seconds"] > 0
    assert line["pipelined_cold_tick_seconds"] > 0
    assert 0.0 <= line["overlap_ratio"] < 1.0


def test_worker_bench_mixed_fleet_small():
    """`make bench-mixed --small` smoke (ISSUE 4): a mixed fleet (15%
    joint docs) must run cold + warm, with the JOINT docs scored on the
    columnar path during the warm ticks (per-kind counters > 0 is the
    acceptance signal) and zero joint-arena fallbacks."""
    from benchmarks.worker_bench import run

    out = run(
        services=40,
        ticks=2,
        algorithm="auto",
        season=24,
        hist_len=256,
        cur_len=30,
        joint_frac=0.15,
    )
    assert out["joint_services"] == 6
    fast = out["fast_path_docs"]
    assert fast["bivariate"] > 0 and fast["lstm"] > 0, fast
    assert fast["univariate"] > 0, fast
    assert out["joint_arena"]["fallbacks"] == 0
    assert out["joint_arena"]["rows_live"] > 0
    assert out["warm_windows_per_sec"] > 0


def test_ingest_bench_small_smoke(capsys):
    """`make bench-ingest --small` smoke (ISSUE 5): warm RingSource vs
    PrometheusSource-over-localhost on the same fleet — judgments must
    be byte-identical (asserted inside run()), the push worker's ticks
    must issue ZERO Prometheus HTTP requests, and the fetch stage must
    get faster (the >= 5x acceptance bar is checked at full benchmark
    shapes, not CI smoke shapes)."""
    import benchmarks.ingest_bench as ingest_bench

    ingest_bench.main(["--small"])
    lines = capsys.readouterr().out.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["config"] == "i-ingest-warm-fetch"
    assert line["equivalent"] is True
    assert line["zero_http_warm_tick"] is True
    assert line["ring_hit_ratio"] == 1.0
    assert line["series_resident"] == line["windows"]
    assert line["value"] and line["value"] > 1.0
    # ISSUE 18 cross-codec parity on the fixed fleet fixture: the
    # receiver answered byte-identical responses for JSON and binary
    # warming, and the judged statuses matched (both asserted inside
    # run(); the flags witness the asserts ran)
    assert line["codec_responses_identical"] is True
    assert line["codec_statuses_identical"] is True
    # wire-protocol phase prints its own line before the warm-fetch one
    wire = json.loads(lines[-2])
    assert wire["config"] == "i-ingest-wire-codec"
    assert (
        wire["codecs"]["json"]["samples"]
        == wire["codecs"]["binary"]["samples"]
        == wire["codecs"]["binary_snappy"]["samples"]
        == wire["total_samples"]
    )
    assert wire["value"] and wire["value"] > 0
    assert wire["dirty_slo"]["items_closed"] > 0
    # perf bars (>= 5M samples/s/worker, >= 6x JSON at equal CPU, SLO
    # p99 <= 0.5 s) are asserted in-run at FULL shapes only, not CI smoke


def test_cold_bench_small_smoke(capsys):
    """`make bench-cold --small` smoke (ISSUE 10): ring-resident cold
    fits (zero HTTP, byte-identical statuses vs the pull path — both
    asserted inside run()), a zero-HTTP churn tick, short-history
    newcomer admission (no UNKNOWNs), and refinement draining to
    band-parity with from-scratch fits."""
    import benchmarks.cold_bench as cold_bench

    cold_bench.main(["--small"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"] == "c-cold-ring-tick"
    assert line["zero_http_cold"] is True
    assert line["zero_http_churn"] is True
    assert line["newcomer_unknown"] == 0
    assert line["band_parity"] is True
    assert line["refine_counts"]["pending"] == 0
    assert line["cold_speedup"] > 1.0


def test_scaleout_bench_small_smoke(capsys):
    """`make bench-scaleout --small` smoke (ISSUE 6): 1 then 2 REAL
    worker processes over the HTTP store — exactly-once judgment and
    the kill/rebalance ≤2-tick bar are asserted inside run(); routed
    pushes must converge by the second cycle (the ≥3x throughput bar is
    checked at full benchmark shapes, not CI smoke shapes)."""
    import benchmarks.scaleout_bench as scaleout_bench

    scaleout_bench.main(["--small"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"] == "s-mesh-scaleout"
    assert line["worker_counts"] == [1, 2]
    assert line["no_double_judgment"] is True
    assert line["routed_push_converged"] is True
    assert line["rebalance"] is not None
    assert line["rebalance"]["worst_ticks_after_heal"] <= 2
    assert line["rebalance"]["orphan_docs"] > 0
    assert all(
        v > 0 for v in line["fleet_warm_windows_per_sec"].values()
    )


def test_scaleout_bench_sharded_judge_small_smoke(capsys):
    """`make bench-scaleout` sharded-judge variant smoke (ISSUE 13):
    one REAL worker process whose judge partitions over a forced
    2-virtual-device mesh — exactly-once judgment asserts run inside
    run(), the in-run partition assert runs inside ShardedJudge._place,
    and the summary must carry the roofline account (H2D place / device
    dispatch / host gather / decode) plus the padded-row fraction."""
    import benchmarks.scaleout_bench as scaleout_bench

    scaleout_bench.main(
        ["--small", "--workers", "1", "--no-kill", "--device-mesh", "2"]
    )
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"] == "s-mesh-scaleout-sharded"
    assert line["device_mesh"] == 2
    rl = line["roofline"]
    assert rl is not None
    assert rl["devices_per_worker"] == 2
    assert rl["h2d_seconds"] >= 0 and rl["gather_seconds"] > 0
    assert rl["padded_row_fraction"] is not None
    # per-device bytes x devices: the SHARD-SUM under the default
    # sharded layout (ISSUE 19) — same arithmetic the replicated
    # layout used for its replication tax
    assert rl["arena_layout"] == "sharded"
    assert rl["arena_capacity_rows"] > 0
    assert rl["arena_total_device_bytes"] == 2 * rl["arena_replica_bytes"]
    # the ISSUE 19 capacity claims ran in-run (run_arena_check asserts
    # them before the fleet starts; the summary echoes the verdict)
    cap = line["arena_capacity"]
    assert cap["oom_replicated"] and cap["fits_sharded"], cap
    assert cap["linear_scaling"], cap
    assert cap["warm_gather_collectives"] == [], cap
    assert line["no_double_judgment"] is True
    assert all(
        v > 0 for v in line["fleet_warm_windows_per_sec"].values()
    )


def test_restart_bench_small_smoke(capsys):
    """`make bench-restart --small` smoke (ISSUE 7): one REAL worker
    SIGKILLed mid-tick (claim persisted, no verdict) and restarted
    against the same snapshot directory, single-worker and 3-worker
    mesh variants. The acceptance bar is asserted inside run() —
    recovery tick ≥ 90% fast-path, ZERO fallback fetches, exactly-once
    judgment across the kill — and echoed in the output line."""
    import benchmarks.restart_bench as restart_bench

    restart_bench.main(["--small"])
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.strip().splitlines()
    ]
    assert [ln["variant"] for ln in lines] == ["single", "mesh-3"]
    for ln in lines:
        assert ln["config"] == "r-restart-recovery"
        assert ln["recovery_fast_fraction"] >= 0.9
        assert ln["recovery_fallback_fetches"] == 0
        assert ln["exactly_once"] is True
        assert ln["restored_series"] > 0 and ln["restored_fits"] > 0
        assert ln["parked_docs_at_kill"] > 0


def test_chaos_bench_small_smoke(capsys):
    """`make bench-chaos --small` smoke (ISSUE 9): the 3-worker chaos
    soak — store brownout, prometheus blackhole, pusher flood, skewed
    clocks, worker crash — with every acceptance assert in-run (the
    bench FAILS on a lost/duplicated verdict, a breaker that never
    re-closes, recovery > 2 busy ticks, a lock-witness miss, or an
    unbounded buffer). The summary line echoes the bars; `make ci`
    runs this via test-fast."""
    import benchmarks.chaos_bench as chaos_bench

    chaos_bench.main(["--small"])
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.strip().splitlines()
    ]
    summary = lines[-1]
    assert summary["config"] == "c-chaos-soak"
    assert summary["phases"] == [
        "baseline", "brownout", "blackhole", "flood", "skew", "crash",
    ]
    assert summary["no_lost_or_duplicated_verdicts"] is True
    assert summary["breakers_reclosed"] is True
    assert summary["recovery_within_2_ticks"] is True
    assert summary["lock_witness_clean"] is True
    assert summary["memory_bounded"] is True
    by_phase = {ln["phase"]: ln for ln in lines}
    # mid-write asserts gated on observed overlap: on a loaded 1-CPU
    # host the judge pass can outlast even the bench's extended
    # brownout window, in which case no write could have buffered —
    # the bench records that honestly instead of flaking
    if by_phase["brownout"]["overlap_observed"]:
        assert by_phase["brownout"]["buffered"] > 0
        assert by_phase["brownout"]["replayed"] > 0
    assert by_phase["blackhole"]["released"] > 0
    assert by_phase["flood"]["sheds"] > 0
    assert by_phase["crash"]["parked_at_wedge"] > 0


def test_latency_bench_small_smoke(capsys):
    """`make bench-latency --small` smoke (ISSUE 12): the reactive
    plane end to end at CI shapes — a deployment PATCHed into the fake
    kube server produces a verdict through the real watch stream +
    micro-tick chain, anomaly injections through the real receiver all
    land (the bench FAILS on a timed-out injection, a missing deploy
    verdict, or a micro-vs-full tick-path parity break). The <= 1 s /
    p99 <= 2 s bars are asserted at the full 16k shape, not CI smoke
    shapes."""
    import benchmarks.latency_bench as latency_bench

    latency_bench.main(["--small"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["bench"] == "latency"
    assert out["injections_timed_out"] == 0
    assert out["deploy_to_first_verdict_seconds"] is not None
    assert out["anomaly_latency_p99_seconds"] is not None
    assert out["parity"] == "byte-identical (asserted)"
    # ISSUE 15: the sliced-vs-monolithic parity arm ran (the sharded
    # child arm is full-run only), and the warm-throughput phase
    # actually exercised the sliced warm pipeline (slices > 1)
    assert out["sliced_parity"].startswith("byte-identical")
    assert out["warm_throughput"]["slices"] > 1
    assert out["warm_throughput"]["warm_windows_per_sec"] > 0


def test_noisy_bench_small_smoke(capsys):
    """`make bench-noisy --small` smoke (ISSUE 20): the noisy-neighbor
    fleet at CI shapes — a whale tenant at 10x share floods the real
    receiver while quiet tenants' anomaly injections are measured
    against a solo-tenant control. The bench FAILS in-run on a shed
    landing anywhere but the whale, a quiet-tenant F1 change, an
    evicted quiet series, a missing /debug/state tenants section, or a
    zero-vs-one-tenant parity break; the p99-vs-control bar asserts at
    the full shape only."""
    import benchmarks.noisy_bench as noisy_bench

    noisy_bench.main(["--small"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["bench"] == "noisy"
    assert out["quiet_push_codes"] == {"200": out["inject"]}
    assert out["whale_flood_codes"].get("429", 0) > 0
    assert out["treatment"]["f1"] == out["control"]["f1"]
    assert out["treatment"]["timeouts"] == 0
    assert out["accounting"]["t0"]["shed"] > 0
    assert out["debug_state_tenants"] is True
    assert out["parity"].startswith("zero-vs-one-tenant byte-identical")


def test_elastic_bench_small_smoke(capsys):
    """`make bench-elastic --small` smoke (ISSUE 11): 2 -> 4 -> 2
    workers under continuous load with every acceptance assert in-run
    (the bench FAILS on a lost/duplicated verdict, an UNKNOWN
    regression, a handoff past 2 ticks, a cold refit or fallback fetch
    on a planned move, or a blackholed transfer that wedges instead of
    degrading to cold refit). The summary line echoes the bars; `make
    ci` runs this via test-fast."""
    import benchmarks.elastic_bench as elastic_bench

    elastic_bench.main(["--small"])
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.strip().splitlines()
    ]
    summary = lines[-1]
    assert summary["config"] == "c-elastic"
    assert summary["phases"] == [
        "load", "scale_up", "scale_down", "fault",
    ]
    assert summary["no_lost_or_duplicated_verdicts"] is True
    assert summary["no_unknown_regression"] is True
    assert summary["planned_moves_zero_cold_refits"] is True
    assert summary["planned_moves_zero_fallback_fetches"] is True
    assert summary["handoff_within_2_ticks"] is True
    assert summary["fault_degraded_to_cold_refit"] is True
    assert summary["lock_witness_clean"] is True
    by_phase = {ln["phase"]: ln for ln in lines}
    assert by_phase["scale_up"]["moved_series"] > 0
    assert by_phase["scale_up"]["moved_fits"] > 0
    assert by_phase["scale_up"]["joiner_docs"] > 0
    assert by_phase["scale_down"]["survivor_cold_refits"] == 0
    assert by_phase["fault"]["failed_sends"] >= 1
    assert by_phase["fault"]["w5_cold_refits"] > 0


def test_plane_bench_small_smoke():
    """Watch-plane scale benchmark (VERDICT r5 #7) at CI shapes: the
    informer resync and the controller poll tick must run and stay
    inside the ~1 s budget."""
    from benchmarks.plane_bench import run

    out = run(monitors=64, ticks=2)
    assert out["events_handled"] == 64
    assert out["within_budget"] is True
    assert out["poll_tick_seconds"] >= 0


def test_fleet_mix_f1_pinned():
    """Regression pin for the fleet-mix quality scenario (ISSUE 4: the
    joint columnar path must not move univariate routing quality): at
    the CI shape, `auto_univariate` over one batch mixing all five
    shapes holds the round-5 floors."""
    from benchmarks.quality import fleet_mix

    f1, precision, recall, by_kind = fleet_mix(32, 240, 30)
    assert f1 >= 0.97, (f1, by_kind)
    assert precision >= 0.99, (precision, by_kind)
    assert all(v >= 0.95 for v in by_kind.values()), by_kind


def test_mixed_univariate_joint_worker_tick():
    """VERDICT r4 #5: ONE worker claim set mixing all five univariate
    shapes with bivariate + LSTM-hybrid joint jobs under the `auto`
    selector; tick 1 warms every model clean, tick 2 judges the spiked
    fleet warm (univariate docs on the columnar fast path, joint docs on
    the slow path — in the same tick). Small CI shapes; at benchmark
    size (per_uni=24, per_joint=4) every kind measures F1 = 1.0 with 0
    false alarms."""
    from benchmarks.quality import mixed_fleet_tick

    by_kind, false_alarms = mixed_fleet_tick(4, 2, 240, 30)
    assert false_alarms == 0  # clean docs stay healthy: no contamination
    for kind, (f1, points) in by_kind.items():
        floor = 1.0 if kind in ("bivariate", "lstm") else 0.93
        assert f1 >= floor, (kind, f1, points)


def test_mixed_bench_canary_small():
    """`make bench-mixed` canary phase smoke (ISSUE 14): a canary-heavy
    fleet judged through the columnar canary bucket vs the knob-off and
    full-object arms — byte parity between ALL arms is asserted inside
    run_canary() at every shape; the >= 3x / >= 12.5k w/s bars are
    asserted at full benchmark shapes, not CI smoke shapes."""
    from benchmarks.mixed_bench import run_canary

    out = run_canary(24, 2, 256, 30, assert_bars=False)
    assert out["config"] == "w-canary-fleet-tick"
    assert out["equivalent"] is True
    assert out["canary_services"] == 12
    fast = out["fast_path_docs"]
    assert fast["baseline"] > 0 and fast["univariate"] > 0, fast
    assert out["columnar"]["warm_windows_per_sec"] > 0
    assert out["object_path"]["warm_windows_per_sec"] > 0
    assert out["value"] > 0


def test_mixed_bench_scenario_matrix_small():
    """Scenario-matrix smoke (ISSUE 14): every strategy x regime cell
    runs at CI shape and holds its F1 floor (in-run assert inside
    run_scenarios); canary cells must report the pairwise false-reject
    rate and never score materially WORSE than their baseline-less
    siblings on the same regime (the rank tests must not hurt clean
    detection)."""
    from benchmarks.mixed_bench import run_scenarios
    from benchmarks.scenarios import REGIMES, STRATEGIES

    rows = run_scenarios(16, 240, 30, assert_floors=True)
    assert len(rows) == len(STRATEGIES) * len(REGIMES)
    by = {(r["strategy"], r["regime"]): r for r in rows}
    for regime in REGIMES:
        canary = by[("canary", regime)]
        assert "pairwise_differs_rate" in canary
        for other in ("rolling", "continuous"):
            assert canary["f1"] >= by[(other, regime)]["f1"] - 0.1, (
                canary, by[(other, regime)],
            )


def test_mixed_bench_label_shape_routing_small():
    """Label-shape routing cells (ISSUE 15 satellite / ROADMAP item
    4's generator gap): multi-cluster and multi-tenant label shapes
    must leave doc↔series co-location AND ownership spread invariant —
    the mesh routes by the `app` label value alone, so extra
    cluster/tenant labels can never move a series off its document's
    worker (asserted inside the cell)."""
    from benchmarks.scenarios import LABEL_SHAPES, label_shape_routing_cell

    rows = [
        label_shape_routing_cell(shape, services=64, workers=4)
        for shape in LABEL_SHAPES
    ]
    assert [r["label_shape"] for r in rows] == list(LABEL_SHAPES)
    for row in rows:
        assert row["co_located"] is True
        assert sum(row["owners"].values()) == 64
    # ownership is a function of the ROUTE KEY alone: identical
    # distributions across shapes is the invariance made visible
    assert rows[0]["owners"] == rows[1]["owners"] == rows[2]["owners"]


def test_bench_report_round_and_merge(tmp_path, monkeypatch):
    """BENCH_rNN.json emission (ISSUE 15 satellite): summaries merge
    per bench under one round file, --small runs never write, and the
    round resolves from the highest existing BENCH_rNN.json + 1."""
    from benchmarks import report

    # the env override must not leak into the resolution assertions
    monkeypatch.delenv("FOREMAST_BENCH_ROUND", raising=False)

    assert report.current_round(str(tmp_path)) == 1  # no artifacts yet
    path = str(tmp_path / "BENCH_r99.json")
    assert report.write_summary("latency", {"p99": 0.4}, small=True) is None
    out = report.write_summary("latency", {"p99": 0.4}, path=path)
    assert out == path
    report.write_summary("mixed", {"wps": 1.0}, path=path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["round"] == 99  # an explicit BENCH_rNN.json names its round
    assert set(doc["results"]) == {"latency", "mixed"}
    assert doc["results"]["latency"]["asserts_passed"] is True
    assert doc["results"]["latency"]["p99"] == 0.4
    # round resolution: highest existing artifact + 1; other files and
    # near-miss names do not count
    (tmp_path / "BENCH_r07.json").write_text("{}")
    (tmp_path / "BENCH_r100.json.tmp").write_text("{}")
    assert report.current_round(str(tmp_path)) == 100
    monkeypatch.setenv("FOREMAST_BENCH_ROUND", "12")
    assert report.current_round(str(tmp_path)) == 12
    monkeypatch.delenv("FOREMAST_BENCH_ROUND")
    # the REAL checkout resolves to a round past every committed artifact
    assert report.current_round() >= 21
    # a foreign-schema artifact (e.g. the driver's own BENCH_rNN.json)
    # is never clobbered — loud failure, not silent overwrite
    foreign = tmp_path / "BENCH_r01.json"
    foreign.write_text('{"n": 1, "cmd": "x"}')
    with pytest.raises(ValueError):
        report.write_summary("latency", {"p99": 1}, path=str(foreign))
    assert json.loads(foreign.read_text())["n"] == 1


def test_mixed_bench_fanin_small():
    """Pusher fan-in smoke (ISSUE 14): the canary fleet pushed through
    the REAL receiver by 1 vs 8 concurrent pushers, judged pure-push
    from the ring — statuses identical across fan-in shapes (asserted
    inside run_fanin) and the canary bucket engaged on the warm tick."""
    from benchmarks.mixed_bench import run_fanin

    rows = run_fanin(8, 128, 30, (1, 4))
    assert [r["fan_in"] for r in rows] == [1, 4]
    for row in rows:
        assert row["pure_push"] is True
        assert row["equivalent_across_shapes"] is True
        assert row["push_samples_per_sec"] > 0
