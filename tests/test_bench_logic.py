"""Unit tests for bench.py's config-selection logic (no chip needed).

The measurement numbers themselves are chip-side; what IS testable here
is the glue around them: the headline resnet config fixed in code, no
row without a TPU, and metric-name stability across success/skip/error
rows.
"""

import bench


def test_resnet_headline_ignores_persisted_sweep(monkeypatch):
    """The headline config is fixed in code: a sweep row persisted by
    an earlier capture must not steer today's batch size."""
    stale = {"rows": {"resnet50_sweep": {"configs": [
        {"batch": 192, "bn_stats_sample": 8, "mfu": 0.17}]}}}
    calls = []

    def fake_time_config(peak, batch=128, remat=False, iters=40,
                         data_format="NHWC", bn_stats_sample=0):
        calls.append({"batch": batch, "ss": bn_stats_sample})
        return {"batch": batch, "remat": remat, "step_ms": 10.0,
                "samples_per_sec": 1.0, "mfu": 0.2}

    monkeypatch.setattr(bench, "_load_bench_tpu", lambda: stale)
    monkeypatch.setattr(bench, "resnet50_time_config", fake_time_config)
    row = bench.bench_resnet50(True, 197e12)
    assert calls == [{"batch": 128, "ss": 16}]
    assert row["batch"] == 128
    assert row["metric"] == "resnet50_train_mfu"


def test_main_needs_a_tpu_and_replays_nothing(capsys):
    """`python bench.py` on a host with no TPU: non-zero exit naming
    the platform found, and not one row on stdout (in particular none
    out of BENCH_TPU.json)."""
    import pytest

    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)
    assert "cpu" in str(ei.value.code)
    assert capsys.readouterr().out == ""


def test_error_rows_carry_real_metric_names():
    # the benches table must name each config's REAL metric so error
    # rows can't flip keys vs success rows; this pins the pairs that
    # previously drifted
    src = open(bench.__file__).read()
    for key, metric in (
            ("decode", "gpt_decode_tokens_per_sec"),
            ("longctx", "longctx_8k_train_mfu"),
            ("bert_chunked_ce", "bert_chunked_ce_mfu"),
            ("transformer_h128", "transformer_h128_train_mfu")):
        assert f'("{key}", "{metric}"' in src, (key, metric)

# ---------------------------------------------------------------------------
# resnet50_sweep lever grid (ISSUE 1 tentpole)
# ---------------------------------------------------------------------------


def _row(name, mfu, **kw):
    r = {"config": name, "batch": 8, "data_format": "NCHW",
         "remat": False, "prefetch": False, "precision": "highest",
         "step_ms": 1.0, "samples_per_sec": 1.0, "mfu": mfu}
    r.update(kw)
    return r


def test_sweep_payload_lever_deltas_and_best():
    rows = [_row("base", 0.10),
            _row("layout", 0.12, data_format="NHWC"),
            _row("remat", 0.08, remat=True),
            _row("prefetch", 0.11, prefetch=True),
            _row("precision", 0.13, precision="bfloat16"),
            _row("compose_fast", 0.15, data_format="NHWC",
                 prefetch=True, precision="bfloat16")]
    p = bench._sweep_payload(rows)
    assert p["metric"] == "resnet50_sweep"
    assert p["errors"] == 0
    assert set(p["levers"]) == set(bench.SWEEP_LEVERS)
    # isolated deltas vs the all-off base, sign preserved (remat is a
    # memory lever — negative time delta is a finding, not an error)
    assert p["levers"]["layout"]["delta_mfu"] == 0.02
    assert p["levers"]["remat"]["delta_mfu"] == -0.02
    assert p["levers"]["remat"]["delta_pct"] == -20.0
    # best composition is the max measured row, whatever its levers
    assert p["best"]["config"] == "compose_fast"


def test_sweep_payload_counts_errors_and_survives_missing_base():
    rows = [{"config": "base", "error": "Boom"},
            _row("layout", 0.12, data_format="NHWC")]
    p = bench._sweep_payload(rows)
    assert p["errors"] == 1
    assert p["levers"] == {}          # no base -> no deltas, no crash
    assert p["best"]["config"] == "layout"


def test_persist_sweep_partial_and_no_clobber(monkeypatch, tmp_path):
    path = tmp_path / "BENCH_TPU.json"
    monkeypatch.setattr(bench, "BENCH_TPU_PATH", str(path))
    monkeypatch.setattr(bench, "_git_sha", lambda: "abc123")
    # an all-error partial grid must not write anything
    assert bench._persist_sweep([{"config": "base", "error": "x"}],
                                "v5e") is None
    assert not path.exists()
    # a timed partial grid persists incrementally
    rows = [_row("base", 0.10)]
    bench._persist_sweep(rows, "v5e")
    rows.append(_row("layout", 0.12, data_format="NHWC"))
    best = bench._persist_sweep(rows, "v5e")
    assert best["config"] == "layout"
    doc = bench._load_bench_tpu()
    saved = doc["rows"]["resnet50_sweep"]
    assert saved["device"] == "v5e" and saved["git_sha"] == "abc123"
    assert len(saved["configs"]) == 2
    assert saved["levers"]["layout"]["delta_pct"] == 20.0


def test_lever_grid_structure(monkeypatch):
    """The grid wires every lever through a REAL model/step build (only
    the timing is stubbed): 7 rows, each lever isolated exactly once,
    compositions at the end, remat rows present and non-erroring."""
    speeds = {"base": 1.0, "layout": 0.9, "remat": 1.3, "prefetch": 0.95,
              "precision": 0.85, "compose_fast": 0.7, "compose_all": 1.1}
    seen_prefetch = {}

    def fake_time(step, state, batches_fn, prefetch, reps=3):
        # the step must be a callable the real harness could jit; pull
        # the config name back out via the call order below
        name = order[len(seen_prefetch)]
        seen_prefetch[name] = prefetch
        return 0.1 * speeds[name], state

    order = ["base", "layout", "remat", "prefetch", "precision",
             "compose_fast", "compose_all"]
    monkeypatch.setattr(bench, "_time_feed_steps", fake_time)
    progressive = []
    p = bench.resnet50_lever_grid(
        1e11, False, on_result=lambda rs: progressive.append(len(rs)))
    assert [r["config"] for r in p["configs"]] == order
    assert p["errors"] == 0
    assert progressive == list(range(1, 8))   # on_result after each row
    # prefetch flag reaches the harness for exactly the prefetch rows
    assert [n for n, pf in seen_prefetch.items() if pf] == \
        ["prefetch", "compose_fast", "compose_all"]
    # isolated rows flip exactly one lever vs base
    base = p["configs"][0]
    flips = {"layout": "data_format", "remat": "remat",
             "prefetch": "prefetch", "precision": "precision"}
    for name, field in flips.items():
        row = next(r for r in p["configs"] if r["config"] == name)
        diff = [k for k in ("data_format", "remat", "prefetch",
                            "precision") if row[k] != base[k]]
        assert diff == [field], (name, diff)
    assert p["best"]["config"] == "compose_fast"


# ---------------------------------------------------------------------------
# dispatch_overhead host scoreboard (ISSUE 2 tentpole)
# ---------------------------------------------------------------------------


def test_dispatch_overhead_row_shape():
    """The scoreboard runs end-to-end on CPU and its row carries every
    field BENCH_TPU consumers read.  No timing comparisons here: wall
    numbers under suite load are noise (the cached-hit vs fast-path
    ordering is asserted structurally by
    test_dispatch_fastpath.test_cached_hit_skips_listvars_and_repruning,
    which proves the work the fast path skips)."""
    r = bench.bench_dispatch_overhead(False, 1e11, steps=15)
    assert r["metric"] == "dispatch_overhead"
    for k in ("first_trace_ms", "cached_hit_us", "fast_path_us",
              "blocking_us", "steps_ahead", "steps"):
        assert k in r, k
    assert r["first_trace_ms"] > 0
    assert r["fast_path_us"] > 0 and r["cached_hit_us"] > 0
    assert r["steps_ahead"] is None or r["steps_ahead"] >= 0


def test_dispatch_overhead_in_suite_and_standalone():
    src = open(bench.__file__).read()
    assert '("dispatch_overhead", "dispatch_overhead"' in src
    assert '"dispatch_overhead" in sys.argv[1:]' in src


# ---------------------------------------------------------------------------
# fault_tolerance_smoke chaos row (ISSUE 4 satellite)
# ---------------------------------------------------------------------------


def test_op_profile_smoke_in_suite_and_standalone():
    """The attribution smoke row is wired into the suite AND the
    standalone argv entry (the invariants themselves run end-to-end in
    tests/test_op_profile.py on the test mesh; the row re-asserts them
    on the 2-device standalone mesh in CI)."""
    src = open(bench.__file__).read()
    assert '("op_profile_smoke", "op_profile_smoke"' in src
    assert '"op_profile_smoke" in sys.argv[1:]' in src
    assert "main_op_profile_smoke" in src


def test_bench_op_profile_smoke_row_passes():
    """The CI row end-to-end on the test mesh: FLOPs sum exactly to the
    whole-program cost_analysis total, every op scoped, residual
    bounded."""
    row = bench.bench_op_profile_smoke(False, 1e11)
    assert row["value"] == 1, row.get("checks")
    # >= : framework-inserted dp-sync collectives carry their own
    # scopes on top of the ProgramDesc ops
    assert row["attributed_scopes"] >= row["program_ops"]
    assert row["unattributed_flops_pct"] <= 1.0


def test_mem_profile_smoke_in_suite_and_standalone():
    """The HBM-attribution smoke row is wired into the suite AND the
    standalone argv entry (the invariants run end-to-end in
    tests/test_mem_profile.py on the test mesh; the row re-asserts
    them on the 2-device standalone mesh in CI)."""
    src = open(bench.__file__).read()
    assert '("mem_profile_smoke", "mem_profile_smoke"' in src
    assert '"mem_profile_smoke" in sys.argv[1:]' in src
    assert "main_mem_profile_smoke" in src


def test_bench_mem_profile_smoke_row_passes():
    """The CI row end-to-end on the test mesh: per-scope peak bytes
    sum exactly to memory_analysis temp+output, residual <= 1%,
    timeline monotone, peak table non-empty."""
    row = bench.bench_mem_profile_smoke(False, 1e11)
    assert row["value"] == 1, row.get("checks")
    assert row["peak_hbm_bytes"] > 0
    assert row["unattributed_peak_pct"] <= 1.0


def test_fault_tolerance_smoke_in_suite_and_standalone():
    """The chaos row is wired into the suite AND the standalone argv
    entry (the recovery behaviors themselves are covered end-to-end by
    tests/test_resilience.py; re-running the whole row here would pay
    its compiles twice per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("fault_tolerance_smoke", "fault_tolerance_smoke"' in src
    assert '"fault_tolerance_smoke" in sys.argv[1:]' in src
    assert "main_fault_tolerance_smoke" in src


# ---------------------------------------------------------------------------
# goodput_smoke chaos row (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


def test_goodput_smoke_in_suite_and_standalone():
    """The goodput attribution row is wired into the suite AND the
    standalone argv entry (the ledger behaviors themselves are covered
    end-to-end by tests/test_goodput.py; re-running the whole row here
    would pay its compiles twice per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("goodput_smoke", "goodput_smoke"' in src
    assert '"goodput_smoke" in sys.argv[1:]' in src
    assert "main_goodput_smoke" in src


# ---------------------------------------------------------------------------
# serving_smoke chaos row (ISSUE 8 satellite)
# ---------------------------------------------------------------------------


def test_serving_smoke_in_suite_and_standalone():
    """The serving chaos row is wired into the suite AND the standalone
    argv entry (the robustness behaviors themselves are covered
    end-to-end by tests/test_serving.py; re-running the whole row here
    would pay its compiles twice per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("serving_smoke", "serving_smoke"' in src
    assert '"serving_smoke" in sys.argv[1:]' in src
    assert "main_serving_smoke" in src


# ---------------------------------------------------------------------------
# decode_serving_smoke chaos row (ISSUE 17 satellite)
# ---------------------------------------------------------------------------


def test_decode_serving_smoke_in_suite_and_standalone():
    """The continuous-batching decode chaos row is wired into the
    suite AND the standalone argv entry (the engine behaviors
    themselves are covered end-to-end by tests/test_decode_serving.py;
    re-running the whole row here would pay its compiles twice per CI
    run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("decode_serving_smoke", "decode_serving_smoke"' in src
    assert '"decode_serving_smoke" in sys.argv[1:]' in src
    assert "main_decode_serving_smoke" in src


# ---------------------------------------------------------------------------
# request_tracing_smoke chaos row (ISSUE 18 satellite)
# ---------------------------------------------------------------------------


def test_request_tracing_smoke_in_suite_and_standalone():
    """The request-tracing chaos row is wired into the suite AND the
    standalone argv entry (the tracing behaviors themselves are
    covered end-to-end by tests/test_request_tracing.py; re-running
    the whole row here would pay its compiles twice per CI run for no
    new signal)."""
    src = open(bench.__file__).read()
    assert '("request_tracing_smoke", "request_tracing_smoke"' in src
    assert '"request_tracing_smoke" in sys.argv[1:]' in src
    assert "main_request_tracing_smoke" in src


def test_request_tracing_smoke_row_shape():
    """The smoke row's check list carries every acceptance pillar of
    ISSUE 18: orphan-free span trees, exact integer-ns attribution
    (trees AND table rows), ledger reconciliation, external
    traceparent join, the injected stall landing in the stall
    component, violator exemplar retention under zero sampling, the
    SLO Prometheus families, and the tracing-off gate-free dispatch
    guard."""
    src = open(bench.__file__).read()
    for check in ("zero_silently_lost", "all_completed",
                  "trees_orphan_free", "attribution_exact_trees",
                  "attribution_exact_rows", "ledger_reconciles",
                  "external_trace_joined", "stall_attributed",
                  "violator_exemplar_retained", "slo_families_exported",
                  "trace_records_on_stream",
                  "serving_record_carries_tracing",
                  "chrome_trace_request_tracks",
                  "report_renders_tracing_section",
                  "tracing_off_gate_free"):
        assert f'"{check}"' in src, check


# ---------------------------------------------------------------------------
# numerics_lint_smoke row (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


def test_numerics_lint_smoke_in_suite_and_standalone():
    """The numerics-analyzer row is wired into the suite AND the
    standalone argv entry (the PT4xx behaviors themselves are covered
    end-to-end by tests/test_numerics.py, which also runs the row
    once; re-running the full zoo sweep here would pay the builds
    twice per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("numerics_lint_smoke", "numerics_lint_smoke"' in src
    assert '"numerics_lint_smoke" in sys.argv[1:]' in src
    assert "main_numerics_lint_smoke" in src


def test_numerics_lint_smoke_row_shape():
    """The smoke row's check list carries every acceptance pillar of
    ISSUE 15: the PT4xx-clean zoo substitutes, one seeded program per
    code, the PT406 guard flip, the seeded-PT401 runtime divergence
    conformance, and the PT403 churn-vs-structural-removal equality."""
    src = open(bench.__file__).read()
    for check in ("zoo_pt4xx_clean", "fragile_bf16_PT401",
                  "lost_master_PT402", "cast_churn_PT403",
                  "bf16_accumulation_PT404", "fp16_no_scaling_PT405",
                  "fusion_near_miss_PT406", "fetch_drift_PT407",
                  "near_miss_guard_flip_fuses",
                  "seeded_pt401_diverges_past_tolerance",
                  "lint_clean_twin_within_tolerance",
                  "churn_count_equals_structural_removal"):
        assert check in src, check


# ---------------------------------------------------------------------------
# graph_opt_sweep row (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def test_graph_opt_sweep_in_suite_and_standalone():
    """The graph-optimizer row is wired into the suite AND the
    standalone argv entry (the pass/bucketing behaviors themselves are
    covered end-to-end by tests/test_passes.py; re-running the whole
    row here would pay its compiles twice per CI run for no new
    signal)."""
    src = open(bench.__file__).read()
    assert '("graph_opt_sweep", "graph_opt_sweep"' in src
    assert '"graph_opt_sweep" in sys.argv[1:]' in src
    assert "main_graph_opt_sweep" in src


def test_graph_opt_sweep_row_shape():
    """The sweep row's check list carries both acceptance pillars: the
    bitwise bucketed sync and the >=10%-on-3-models op reduction."""
    src = open(bench.__file__).read()
    for check in ("bucketed_params_bitwise", "tiny_buckets_at_ceil_bound",
                  "opcount_10pct_on_3_models", "all_models_allclose",
                  "optimized_lint_clean", "pipeline_idempotent"):
        assert check in src, check


# ---------------------------------------------------------------------------
# fused_amp_sweep row (ISSUE 14)
# ---------------------------------------------------------------------------


def test_fused_amp_sweep_in_suite_and_standalone():
    """The fusion+AMP sweep row is wired into the suite AND the
    standalone argv entry (the matcher/AMP behaviors themselves are
    covered end-to-end by tests/test_fuse.py; re-running the 20-config
    grid here would pay its compiles twice per CI run for no new
    signal)."""
    src = open(bench.__file__).read()
    assert '("fused_amp_sweep", "fused_amp_sweep"' in src
    assert '"fused_amp_sweep" in sys.argv[1:]' in src
    assert "main_fused_amp_sweep" in src


def test_fused_amp_sweep_row_shape():
    """The sweep row's check list carries the acceptance pillars:
    per-lever isolation, all-fused-configs allclose, pattern coverage,
    AMP casts in the compiled graph, cost_analysis MFU, the <=1%
    fused attribution residual, and the TPU-armed step-time gates."""
    src = open(bench.__file__).read()
    for check in ("all_fused_configs_allclose",
                  "per_lever_deltas_isolated",
                  "fusion_step_reduction_2_models",
                  "fused_amp_step_reduction_2_models",
                  "patterns_fired_all_fusable_models",
                  "amp_casts_in_graph", "mfu_reported",
                  "fused_unattributed_residual_le_1pct"):
        assert check in src, check


# ---------------------------------------------------------------------------
# fleet_obs_smoke row (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_fleet_obs_smoke_in_suite_and_standalone():
    """The fleet-observability row is wired into the suite AND the
    standalone argv entry (the straggler/exporter behaviors themselves
    are covered by tests/test_fleet.py and the 2-process row runs
    end-to-end under `python bench.py fleet_obs_smoke`; re-running the
    cluster spawn here would pay the rendezvous twice per CI run for
    no new signal)."""
    src = open(bench.__file__).read()
    assert '("fleet_obs_smoke", "fleet_obs_smoke"' in src
    assert '"fleet_obs_smoke" in sys.argv[1:]' in src
    assert "main_fleet_obs_smoke" in src


def test_fleet_obs_smoke_row_shape():
    """The smoke row's check list carries every acceptance pillar:
    named straggler on both ranks, the ±20% injected-delay bound, the
    exact wait-fraction recomputation, the scrape==snapshot spot
    check, the rank-attributed fleet merge, and the exporter-off
    dispatch guard."""
    src = open(bench.__file__).read()
    for check in ("straggler_named_r",      # per-rank, f-string keyed
                  "behind_within_20pct", "wait_frac_recomputed_exactly",
                  "scrape_matches_snapshot", "healthz_ok",
                  "fleet_merge_names_straggler",
                  "exporter_off_no_regression"):
        assert check in src, check


# ---------------------------------------------------------------------------
# elastic_fleet_smoke row (ISSUE 11 satellite)
# ---------------------------------------------------------------------------


def test_elastic_fleet_smoke_in_suite_and_standalone():
    """The elastic chaos row is wired into the suite AND the
    standalone argv entry (the shrink/grow/policy behaviors themselves
    are covered by tests/test_elastic.py; the 5-launch kill/reshard/
    rejoin arc runs end-to-end under `python bench.py
    elastic_fleet_smoke` — re-running the cluster spawns here would
    pay five rendezvous per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("elastic_fleet_smoke", "elastic_fleet_smoke"' in src
    assert '"elastic_fleet_smoke" in sys.argv[1:]' in src
    assert "main_elastic_fleet_smoke" in src


def test_elastic_fleet_smoke_row_shape():
    """The chaos row's check list carries every acceptance pillar of
    ISSUE 11: the deterministic kill, the named rank death, the
    in-process 2→1 reshard, the healthz transition window with its
    reason body, the grow/relaunch rejoin, bitwise params + identical
    loss stream vs the clean-scheduled reference, the full elastic
    counter set, and the merged topology history."""
    src = open(bench.__file__).read()
    for check in ("kill_fired", "rank_death_named", "shrunk_at_kill",
                  "healthz_503_during_transition",
                  "healthz_ok_after_commit", "grow_relaunch",
                  "elastic_counters", "rejoin_resumed",
                  "topology_provenance", "params_bitwise_identical",
                  "loss_stream_identical", "topology_history_reported"):
        assert check in src, check


# ---------------------------------------------------------------------------
# fleet_serving_smoke row (ISSUE 19 satellite)
# ---------------------------------------------------------------------------


def test_fleet_serving_smoke_in_suite_and_standalone():
    """The fleet-serving chaos row is wired into the suite AND the
    standalone argv entry (registry/failover/hot-swap behaviors
    themselves are covered by tests/test_fleet_serving.py; the real
    2-subprocess kill/roll arc runs end-to-end under `python bench.py
    fleet_serving_smoke` — respawning the replica fleet here would pay
    two cold jax starts per CI run for no new signal)."""
    src = open(bench.__file__).read()
    assert '("fleet_serving_smoke", "fleet_serving_smoke"' in src
    assert '"fleet_serving_smoke" in sys.argv[1:]' in src
    assert "main_fleet_serving_smoke" in src


def test_fleet_serving_smoke_row_shape():
    """The row's check list carries every acceptance pillar of ISSUE
    19: the mid-request replica kill verifiably fired and the failover
    absorbed it, the dead replica is health-gated out, the version
    rolled forward and back bitwise under zero-drop traffic, the
    merged requests==sum(outcomes) identity plus per-attempt
    accounting, the AOT cold start with zero serving compiles, and the
    router-hop/replica trace join."""
    src = open(bench.__file__).read()
    for check in ("replicas_started", "failover_absorbed",
                  "kill_fired", "dead_replica_gated",
                  "roll_applied_to_live_fleet",
                  "roll_forward_back_bitwise", "zero_drop_during_roll",
                  "ledger_identity", "attempts_all_resolved",
                  "aot_cold_start_zero_compiles",
                  "trace_joined_across_hop"):
        assert check in src, check


# ---------------------------------------------------------------------------
# tp_runtime_smoke row (ISSUE 16)
# ---------------------------------------------------------------------------


def test_tp_runtime_smoke_in_suite_and_standalone():
    """The GSPMD runtime-tier row is wired into the suite AND the
    standalone argv entry (the sharded placement/conformance behaviors
    themselves run in tests/test_spmd_runtime.py; the full dp-reference
    comparison with both compiles runs end-to-end under `python
    bench.py tp_runtime_smoke` — re-paying the second bert compile
    here would double CI cost for no new signal)."""
    src = open(bench.__file__).read()
    assert '("tp_runtime_smoke", "tp_runtime_smoke"' in src
    assert '"tp_runtime_smoke" in sys.argv[1:]' in src
    assert "main_tp_runtime_smoke" in src


def test_tp_runtime_smoke_row_shape():
    """The row's check list carries every acceptance pillar of ISSUE
    16: dp-loss conformance, exact predicted==executed model
    collectives, verifiably sharded param/moment leaves, the static
    memory estimate within tolerance AND below the dp-only peak, the
    mesh-axes checkpoint provenance, and the bitwise {dp=2,mp=2} →
    {dp=4} reshard."""
    src = open(bench.__file__).read()
    for check in ("loss_allclose_vs_dp", "model_collectives_exact",
                  "param_and_moment_leaves_sharded", "mem_within_25pct",
                  "tp_peak_below_dp_peak", "topology_mesh_axes",
                  "ckpt_reshard_bitwise"):
        assert check in src, check
