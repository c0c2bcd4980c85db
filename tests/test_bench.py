"""bench.py contract smoke tests.

The driver runs `python bench.py` / `python bench.py data` at round end and
records the single JSON line; these tests pin that contract (one parseable
line, required keys, sane values) at toy sizes so a regression is caught
before the round-end artifact is produced.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*args, env_extra=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + ":" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    # bench.py is an entry point and places the compile cache; keep the
    # test's entries out of <checkout>/.jax_cache.
    with tempfile.TemporaryDirectory(prefix="bench_jax_cache_") as cache_dir:
        env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "bench.py"), *args],
            capture_output=True,
            text=True,
            env=env,
            timeout=timeout,
        )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.slow
def test_bench_mfu_contract():
    """The headline MFU path, on the CPU-proxy branch (reduced tower)."""
    payload = _run_bench()
    assert payload["metric"] == "qtopt_critic_train_mfu_cpu_proxy"
    assert payload["unit"] == "fraction_of_peak"
    assert 0 < payload["value"] <= 1.0
    assert "error" not in payload
    # CPU-proxy payloads must self-describe: the
    # top-level proxy flag and the vs_baseline disclaimer, not just a
    # detail-channel backend note.
    assert payload["proxy"] is True
    assert "vs_baseline_note" in payload
    # The proxy self-description includes the on-chip pointer: this repo
    # carries committed TPU headline artifacts, so it must resolve.
    assert payload["last_onchip"] is not None
    assert payload["last_onchip"]["metric"].startswith("qtopt_critic_train_mfu")
    detail = payload["detail"]
    assert detail["steps_per_sec"] > 0
    assert detail["per_step_dispatch_avg_steps_per_sec"] > 0
    assert detail["flops_per_step"] > 0
    assert detail["timing"] == "median_of_windows_best_regime"
    assert detail["per_step_dispatch_best_steps_per_sec"] >= (
        detail["per_step_dispatch_steps_per_sec"]
    )
    assert detail["bf16_forward"] is True
    assert detail["tower_width"] == 64
    # Round-5 provenance field: which stem lowering this process traced
    # with (the on-chip A/B leg keys off it).
    assert isinstance(detail["stem_s2d"], bool)
    # The clamped overlap headline can never exceed 1.0; the raw ratio
    # rides alongside whenever the infeed leg ran.
    assert detail["infeed_overlap_efficiency"] <= 1.0
    if detail["infeed_steps_per_sec"] > 0:
        assert "infeed_overlap_efficiency_raw" in detail
        if detail["infeed_overlap_efficiency_raw"] > 1.0:
            assert "infeed_overlap_note" in detail


def test_overlap_fields_clamp():
    """Unit-pins _overlap_fields: impossible >1.0 ratios are clamped and
    annotated; the raw value is preserved."""
    sys.path.insert(0, REPO_ROOT)
    import bench

    noisy = bench._overlap_fields(10.431, 10.0)
    assert noisy["infeed_overlap_efficiency"] == 1.0
    assert noisy["infeed_overlap_efficiency_raw"] == 1.0431
    assert "infeed_overlap_note" in noisy
    clean = bench._overlap_fields(9.8, 10.0)
    assert clean["infeed_overlap_efficiency"] == 0.98
    assert "infeed_overlap_note" not in clean
    assert bench._overlap_fields(1.0, 0.0) == {
        "infeed_overlap_efficiency": 0.0
    }


def test_last_onchip_pointer():
    """Unit-pins _last_onchip: the pointer finds the
    newest committed real-hardware artifact of a metric family, skips
    proxies/failures, and degrades to None for unknown families."""
    sys.path.insert(0, REPO_ROOT)
    import bench

    pointer = bench._last_onchip("qtopt_critic_train_mfu")
    assert pointer is not None
    assert pointer["metric"].startswith("qtopt_critic_train_mfu")
    assert "cpu_proxy" not in pointer["metric"]
    assert pointer["artifact"].endswith(".json")
    # Strict UTC ISO-8601 Zulu (sortable, timezone-unambiguous).
    assert pointer["utc"].endswith("Z") and "T" in pointer["utc"]
    assert bench._last_onchip("metric_family_that_never_existed") is None


def test_analytic_flops_width_scaling():
    """The width knob reaches the analytic FLOPs model: the c128 twin's
    conv tower must cost ~4x the reference 64-wide tower (c_in*c_out)."""
    sys.path.insert(0, REPO_ROOT)
    import bench

    base = bench._analytic_train_flops((472, 472), 64)
    wide = bench._analytic_train_flops((472, 472), 64, width=128)
    assert 3.5 < wide / base < 4.1


@pytest.mark.slow
def test_bench_data_contract():
    """bench.py data on the (default) fast path at toy sizes: one JSON
    line, the three-leg breakdown (fast+cache headline, cold fast,
    SpecParser oracle), and sane values."""
    payload = _run_bench(
        "data",
        env_extra={
            "BENCH_DATA_RECORDS": "8",
            "BENCH_DATA_BATCH": "4",
            "BENCH_DATA_BATCHES": "2",
        },
    )
    assert payload["metric"] == "qtopt_input_pipeline_images_per_sec"
    assert payload["unit"] == "images_per_sec"
    assert payload["value"] > 0
    detail = payload["detail"]
    assert detail["records_per_sec"] > 0
    assert detail["batch_size"] == 4
    assert detail["parse_workers"] >= 1
    # Fast-path provenance: which parser produced the headline and what
    # each mechanism contributed (ISSUE 1 tentpole).
    assert detail["parse_fast"] is True
    assert detail["fast_no_cache_images_per_sec"] > 0
    assert detail["specparser_images_per_sec"] > 0
    assert detail["fast_vs_specparser"] > 0
    if detail["decode_cache_mb"] > 0 and detail["decode_cache"] is not None:
        cache = detail["decode_cache"]
        assert cache["hits"] + cache["misses"] > 0
        assert 0.0 <= cache["hit_rate"] <= 1.0
    # ISSUE 2 tentpole provenance: decode-ROI config, the ROI-off cold
    # attribution twin, the content mode + its r06-continuity legs, and
    # the first measured parse_workers sweep.
    assert detail["content"] == "camera"
    assert detail["decode_roi"] in (True, False)
    assert detail["roi"]["crop"] == [472, 472]
    assert detail["roi"]["source"] == [512, 640]
    assert detail["roi"]["mode"] == "random"
    assert detail["cold_noroi_images_per_sec"] > 0
    assert detail["roi_cold_speedup"] > 0
    assert set(detail["worker_sweep"].keys()) == {"1", "2"}
    for legs in detail["worker_sweep"].values():
        assert legs["cold_images_per_sec"] > 0
        assert legs["fast_images_per_sec"] > 0
        assert legs["specparser_images_per_sec"] > 0
    assert detail["noise_content"]["cold_images_per_sec"] > 0
    assert detail["noise_content"]["cold_noroi_images_per_sec"] > 0


@pytest.mark.slow
def test_bench_data_slow_path_still_runs():
    """T2R_PARSE_FAST=0 must keep the bench (and pipeline) functional —
    the oracle path is the fallback story."""
    payload = _run_bench(
        "data",
        env_extra={
            "BENCH_DATA_RECORDS": "8",
            "BENCH_DATA_BATCH": "4",
            "BENCH_DATA_BATCHES": "2",
            "T2R_PARSE_FAST": "0",
        },
    )
    assert payload["value"] > 0
    assert "error" not in payload


@pytest.mark.slow
def test_bench_auc_contract():
    """The bf16-accuracy-budget leg at toy step counts: pins the JSON
    contract and the tie-safe AUC (values must be genuine fractions, not
    the degenerate 0/1 an untie-corrected rank sum produces on constant
    predictors)."""
    payload = _run_bench(
        "auc",
        env_extra={
            "BENCH_AUC_STEPS": "4",
            "BENCH_AUC_BATCH": "8",
        },
    )
    # On the CPU backend the metric self-describes as a proxy (the real
    # bf16-MXU budget check runs on TPU under the plain name).
    assert payload["metric"] == "qtopt_bf16_eval_auc_delta_cpu_proxy"
    assert payload["proxy"] is True
    assert payload["unit"] == "auc_delta"
    assert 0.0 <= payload["value"] <= 1.0
    assert "error" not in payload
    # Budget-delta metrics name their ratio honestly:
    # fraction_of_budget == vs_baseline == value / budget, budget explicit.
    assert payload["budget"] == 0.02
    assert payload["fraction_of_budget"] == payload["vs_baseline"]
    assert payload["fraction_of_budget"] == pytest.approx(
        payload["value"] / 0.02, abs=1e-3
    )
    # Proxy payloads point at the newest on-chip artifact of the family
    # — present even when None.
    assert "last_onchip" in payload
    detail = payload["detail"]
    assert detail["backend"] == "cpu"
    assert detail["f32_leg_precision"] == "true_f32"
    assert 0.0 <= detail["auc_f32"] <= 1.0
    assert 0.0 <= detail["auc_bf16"] <= 1.0
    assert detail["train_steps"] == 4
    assert detail["auc_method"] == "mann_whitney_rank"


@pytest.mark.slow
def test_bench_predict_contract():
    payload = _run_bench(
        "predict",
        env_extra={"BENCH_PREDICT_SAMPLES": "8"},
    )
    assert payload["metric"] == "qtopt_cem_predict_hz_cpu_proxy"
    assert payload["unit"] == "predict_calls_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    assert payload["detail"]["cem_samples_per_call"] == 8
    assert payload["detail"]["interface"] == "stablehlo_exported_model"
    assert payload["proxy"] is True
    # The jit-native CEM leg really ran (one fused program per selection).
    assert payload["detail"]["jit_cem_action_selects_per_sec"] > 0


@pytest.mark.slow
def test_bench_pipe_contract():
    """The end-to-end host-pipeline->device-step composite on the proxy
    branch: real tfrecord write -> generator -> parse -> prefetch ->
    train step, ratio against the resident-batch rate."""
    payload = _run_bench(
        "pipe",
        env_extra={"BENCH_PIPE_RECORDS": "8"},
    )
    assert payload["metric"] == "qtopt_e2e_pipeline_steps_per_sec_cpu_proxy"
    assert payload["unit"] == "steps_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    assert payload["proxy"] is True
    detail = payload["detail"]
    assert detail["resident_batch_steps_per_sec"] > 0
    assert 0 < detail["e2e_fraction_of_compute_rate"]
    assert detail["records_in_file"] == 8
    assert detail["parse_workers"] >= 1


def test_bench_cli_lists_legs():
    """bench.py --help must list every leg; serve --help its options
    (the argparse-subcommand contract that replaced the argv chain)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"), "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for leg in (
        "data", "auc", "predict", "bc", "stream", "pipe", "serve", "comms",
        "fleet", "rl", "aot", "plan", "policies", "fabric", "wire",
    ):
        assert leg in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "fabric", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in (
        "--replicas-per-zone", "--trace-secs", "--deadline-ms",
        "--hedge-ms", "--gold-rps", "--crowd-factor", "--out",
    ):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "policies", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in (
        "--variants", "--replicas", "--trace-secs", "--mem-budget-mb",
        "--policy-mem-mb", "--out",
    ):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "rl", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in (
        "--actors", "--replicas", "--steps", "--seal-episodes",
        "--shards", "--chaos-at-s", "--out",
    ):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "serve", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in ("--buckets", "--burst", "--deadline-ms", "--out"):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "wire", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in (
        "--frames", "--trials", "--warmup", "--image-hw", "--state-dim",
        "--speedup-min", "--quant", "--pipeline-requests", "--out",
    ):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "comms", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in ("--block", "--steps", "--repeats", "--out"):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "aot", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in ("--buckets", "--leg-secs", "--swap-rate-hz", "--out"):
        assert option in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "plan", "--help"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    for option in ("--steps", "--steps-3d", "--block", "--out"):
        assert option in proc.stdout
    # Unknown legs are an argparse error now, not a silent fallthrough
    # into the headline benchmark.
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"), "bogus"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0


def test_bench_wire_contract(tmp_path):
    """The zero-copy wire codec leg at toy scale, tier-1: one JSON
    line + the --out artifact, every acceptance gate green (bitwise
    replies across codecs, quant parity, zero steady-state receive
    allocs, all corruption variants typed-rejected, pipelining
    overlap), and the observability surface present. The reduced image
    gets a reduced speedup floor — the full camera-sized >= 3x gate is
    the round-end `bench.py wire` run."""
    out = tmp_path / "BENCH_WIRE_smoke.json"
    payload = _run_bench(
        "wire", "--frames", "30", "--trials", "3", "--warmup", "8",
        "--image-hw", "224", "--state-dim", "1024",
        "--pipeline-requests", "12", "--speedup-min", "1.2",
        "--out", str(out),
    )
    assert payload["metric"] == "wire_codec_spec_vs_pickle_reqs_per_sec"
    assert "error" not in payload
    assert all(payload["gates"].values()), payload
    assert payload["ok"] is True
    assert payload["value"] >= 1.2
    assert payload["cpu_proxy"] is True
    detail = payload["detail"]
    assert detail["spec_reqs_per_sec"] > detail["pickle_reqs_per_sec"] > 0
    assert detail["quant_leg"]["rel_linf"] <= detail["quant_leg"][
        "parity_gate"
    ]
    audit = detail["pool_audit"]
    assert (
        audit["after_steady_window"]["allocs"]
        == audit["before_steady_window"]["allocs"]
    )
    variants = detail["corruption_variants"]
    assert variants["typed_rejected"] == variants["total"] > 0
    # Per-stage timings + per-segment-class byte counters surfaced.
    stats = detail["wire_stats"]
    for stage in ("serialize_ms", "crc_ms", "send_ms", "recv_ms",
                  "deserialize_ms"):
        assert stage in stats["timings_ms"]
    for counter in ("frames_spec_tx", "frames_pickle_tx", "bytes_raw",
                    "bytes_skeleton", "bytes_quant", "bytes_pickle"):
        assert counter in stats["counters"]
    assert json.loads(out.read_text())["gates"] == payload["gates"]


@pytest.mark.slow
def test_bench_rl_contract(tmp_path):
    """The closed online-RL loop leg at toy scale: one JSON line + the
    --out artifact, all four legs (fault-free + chaos, sharded
    fault-free + sharded chaos) present, the chaos acceptance block
    all-green (equal learner steps, zero torn segments sampled, bounded
    counted loss, real respawn + actor kill; sharded: zero duplicate
    appends, per-shard loss bounded, coverage loss counted), and the
    headline rates positive. Slow slice: it spawns a replay service,
    shard services, actor processes and a policy-server replica; tier-1
    covers the same loops in-process (tests/test_rl_loop.py,
    tests/test_replay_shard.py) and the CLI surface above."""
    out = str(tmp_path / "rl.json")
    payload = _run_bench(
        "rl", "--steps", "6", "--actors", "2", "--replicas", "1",
        "--shards", "3", "--chaos-at-s", "2.0", "--out", out,
        timeout=560,
    )
    assert payload["metric"] == "rl_loop_episodes_per_sec_cpu_proxy"
    assert payload["unit"] == "episodes_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    assert payload["proxy"] is True
    detail = payload["detail"]
    for leg in ("fault_free", "chaos", "sharded_fault_free",
                "sharded_chaos"):
        assert detail[leg]["learner_steps"] == 6
        assert detail[leg]["episodes_appended"] > 0
        assert detail[leg]["samples_drawn"] > 0
        assert detail[leg]["torn_segments_sampled"] == []
    acceptance = detail["acceptance"]
    assert acceptance["learner_steps_equal"] is True
    assert acceptance["zero_torn_segments_sampled"] is True
    assert acceptance["loss_bounded_to_unsealed_tail"] is True
    assert acceptance["replay_service_respawned"] is True
    assert acceptance["actor_killed"] is True
    assert acceptance["sharded_learner_steps_equal"] is True
    assert acceptance["sharded_zero_duplicate_appends"] is True
    assert acceptance["sharded_per_shard_loss_bounded"] is True
    assert acceptance["sharded_shard_respawned"] is True
    assert acceptance["sharded_coverage_loss_counted"] is True
    assert detail["chaos"]["chaos"]["replay_pid"] is not None
    assert detail["sharded_chaos"]["chaos"]["shard_pid"] is not None
    assert detail["sharded_chaos"]["uid_audit"]["episodes"] > 0
    assert detail["replay_ratio"] > 0
    with open(out) as f:
        assert json.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_policies_contract(tmp_path):
    """The multi-policy fleet leg at toy scale: one JSON line + the
    --out artifact, the content-addressed store's delta ratio clearing
    the 5x gate, every acceptance gate green (bitwise-vs-twin, zero
    cross-policy coalesce joins, eviction churn actually exercised,
    per-policy rolling swap with zero blip on other policies, zero
    lost). Slow slice: it publishes dozens of policy exports and spawns
    a 4-replica mock fleet; tier-1 covers the store and policy-server
    contracts in-process (tests/test_artifact_store.py,
    tests/test_policy_fleet.py) and the CLI surface above."""
    out = str(tmp_path / "policies.json")
    payload = _run_bench(
        "policies", "--variants", "40", "--trace-secs", "4",
        "--rate", "90", "--mem-budget-mb", "8", "--out", out,
        timeout=560,
    )
    assert payload["metric"] == "multi_policy_fleet_delta_store_cpu_proxy"
    assert payload["unit"] == "dense_over_store_bytes"
    assert payload["value"] >= 5.0
    assert "error" not in payload
    assert payload["cpu_proxy"] is True
    assert payload["all_green"] is True, payload["gates"]
    for gate in (
        "variants_ge_target", "delta_store_ge_5x",
        "per_policy_bitwise_vs_twin", "zero_cross_policy_joins",
        "coalesce_still_effective", "eviction_churn_counted",
        "swap_zero_blip_other_policies", "zero_lost",
    ):
        assert payload["gates"][gate] is True, gate
    detail = payload["detail"]
    assert detail["store"]["n_delta_policies"] == 40
    assert detail["store"]["delta_ratio"] >= 5.0
    assert detail["evictions"] >= 1
    assert detail["cold_loads"] >= 1
    assert detail["coalesced"] > 0
    assert detail["cross_policy_joins"] == 0
    assert detail["bitwise_mismatches"] == 0
    assert detail["lost"] == 0
    assert detail["swap_result"]["failed"] is None
    with open(out) as f:
        assert json.load(f)["metric"] == payload["metric"]


def test_aot_boot_env_scrubs_every_serving_flag(monkeypatch):
    """The aot leg's child boots must see ONLY the settings the twin
    under measurement sets: a leaked ambient bucket ladder / quant regime
    / cache dir would change what the twins boot and fail the acceptance
    gates (or worse, silently measure the wrong tier)."""
    sys.path.insert(0, REPO_ROOT)
    import bench

    for key, value in {
        "T2R_SERVE_AOT": "0",
        "T2R_AOT_REQUIRE": "1",
        "JAX_COMPILATION_CACHE_DIR": "/tmp/leak",
        "T2R_SERVE_BUCKETS": "1,2",
        "T2R_SERVE_QUANT": "int8",
    }.items():
        monkeypatch.setenv(key, value)
    env = bench._aot_scrubbed_env(True, "cpu")
    for key in (
        "T2R_AOT_REQUIRE", "JAX_COMPILATION_CACHE_DIR",
        "T2R_SERVE_BUCKETS", "T2R_SERVE_QUANT",
    ):
        assert key not in env, key
    assert env["T2R_SERVE_AOT"] == "1"
    assert env["JAX_PLATFORMS"] == "cpu"  # pinned to the parent backend
    cached = bench._aot_scrubbed_env(False, "cpu", cache_dir="/tmp/tier")
    assert cached["T2R_SERVE_AOT"] == "0"
    # The cache twin's directory travels the way jax itself reads it.
    assert cached["JAX_COMPILATION_CACHE_DIR"] == "/tmp/tier"
    assert cached["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_devices_gate_and_peak_table(monkeypatch):
    """No leg picks a platform on its own: a non-tpu platform without the
    explicit JAX_PLATFORMS=cpu request is a reported failure, and a
    device kind missing from the peaks table is an error off the CPU."""
    sys.path.insert(0, REPO_ROOT)
    import types

    import bench

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit):
        bench._devices("gate_probe")
    with pytest.raises(SystemExit):
        bench._require_cpu_request("gate_probe", "this leg")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench._require_cpu_request("gate_probe", "this leg")
    devices = bench._devices("gate_probe", compile_cache=False)
    assert devices[0].platform == "cpu"
    assert bench._peak_flops(devices[0]) == bench._CPU_PROXY_PEAK_FLOPS
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench._peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(ValueError, match="TPU v9"):
        bench._peak_flops(unknown)
    with pytest.raises(SystemExit):
        bench._refuse_children_on_chip([v5e], "gate_probe", "boot twins")
    bench._refuse_children_on_chip(devices, "gate_probe", "boot twins")


@pytest.mark.slow
def test_bench_aot_contract(tmp_path):
    """The instant-deploy leg at toy scale: one JSON line + the --out
    artifact, all three boot twins present, the acceptance block
    all-green — in particular zero fresh bucket compiles on the AOT
    boot (prewarm_source all "aot", fresh_trace_calls == 0) and the AOT
    cold start strictly below the fresh-compile twin's. Slow slice: it
    spawns four cold-boot subprocesses; tier-1 covers the restore
    ladder in-process (tests/test_aot.py) and the CLI surface above."""
    out = str(tmp_path / "aot.json")
    payload = _run_bench(
        "aot", "--buckets", "1,2,4", "--leg-secs", "2.0", "--out", out,
        timeout=560,
    )
    assert payload["metric"] == "serve_cold_start_aot_speedup_cpu_proxy"
    assert payload["unit"] == "x_cold_start_speedup"
    assert payload["value"] > 1.0  # strictly below fresh = speedup > 1
    assert "error" not in payload
    assert payload["proxy"] is True
    detail = payload["detail"]
    for mode in ("fresh", "cache_first", "cache", "aot"):
        assert detail["boots"][mode]["cold_start_s"] > 0
    aot_boot = detail["boots"]["aot"]
    assert aot_boot["fresh_trace_calls"] == 0
    assert aot_boot["aot_misses"] == 0
    assert set(aot_boot["prewarm_source"].values()) == {"aot"}
    assert aot_boot["aot_hits"] == 3
    # The fresh twin really compiled (its sources are the compile tier).
    assert set(detail["boots"]["fresh"]["prewarm_source"].values()) == {
        "compile"
    }
    assert set(detail["boots"]["cache"]["prewarm_source"].values()) == {
        "cache"
    }
    assert detail["boots"]["cache"]["cache_entries_added"] == 0
    assert detail["boots"]["cache_first"]["cache_entries_added"] > 0
    for tier in ("aot", "compile"):
        swap = detail["rolling_swap"][tier]
        assert swap["failed_requests"] == 0
        assert swap["version_after"] > swap["version_before"]
        assert swap["swap_latency_s"] > 0
    acceptance = detail["acceptance"]
    assert all(acceptance.values()), acceptance
    with open(out) as f:
        assert json.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_serve_contract(tmp_path):
    """The fleet-serving leg at toy scale: one JSON line + the --out
    artifact, with the structural fields the round-end driver and
    PERFORMANCE.md rely on."""
    out = str(tmp_path / "serve.json")
    payload = _run_bench(
        "serve",
        "--burst", "128",
        "--baseline-secs", "0.9",
        "--leg-secs", "1.5",
        "--out", out,
        timeout=420,
    )
    assert payload["metric"] == "policy_serve_throughput_cpu_proxy"
    assert payload["unit"] == "requests_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    assert payload["proxy"] is True
    detail = payload["detail"]
    assert detail["sequential_baseline_hz"] > 0
    assert detail["saturated_hz"] > 0
    assert detail["batched_speedup"] > 0
    # The timed bursts run on a dedicated server (no warm-in batches in
    # the snapshot); fill is ~1.0 at saturation but the first dispatch
    # window of a burst can close partially on a loaded host.
    assert detail["saturation_batch_fill"] >= 0.9
    # Served batch sizes are warmup buckets only.
    buckets = set(detail["buckets"])
    assert set(
        int(k) for k in detail["saturation_batches_by_bucket"]
    ) <= buckets
    for leg in detail["open_loop"].values():
        assert leg["offered_hz"] > 0
        assert "deadline_missed" in leg and "p99_ms" in leg
    swap = detail["hot_swap"]
    assert swap["swap_observed"] is True
    assert swap["version_after"] > swap["version_before"]
    # Round-11 quant legs (regime set widened in r16): every regime
    # served, bytes-of-param reduction reported against the bar, req/s
    # attributed honestly.
    quant = detail["quant"]
    assert set(quant["regimes"]) == {
        "none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"
    }
    for regime, leg in quant["regimes"].items():
        assert leg["saturated_hz"] > 0, (regime, leg)
        assert leg["params_bytes"] > 0
    assert quant["int8_params_bytes_reduction_x"] >= 3.5
    assert quant["regimes"]["fp16"]["params_bytes_reduction_x"] >= 1.8
    for regime in ("fp16", "int8"):
        parity = quant["regimes"][regime]["parity_recorded"]
        assert parity["max_divergence"]["a_predicted"] <= parity["tolerance"]
    assert "req_s_attribution" in quant
    # Round-18 acceptance: the dequant twin shows zero low-precision
    # contractions, the static-calib artifact shows zero activation-
    # quant reduces, and its AOT cold boot serves bitwise with zero
    # fresh compiles.
    assert quant["native_audit_pass"] is True
    assert quant["native_ab"]["audit_delta_proves_lowering"] is True
    assert quant["calib_ab"]["static_zero_reduce_pass"] is True
    assert quant["calib_ab"]["dynamic_reduces_match_native_layers"] is True
    assert quant["static_aot_boot"]["bitwise_vs_fresh"] is True
    assert quant["static_aot_boot"]["zero_fresh_compiles"] is True
    assert quant["r18_all_green"] is True
    import json as json_mod

    with open(out) as f:
        assert json_mod.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_fleet_contract(tmp_path):
    """The replica-fleet routing leg at toy scale: one JSON line + the
    --out artifact, with the acceptance-criteria fields — sweep legs
    carrying p50/p99/p999 + availability, a SIGKILL chaos leg with ZERO
    lost requests and bounded p99 degradation, and a rolling fleet-wide
    hot-swap with zero failed requests."""
    out = str(tmp_path / "fleet.json")
    payload = _run_bench(
        "fleet",
        "--replicas", "3",
        "--capacity-secs", "0.8",
        "--leg-secs", "1.2",
        "--quant-replicas", "2",
        "--quant-secs", "1.0",
        "--out", out,
        timeout=540,
    )
    assert payload["metric"] == "fleet_router_capacity_cpu_proxy"
    assert payload["unit"] == "requests_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    detail = payload["detail"]
    assert detail["replicas"] == 3
    assert len(detail["open_loop"]) == 3
    for leg in detail["open_loop"]:
        for key in ("p50_ms", "p99_ms", "p999_ms", "availability"):
            assert key in leg, leg
        # The zero-lost guarantee: every future resolved (ok or typed).
        assert leg["lost"] == 0, leg
    chaos = detail["chaos"]
    assert chaos["sigkill_leg"]["killed_pid"]
    assert chaos["zero_lost"] is True
    assert chaos["sigkill_leg"]["lost"] == 0
    assert chaos["fault_free_leg"]["lost"] == 0
    assert chaos["p99_degradation_x"] <= chaos["p99_degradation_max"]
    # The kill was real AND the fleet recovered from it.
    assert chaos["counters"]["replica_deaths"] >= 1
    assert chaos["counters"]["respawns"] >= 1
    # Round-11 mixed-precision policy-backend leg: real PolicyServer
    # replicas, replica 0 fp32 / replica 1 int8, regimes verified off
    # the router's health snapshots.
    quant = detail["quant"]
    assert quant["mixed_fleet_verified"] is True
    assert quant["replica_serve_quant"] == ["none", "int8"]
    assert quant["closed_loop_capacity_hz"] > 0
    assert quant["int8_params_bytes_reduction_x"] >= 3.5
    swap = detail["rolling_swap"]
    assert swap["failed_requests"] == 0
    assert swap["lost"] == 0
    assert swap["swap_result"]["failed"] is None
    assert all(
        after > before
        for before, after in zip(
            swap["version_before"], swap["version_after"]
        )
    )
    import json as json_mod

    with open(out) as f:
        assert json_mod.load(f)["metric"] == payload["metric"]


# ~13s on 1 cpu: slow slice with the other bench leg contracts;
# BENCH_GATE_r14.json is the committed audit of the same surface.
@pytest.mark.slow
def test_bench_fabric_contract(tmp_path):
    """The cross-host fabric leg at toy scale (one replica per zone,
    short trace): one JSON line + the --out artifact, socket replicas
    in separate process groups, the partition twin holding gold
    availability at the fault-free bar with zero lost requests (all
    shed typed, per-zone ledgers), post-heal re-resolution, the
    ZoneRouter absorbing the partition, typed per-host AOT rows, and
    the local-transport byte-compat pin."""
    out = str(tmp_path / "fabric.json")
    payload = _run_bench(
        "fabric",
        "--replicas-per-zone", "1",
        "--trace-secs", "5",
        "--out", out,
        timeout=540,
    )
    assert payload["metric"] == "fabric_cross_host_partition_slo_cpu_proxy"
    assert payload["unit"] == "gold_availability_under_zone_partition"
    assert "error" not in payload
    assert payload["cpu_proxy"] is True
    assert payload["ok"] is True, payload["gates"]
    assert all(payload["gates"].values()), payload["gates"]
    detail = payload["detail"]
    # The fleet really spanned separate process groups (no replica in
    # the bench's own group, >= 2 distinct groups).
    assert len(detail["process_groups"]) >= 2
    assert os.getpid() not in detail["process_groups"]
    # Zero lost on BOTH twins; the partition twin's gold bar held.
    for leg_name in ("fault_free_leg", "partition_leg"):
        leg = detail[leg_name]
        assert leg["lost"] == 0, leg_name
        assert set(leg["zone_ledgers"]) == {"z0", "z1"}
    assert (
        detail["partition_leg"]["gold_availability"]
        >= detail["fault_free_leg"]["gold_availability"]
    )
    # The healed zone came back with RESPAWNED pids (re-resolved by
    # published address, not by a stale handle).
    assert detail["z1_pids_after_heal"]
    assert not set(detail["z1_pids_after_heal"]) & set(
        detail["zones"]["z1"]["pids"]
    )
    # Cross-zone survival, typed: the zone-router leg lost nothing.
    assert detail["zone_router_leg"]["lost"] == 0
    assert detail["zone_router_leg"]["z0_wins_during_partition"] >= 16
    # Per-host AOT keys: matching host all-aot, transplanted topology
    # typed (never a silent mismatch load).
    het = detail["heterogeneity"]
    assert het["matching_all_aot"] is True
    assert het["transplanted_host"]["topology"] == 2
    assert het["replies_bitwise_identical"] is True
    with open(out) as f:
        assert json.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_gateway_contract(tmp_path):
    """The multi-tenant front-door leg at toy scale: one JSON line + the
    --out artifact, per-tenant accounting with ZERO lost requests on
    every tier, the rogue bronze tenant 100% typed at its quota, the
    coalescing win with bitwise-equal responses, the SIGKILL + rolling
    swap surviving, and the autoscaler scale-up/drain-back cycle. The
    p99-degradation bar is relaxed for CPU-proxy host variance (the
    committed BENCH_GATE artifact runs the strict default)."""
    out = str(tmp_path / "gate.json")
    payload = _run_bench(
        "gateway",
        "--trace-secs", "6",
        "--drain-secs", "4",
        "--rate-scale", "0.6",
        "--max-replicas", "4",
        "--p99-degradation-max", "10",
        "--out", out,
        timeout=540,
    )
    assert payload["metric"] == "gateway_multitenant_slo_cpu_proxy"
    assert payload["unit"] == "requests_per_sec"
    assert payload["value"] > 0
    assert "error" not in payload
    assert payload["cpu_proxy"] is True
    gates = payload["gates"]
    assert payload["all_green"] is True, gates
    detail = payload["detail"]
    for leg_name in ("fault_free", "chaos"):
        leg = detail[leg_name]
        # Per-request accounting: every submission resolved, ok or typed.
        assert leg["lost_total"] == 0, leg_name
        for tenant, stats in leg["per_tenant"].items():
            assert stats["lost"] == 0, (leg_name, tenant)
    chaos_leg = detail["chaos"]
    # Gold held availability 1.0 through kill + swap + crowd.
    assert chaos_leg["per_tenant"]["web-gold"]["availability"] == 1.0
    # The rogue bronze tenant was quota-bound, 100% typed.
    rogue = chaos_leg["per_tenant"]["rogue-bronze"]
    assert rogue["shed_at_admission"].get("TenantThrottled", 0) > 0
    assert rogue["availability"] < 0.5
    # Coalescing measurably cut dispatches, bitwise-equal responses.
    assert chaos_leg["per_tenant"]["app-silver-hot"]["coalesced"] > 0
    assert chaos_leg["gateway_counters"]["coalesced_joins"] > 0
    assert all(
        len(v) == 1 for v in chaos_leg["hot_y_groups"].values()
    )
    # The kill was real, the fleet recovered, the swap published.
    assert chaos_leg["killed_pid"]
    assert chaos_leg["router_counters"]["replica_deaths"] >= 1
    assert chaos_leg["router_counters"]["respawns"] >= 1
    assert chaos_leg["swap_result"]["failed"] is None
    assert max(chaos_leg["versions_observed"]) >= 2
    # The autoscaler reached the ceiling during the crowd and drained
    # back without a single aborted retirement.
    assert chaos_leg["autoscaler"]["peak_replicas_up"] >= 4
    assert chaos_leg["autoscaler"]["counters"].get("scale_down", 0) >= 1
    assert chaos_leg["router_counters"].get("retirement_aborts", 0) == 0
    import json as json_mod

    with open(out) as f:
        assert json_mod.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_plan_contract(tmp_path):
    """The sharding-planner leg at toy step counts: one JSON line + the
    --out artifact, every preset byte-equal with a clean audit, the DP
    family bitwise planner-vs-hand, and the 3D (2x2x2) leg green with
    per-axis wire-byte attribution and the ranked plan table."""
    out = str(tmp_path / "plan.json")
    payload = _run_bench(
        "plan", "--steps", "2", "--steps-3d", "3", "--out", out,
        timeout=700,
    )
    assert payload["metric"] == "plan_preset_byte_equality"
    assert payload["value"] == 1.0
    assert "error" not in payload
    assert all(payload["gates"].values()), payload["gates"]
    audit = payload["detail"]["byte_audit"]
    for preset in (
        "dp", "dp_zero2", "dp_zero2_int8", "dp_zero2_fp8_e4m3",
        "dp_zero2_fp8_e5m2", "dp_sp", "dp_pp", "dp_pp_zero2",
    ):
        assert audit[preset]["layouts_equal"] is True, preset
        assert audit[preset]["audit_mismatches"] == 0, preset
    for preset in ("dp", "dp_zero2", "dp_zero2_int8"):
        assert audit[preset]["params_bitwise_equal"] is True
        assert audit[preset]["loss_abs_diff"] == 0.0
    plan3d = payload["detail"]["plan3d"]
    assert plan3d["preset"]["weight_update_axes"] == ["data", "sequence"]
    assert plan3d["loss_parity_max_abs_diff"] < 1e-3
    axes = {a for e in plan3d["wire_byte_attribution"] for a in e["axes"]}
    assert {"data", "sequence", "pipe"} <= axes
    table = payload["detail"]["ranked_plan_table"]["table"]
    assert len(table) >= 4
    assert any(
        e["plan"]["name"] == "dp2_sp2_pp2" and e["feasible"]
        for e in table
    )
    # Round 19: the widened points pass their parity twins and rank in
    # the widened table.
    widened = payload["detail"]["widened"]
    assert widened["tp"]["loss_parity_max_abs_diff"] < 1e-3
    assert widened["ulysses_in_pipe"]["loss_parity_max_abs_diff"] < 1e-3
    widened_table = widened["ranked_plan_table"]["table"]
    feasible = {
        e["plan"]["name"] for e in widened_table if e["feasible"]
    }
    assert {"dp4_sp1_pp1_tp2", "dp1_sp4_pp2"} <= feasible
    # Round 19: the measured search stores its winner; the warm run
    # replays it byte-for-byte with zero search compiles.
    measured = payload["detail"]["measured_search"]
    assert measured["cold_stats"]["source"] == "measured"
    assert measured["cold_stats"]["probe_compiles"] >= 1
    assert measured["warm_stats"]["source"] == "cache"
    assert measured["warm_stats"]["probe_compiles"] == 0
    assert measured["winner_step_time_ms"] > 0
    assert 0.0 <= measured["analytic_vs_measured_rank_agreement"] <= 1.0
    with open(out) as f:
        assert json.load(f)["metric"] == payload["metric"]


@pytest.mark.slow
def test_bench_comms_contract(tmp_path):
    """The quantized-collective leg at toy step counts: one JSON line +
    the --out artifact, the >=3.5x int8 bytes-reduction bar, loss parity
    within tolerance, and the none-path byte-identity bit."""
    out = str(tmp_path / "comms.json")
    payload = _run_bench(
        "comms", "--steps", "6", "--repeats", "2", "--out", out,
        timeout=560,
    )
    assert payload["metric"] == "zero2_collective_bytes_reduction"
    assert payload["unit"] == "x_fewer_wire_bytes"
    assert payload["value"] >= 3.5
    assert payload["vs_baseline"] >= 1.0
    assert payload["proxy"] is True
    assert payload["parity_ok"] is True
    assert payload["none_byte_identical"] is True
    legs = payload["detail"]["legs"]
    for name in ("none", "fp16", "int8"):
        assert legs[name]["collective/wall_ms"] > 0
        assert legs[name]["collective/bytes_post"] > 0
    assert legs["none"]["collective/compression"] == 1.0
    assert legs["fp16"]["collective/compression"] > 1.9
    parity = payload["detail"]["parity"]
    assert parity["int8_abs_diff"] < parity["tolerance"]
    # The tree really is QT-Opt-critic sized (not a toy vector).
    assert payload["detail"]["n_params"] > 1_000_000
    import json as json_mod

    with open(out) as f:
        assert json_mod.load(f)["value"] == payload["value"]
