"""The bench stdout line must fit the driver's 2,000-byte tail capture.

BENCH_r04.json came back ``parsed: null`` because the monolithic line
(headline + full per-config ``extra``) outgrew the capture window. The
round-5 contract: ``bench.format_line`` emits a compact self-contained
headline ≤ ``bench.MAX_LINE_BYTES`` (1,500 < 2,000 with headroom) no
matter how many configs exist or fail, and ``bench.write_detail`` carries
the full record to BENCH_DETAIL.json. These tests feed worst-case inputs
through the real emission path so adding a config can never silently
re-break the artifact.
"""

import json
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _full_result(name):
    """A maximal per-config result: every field populated."""
    return {
        "metric": bench.METRIC_NAMES.get(
            name, f"{name}_tok_per_sec_per_chip"
        ),
        "value": 1234567.8,
        "unit": "tok/sec/chip",
        "vs_baseline": 12.345,
        "mfu": 0.5678,
        "best_value": 1345678.9,
        "best_mfu": 0.6123,
        "device": dict(DEVICE),
        "kernel_configs": [{"kernel": "flash_fwd", "source": "default"}] * 4,
    }


def _worst_case_results(n_extra=20):
    """Every real config fully populated, plus n_extra future configs —
    far beyond any plausible growth of BENCHES."""
    results = {name: _full_result(name) for name in bench.BENCHES}
    for i in range(n_extra):
        results[f"future_config_with_a_long_name_{i:02d}"] = _full_result(
            f"future_config_with_a_long_name_{i:02d}"
        )
    return results


def test_line_fits_capture_worst_case():
    line = bench.format_line(_worst_case_results())
    assert len(line) <= bench.MAX_LINE_BYTES
    parsed = json.loads(line)
    # The headline must survive every degradation step.
    assert parsed["metric"] == bench.METRIC_NAMES["gpt2"]
    assert parsed["value"] == 1234567.8
    assert parsed["mfu"] == 0.5678
    assert parsed["detail"] == "BENCH_DETAIL.json"


def test_line_fits_when_everything_errors():
    """str(exc) from an XLA failure routinely runs kilobytes — the line
    must fit even when every config carries an unbounded error string."""
    results = {
        name: {"metric": bench.METRIC_NAMES[name],
               "error": "XlaRuntimeError: " + "x" * 8000}
        for name in bench.BENCHES
    }
    line = bench.format_line(results)
    assert len(line) <= bench.MAX_LINE_BYTES
    parsed = json.loads(line)
    assert parsed["error"].startswith("XlaRuntimeError")


def test_normal_sweep_keeps_summary_and_device():
    """At today's config count nothing should be degraded away: the line
    names the device it was measured on AND carries one value per other
    config."""
    results = {name: _full_result(name) for name in bench.BENCHES}
    line = bench.format_line(results)
    assert len(line) <= bench.MAX_LINE_BYTES
    parsed = json.loads(line)
    assert parsed["device"] == DEVICE
    others = parsed["others"]
    for name in bench.BENCHES:
        if name == "gpt2":
            continue
        assert others[name] == 1234567.8
    assert others["resnet50_mfu"] == 0.568


def test_write_detail_round_trips(tmp_path):
    results = {name: _full_result(name) for name in bench.BENCHES}
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail(results, path=str(path))
    detail = json.loads(path.read_text())
    assert detail["headline_metric"] == bench.METRIC_NAMES["gpt2"]
    assert set(detail["configs"]) == set(bench.BENCHES)
    # Full fidelity: the detail file keeps what the line drops.
    assert detail["configs"]["llama"]["device"] == DEVICE


def test_write_detail_merges_partial_runs(tmp_path):
    """A --config X debugging run must not clobber the full-sweep record
    the stdout 'detail' pointer references."""
    path = tmp_path / "BENCH_DETAIL.json"
    full = {name: _full_result(name) for name in bench.BENCHES}
    bench.write_detail(full, path=str(path))
    partial = {"gpt2": dict(_full_result("gpt2"), value=999.9)}
    bench.write_detail(partial, path=str(path))
    detail = json.loads(path.read_text())
    assert set(detail["configs"]) == set(bench.BENCHES)
    assert detail["configs"]["gpt2"]["value"] == 999.9
    assert detail["configs"]["llama"]["value"] == 1234567.8


def test_write_detail_errored_rerun_keeps_good_record(tmp_path):
    """An errored re-run (debug OOM, transient XLA failure) must not
    destroy a committed good config record — it is annotated instead."""
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    bench.write_detail(
        {"gpt2": {"metric": bench.METRIC_NAMES["gpt2"], "error": "OOM" * 200}},
        path=str(path),
    )
    rec = json.loads(path.read_text())["configs"]["gpt2"]
    assert rec["value"] == 1234567.8          # good record survives
    assert rec["last_error"].startswith("OOM")
    assert len(rec["last_error"]) <= 200
    # A fresh error with NO prior good record still lands as-is.
    bench.write_detail({"moe": {"metric": "m", "error": "boom"}},
                       path=str(path))
    assert json.loads(path.read_text())["configs"]["moe"]["error"] == "boom"
    # And a later good run replaces the annotated record cleanly.
    bench.write_detail({"gpt2": dict(_full_result("gpt2"), value=42.0)},
                       path=str(path))
    rec = json.loads(path.read_text())["configs"]["gpt2"]
    assert rec["value"] == 42.0 and "last_error" not in rec


def test_write_detail_carries_audit_calibration_across_partial_runs(
        tmp_path):
    """A partial run cannot recompute calibration entries (each needs
    that config's measured value from THIS run) — the committed blocks
    must survive, per-config for sched and whole for serve."""
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    detail = json.loads(path.read_text())
    sched_cal = {"charlm": {"calibration_error": 1.5, "priced_for": "x"},
                 "resnet18": {"calibration_error": 0.9}}
    serve_cal = {"itl_calibration_error": -0.5, "predicted_itl_us": 10.0}
    detail.setdefault("sched_audit", {})["calibration"] = sched_cal
    detail.setdefault("serve_audit", {})["calibration"] = serve_cal
    path.write_text(json.dumps(detail))
    # A run that measured NO calibration config keeps both blocks whole.
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    detail = json.loads(path.read_text())
    assert detail["sched_audit"]["calibration"] == sched_cal
    assert detail["serve_audit"]["calibration"] == serve_cal


def test_carry_calibration_merges_per_config_and_replaces_flat():
    # Per-config (sched): a fresh entry wins, missing configs carry.
    section = {"calibration": {"charlm": {"calibration_error": 2.0}}}
    bench._carry_calibration(section, {"calibration": {
        "charlm": {"calibration_error": 1.0},
        "resnet18": {"calibration_error": 0.5},
    }})
    assert section["calibration"]["charlm"]["calibration_error"] == 2.0
    assert section["calibration"]["resnet18"]["calibration_error"] == 0.5
    # Flat single-entry (serve): a fresh block replaces wholesale —
    # stale scalar keys from the prior run must not bleed in.
    section = {"calibration": {"itl_calibration_error": 0.1}}
    bench._carry_calibration(section, {"calibration": {
        "itl_calibration_error": 0.9, "ttft_calibration_error": 0.8,
    }})
    assert section["calibration"] == {"itl_calibration_error": 0.1}


def test_write_detail_survives_corrupt_prior(tmp_path):
    path = tmp_path / "BENCH_DETAIL.json"
    for corrupt in ("{not json", "[1,2]", '"a string"', ""):
        path.write_text(corrupt)
        bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
        assert "mlp" in json.loads(path.read_text())["configs"]


def test_write_detail_carries_shard_audit_record(tmp_path):
    """BENCH_DETAIL.json carries the statically-audited per-device HBM
    estimate and per-step collective-bytes totals (from the committed
    SPMD budget records the shard-audit CI gate verifies)."""
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    audit = json.loads(path.read_text())["shard_audit"]
    assert audit["hbm_per_device_bytes"] > 0
    assert audit["collective_bytes_per_step"] > 0
    assert audit["source"] == "tests/fixtures/budgets"
    # Per-target breakdown: every committed budget shows up.
    assert "tp_2x4" in audit["targets"]
    target = audit["targets"]["tp_2x4"]
    assert target["collective_bytes_per_step"] > 0
    assert target["hbm_per_device_bytes"] > 0


def test_write_detail_carries_prec_audit_record(tmp_path):
    """BENCH_DETAIL.json carries the statically-audited numerics (fp32-
    bytes fraction of the traced step, widen/narrow cast counts) from
    the committed numerics budgets the precision CI gate verifies."""
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    audit = json.loads(path.read_text())["prec_audit"]
    assert 0.0 < audit["fp32_bytes_fraction"] < 1.0
    assert audit["narrow_casts"] > 0
    assert audit["source"] == "tests/fixtures/budgets/prec"
    # Per-target breakdown: every committed numerics budget shows up.
    assert "tp_2x4" in audit["targets"]
    target = audit["targets"]["tp_2x4"]
    assert 0.0 < target["fp32_bytes_fraction"] < 1.0
    assert target["widen_casts"] > 0


def test_write_detail_carries_serve_audit_record(tmp_path):
    """BENCH_DETAIL.json carries the statically-predicted serving
    latency/HBM record (from the committed serving budgets the serve
    CI gate verifies), and — when a measured serve record rides along —
    the predicted-vs-measured ITL calibration."""
    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    audit = json.loads(path.read_text())["serve_audit"]
    assert audit["predicted_itl_us"] > 0
    assert audit["predicted_ttft_us"] > audit["predicted_itl_us"]
    assert audit["hbm_total_bytes"] > 0
    assert audit["source"] == "tests/fixtures/budgets/serve"
    for name in ("tiny", "charlm", "gpt2_geom"):
        target = audit["targets"][name]
        assert target["predicted_itl_us"] > target["itl_floor_us"] > 0
        assert target["overfetch_ratio"] >= 1.0


def test_serve_audit_summary_missing_budgets_is_none(tmp_path):
    """A checkout without committed serving budgets must not break
    emission."""
    assert bench.serve_audit_summary(
        None, str(tmp_path / "nowhere")
    ) is None


def test_calib_summary_reads_committed_budgets():
    """The budget half of the calib record (live=False skips the
    capture leg): per-target |calibration error| + unjoined fraction
    from the records the calib CI gate verifies."""
    out = bench.calib_summary(live=False)
    assert out is not None
    assert out["source"] == "tests/fixtures/budgets/calib"
    for name in ("gpt2_sentinel", "fsdp_1x8", "serve_decode"):
        assert 0 < out["targets"][name]["abs_calib_error"] <= 1.5
    # Worst-case headline across targets.
    assert out["abs_calib_error"] >= out["targets"]["gpt2_sentinel"][
        "abs_calib_error"
    ]


def test_calib_summary_missing_budgets_is_none(tmp_path):
    assert bench.calib_summary(str(tmp_path / "nowhere"),
                               live=False) is None


def test_write_detail_carries_calib_record(tmp_path):
    """BENCH_DETAIL.json carries the measured-vs-predicted record, and a
    probe-less rerun must not drop a previously-written one."""
    path = tmp_path / "BENCH_DETAIL.json"
    calib = {
        "abs_calib_error": 0.99,
        "targets": {"gpt2_sentinel": {"abs_calib_error": 0.99,
                                      "unjoined_fraction": 0.32}},
        "live": {"gpt2_sentinel": {"measured_step_us": 64000.0,
                                   "device_matched": False}},
        "source": "tests/fixtures/budgets/calib",
    }
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path),
                       calib=calib)
    assert json.loads(path.read_text())["calib"] == calib
    # Probe-less rerun (calib=None) keeps the committed record.
    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    assert json.loads(path.read_text())["calib"] == calib


@pytest.mark.slow
def test_calib_summary_live_leg_captures_and_reconciles():
    """The live half: a real capture->parse->reconcile of the gpt2
    sentinel on this host. Slow: one AOT compile + a traced run."""
    out = bench.calib_summary()
    assert out is not None and "live" in out
    live = out["live"]["gpt2_sentinel"]
    assert live["measured_step_us"] > 0
    assert live["abs_calib_error"] is not None
    assert live["priced_for"] == "TPU v5 lite"
    assert isinstance(live["device_matched"], bool)


@pytest.mark.slow
def test_serve_calibration_ties_prediction_to_measured_record():
    """The calibration leg: feed serve_audit_summary a measured serve
    record (the shape serve_summary emits) and it must re-predict the
    SAME engine config and report the signed error. Slow: one AOT
    compile of the charlm-geometry programs."""
    measured = {"itl_ms": {"p50": 2.0}, "ttft_ms": {"p50": 20.0}}
    out = bench.serve_audit_summary(measured)
    assert out is not None and "calibration" in out
    calib = out["calibration"]
    assert calib["measured_itl_us"] == 2000.0
    assert calib["predicted_itl_us"] > 0
    expected = (calib["predicted_itl_us"] - 2000.0) / 2000.0
    assert calib["itl_calibration_error"] == pytest.approx(
        expected, abs=1e-3
    )
    assert calib["ttft_calibration_error"] is not None
    # This container benches on CPU: the kind is absent from the peak
    # table, the prediction prices the reference kind instead.
    assert calib["priced_for"]
    assert isinstance(calib["device_matched"], bool)


def test_write_detail_carries_tune_record(tmp_path):
    """BENCH_DETAIL.json carries the tuned-kernel config record
    (rocket_tpu.tune tables): one row per tunable kernel with its entry
    list — each entry keyed (device kind, shape bucket, dtype) and
    carrying the tuner-measured speedup — plus this run's device kind,
    so tuned-vs-default speedup is tracked per kernel per device kind."""
    from rocket_tpu.tune.space import TUNE_SPACES

    path = tmp_path / "BENCH_DETAIL.json"
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    record = json.loads(path.read_text())["tune"]
    assert set(record["kernels"]) == set(TUNE_SPACES)
    for kernel, row in record["kernels"].items():
        assert isinstance(row["n_entries"], int) and row["n_entries"] >= 0
        assert len(row["entries"]) == row["n_entries"]
        for entry in row["entries"]:
            assert entry["device_kind"] and entry["shape_bucket"]
            assert entry["speedup"] > 1.0  # only wins are persisted
        assert isinstance(row["structural_axes"], list)
    # The structural-variant scoreboard (ISSUE 14) rides the same
    # record: a list (empty while the shipped tables carry no wins),
    # carried across probe-less runs like the rest.
    assert isinstance(record["structural_wins"], list)
    assert record["device_kind"]
    assert record["source"].endswith(os.path.join("tune", "configs"))


def test_tune_summary_missing_tables_is_none(tmp_path):
    """A checkout without the tune config tables must not break
    emission."""
    assert bench.tune_summary(str(tmp_path / "nowhere")) is None
    path = tmp_path / "BENCH_DETAIL.json"
    real = bench.TUNE_CONFIGS_DIR
    bench.TUNE_CONFIGS_DIR = str(tmp_path / "nowhere")
    try:
        bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    finally:
        bench.TUNE_CONFIGS_DIR = real
    assert "tune" not in json.loads(path.read_text())


def test_tune_summary_reports_table_entries(tmp_path):
    """A table with a tuned entry surfaces its speedup row and device
    kind in the summary (the shape the tuner's --update-table writes)."""
    from rocket_tpu.tune.space import TUNE_SPACES
    from rocket_tpu.tune.table import write_table

    for kernel in TUNE_SPACES:
        write_table(kernel, [], configs_dir=str(tmp_path))
    write_table("flash_fwd", [{
        "device_kind": "TPU v5 lite", "dtype": "bfloat16",
        "shape": {"t": 1024, "d": 64, "h": 12, "h_kv": 12, "causal": True},
        "shape_bucket": "t1024_d64_h12_h_kv12_causalt",
        "config": {"block_q": 256, "block_k": 256},
        "default_us": 100.0, "tuned_us": 90.0, "speedup": 1.111,
    }], configs_dir=str(tmp_path))
    summary = bench.tune_summary(str(tmp_path))
    row = summary["kernels"]["flash_fwd"]
    assert row["n_entries"] == 1
    assert row["entries"][0]["speedup"] == 1.111
    assert summary["table_device_kinds"] == ["TPU v5 lite"]


def test_prec_audit_summary_missing_budgets_is_none(tmp_path):
    """A checkout without committed numerics budgets must not break
    emission."""
    assert bench.prec_audit_summary(str(tmp_path / "nowhere")) is None
    path = tmp_path / "BENCH_DETAIL.json"
    real = bench.PREC_BUDGETS_DIR
    bench.PREC_BUDGETS_DIR = str(tmp_path / "nowhere")
    try:
        bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    finally:
        bench.PREC_BUDGETS_DIR = real
    assert "prec_audit" not in json.loads(path.read_text())


def test_shard_audit_summary_missing_budgets_is_none(tmp_path):
    """A checkout without committed budgets must not break emission."""
    assert bench.shard_audit_summary(str(tmp_path / "nowhere")) is None
    # And the detail file simply omits the section.
    path = tmp_path / "BENCH_DETAIL.json"
    real = bench.BUDGETS_DIR
    bench.BUDGETS_DIR = str(tmp_path / "nowhere")
    try:
        bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    finally:
        bench.BUDGETS_DIR = real
    assert "shard_audit" not in json.loads(path.read_text())


def test_write_detail_carries_health_sentinel_record(tmp_path):
    """BENCH_DETAIL.json carries the measured health-sentinel overhead
    (steps/sec with the in-step sentinels + lax.cond gate on vs off) when
    main() hands a probe record over — and simply omits the section when
    the probe was skipped or failed."""
    path = tmp_path / "BENCH_DETAIL.json"
    probe = {
        "steps_per_sec_baseline": 150.0,
        "steps_per_sec_with_sentinels": 148.5,
        "overhead_frac": 0.01,
        "action": "skip_step",
        "anomalies": 0,
        "skipped_steps": 0,
        "config": "mlp",
    }
    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path),
                       health=probe)
    record = json.loads(path.read_text())["health_sentinels"]
    assert record["overhead_frac"] == 0.01
    assert record["anomalies"] == 0

    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    assert "health_sentinels" not in json.loads(path.read_text())


def test_write_detail_carries_resilience_record(tmp_path):
    """BENCH_DETAIL.json carries the supervised-restart probe's headline
    (goodput under one injected kill through the real supervisor) when
    main() hands a record over — and omits the section otherwise."""
    path = tmp_path / "BENCH_DETAIL.json"
    probe = {
        "outcome": "completed",
        "restarts": 1,
        "generations": 2,
        "goodput_fraction": 0.97,
        "total_wall_s": 12.3,
        "target_step": 60,
        "fault": "kill:step=23",
    }
    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path),
                       resilience=probe)
    record = json.loads(path.read_text())["resilience"]
    assert record["goodput_fraction"] == 0.97
    assert record["restarts"] == 1 and record["outcome"] == "completed"

    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    assert "resilience" not in json.loads(path.read_text())


def test_write_detail_partial_run_keeps_gpt2_headline(tmp_path):
    """The merged record's headline must stay gpt2 after a debug run of
    a different config."""
    path = tmp_path / "BENCH_DETAIL.json"
    full = {name: _full_result(name) for name in bench.BENCHES}
    bench.write_detail(full, path=str(path))
    bench.write_detail({"mlp": _full_result("mlp")}, path=str(path))
    detail = json.loads(path.read_text())
    assert detail["headline_metric"] == bench.METRIC_NAMES["gpt2"]


def test_write_detail_carries_overlap_record(tmp_path):
    path = tmp_path / "detail.json"
    overlap = {
        "targets": {
            "tp_1x8": {
                "overlap": {"collective_bytes_per_step": 7600432,
                            "exposed_comm_us": 70.0},
                "baseline": {"collective_bytes_per_step": 14176944,
                             "exposed_comm_us": 147.5},
                "bytes_ratio": 1.865,
                "exposed_comm_drop_frac": 0.5255,
            }
        },
        "device_kind": "TPU v5 lite",
        "wire_dtype": "bfloat16",
    }
    bench.write_detail(
        {"gpt2": _full_result("gpt2")}, path=str(path), overlap=overlap
    )
    detail = json.loads(path.read_text())
    rec = detail["overlap"]["targets"]["tp_1x8"]
    assert rec["bytes_ratio"] == 1.865
    assert rec["exposed_comm_drop_frac"] > 0.4
    # A later run without the probe must not drop the committed record.
    bench.write_detail({"gpt2": _full_result("gpt2")}, path=str(path))
    assert "overlap" in json.loads(path.read_text())


def test_overlap_summary_shapes_real_targets():
    summary = bench.overlap_summary(targets=("tp_2x4_eval",))
    assert summary is not None
    rec = summary["targets"]["tp_2x4_eval"]
    assert rec["overlap"]["collective_bytes_per_step"] > 0
    assert rec["baseline"]["collective_bytes_per_step"] > 0
    # The overlapped eval forward moves no MORE than the GSPMD baseline.
    assert rec["bytes_ratio"] >= 1.0
    assert "exposed_comm_drop_frac" in rec


# -- no fallback that hides a failure or the device -------------------------


def _run_main(monkeypatch, capsys, tmp_path, benches, serve_probe=None):
    """bench.main() over stub configs with the detail probes stubbed out
    (``serve_probe`` replaces the serving one); returns (exit code, the
    parsed stdout line)."""
    monkeypatch.setattr(bench, "BENCHES", benches)
    monkeypatch.setattr(
        bench, "METRIC_NAMES", {n: f"{n}_metric" for n in benches}
    )
    for probe in ("health_summary", "serve_summary", "resilience_summary",
                  "overlap_summary", "calib_summary"):
        monkeypatch.setattr(bench, probe, lambda: None)
    if serve_probe is not None:
        monkeypatch.setattr(bench, "serve_summary", serve_probe)
    monkeypatch.setattr(bench, "write_detail", lambda results, **kw: None)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", next(iter(benches))])
    code = 0
    try:
        bench.main()
    except SystemExit as exc:
        code = exc.code
    return code, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _one_number():
    return {"metric": "a_metric", "value": 1.0, "unit": "x"}


def test_main_names_the_device_and_exits_zero(monkeypatch, capsys, tmp_path):
    import jax

    code, line = _run_main(monkeypatch, capsys, tmp_path, {"a": _one_number})
    assert code == 0
    assert line["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }


def test_main_exits_nonzero_when_a_config_raises(monkeypatch, capsys,
                                                 tmp_path):
    def boom():
        raise RuntimeError("kernel refused")

    code, line = _run_main(monkeypatch, capsys, tmp_path, {"a": boom})
    assert code == 1
    assert line["error"] == "kernel refused"  # the line still went out
    assert line["device"]["platform"] == "cpu"


def test_main_exits_nonzero_when_a_probe_raises(monkeypatch, capsys,
                                                tmp_path):
    def bad_serve():
        raise RuntimeError("engine died")

    code, line = _run_main(
        monkeypatch, capsys, tmp_path, {"a": _one_number},
        serve_probe=bad_serve,
    )
    assert code == 1
    assert line["value"] == 1.0


def test_peak_flops_unknown_kind_is_an_error():
    """On the CPU backend (a kind the peak table does not hold) the bench
    refuses to compute MFU instead of dropping the field."""
    with pytest.raises(RuntimeError, match="not in"):
        bench.peak_flops()
