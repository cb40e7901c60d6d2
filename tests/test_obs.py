"""rocket_tpu.obs: spans, goodput accounting, metrics registry, watchdog,
and the end-to-end telemetry files a run writes at DESTROY."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import optax
import pytest

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.models.mlp import MLP
from rocket_tpu.obs import (
    Goodput,
    MetricsRegistry,
    SpanRecorder,
    Telemetry,
    Watchdog,
    load_chrome_trace,
)
from rocket_tpu.runtime.context import Runtime


# -- goodput ---------------------------------------------------------------


def test_goodput_exclusive_accounting_and_derived_other():
    g = Goodput()
    g.push("step", 0.0)
    g.push("data_wait", 2.0)   # pauses step at t=2
    g.pop(5.0)                 # data_wait = 3, step resumes
    g.pop(6.0)                 # step = 2 + 1
    totals = g.totals()
    assert totals["step"] == pytest.approx(3.0)
    assert totals["data_wait"] == pytest.approx(3.0)

    report = g.report(total_wall_s=10.0)
    assert report["categories"]["other"] == pytest.approx(4.0)
    assert sum(report["categories"].values()) == pytest.approx(
        report["total_wall_s"]
    )
    assert report["goodput_fraction"] == pytest.approx(0.3)


def test_goodput_total_never_below_measured():
    g = Goodput()
    g.push("step", 0.0)
    g.pop(2.0)
    report = g.report(total_wall_s=1.0)  # caller's clock lagged
    assert report["total_wall_s"] == pytest.approx(2.0)
    assert report["categories"]["other"] == 0.0


# -- registry --------------------------------------------------------------


def test_registry_instruments_and_snapshots():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    hist = reg.histogram("h", base=1.0)
    for v in (0.5, 1.0, 3.0, 3.0):
        hist.observe(v)

    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 4
    assert snap["histograms"]["h"]["max"] == 3.0
    assert snap["histograms"]["h"]["mean"] == pytest.approx(1.875)
    # le_1 bucket holds the two <=1.0 observations, le_4 the two 3.0s.
    assert snap["histograms"]["h"]["buckets"] == {"le_1": 2, "le_4": 2}

    scalars = reg.scalars()
    assert scalars["c"] == 3.0 and scalars["g"] == 7.0
    assert scalars["h/count"] == 4.0
    assert scalars["h/mean"] == pytest.approx(1.875)


def test_registry_device_memory_is_harmless_on_cpu():
    reg = MetricsRegistry()
    reg.record_device_memory()  # CPU devices report no memory stats
    assert "hbm/bytes_in_use_max" not in reg.snapshot()["gauges"]


# -- spans -----------------------------------------------------------------


def test_span_recorder_chrome_trace_roundtrip(tmp_path):
    rec = SpanRecorder()
    rec.add("outer", "step", rec.t0, 0.5)
    rec.add("inner", None, rec.t0 + 0.1, 0.2)
    path = rec.write(str(tmp_path / "spans.trace.json"))
    events = load_chrome_trace(path)
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"outer", "inner"}
    outer = next(e for e in complete if e["name"] == "outer")
    assert outer["cat"] == "step" and outer["dur"] == pytest.approx(5e5)
    assert outer["ts"] == pytest.approx(0.0, abs=1e-3)


def test_span_recorder_bounded_buffer():
    rec = SpanRecorder(max_events=2)
    for i in range(5):
        rec.add(f"s{i}", None, 0.0, 0.1)
    assert len(rec) == 2 and rec.dropped == 3
    # A ring: the NEWEST events are the ones kept.
    assert [ev.name for ev in rec.events()] == ["s3", "s4"]
    assert rec.to_chrome_trace()["otherData"]["dropped"] == 3


def test_telemetry_span_tracks_open_stack_and_goodput():
    tel = Telemetry(enabled=True)
    with tel.span("phase", cat="step"):
        with tel.span("inner"):
            stacks = tel.spans.open_spans()
            names = stacks[threading.get_ident()]
            assert names == ["phase", "inner"]
    assert tel.spans.open_spans() == {}
    assert tel.goodput.totals()["step"] > 0.0
    assert len(tel.spans) == 2


def test_disabled_telemetry_is_inert(tmp_path):
    tel = Telemetry(enabled=False)
    with tel.span("x", cat="step"):
        pass
    assert len(tel.spans) == 0
    assert tel.scalars_snapshot() == {}
    assert tel.flush(str(tmp_path)) is None
    assert not os.path.exists(tmp_path / "telemetry.json")


# -- watchdog --------------------------------------------------------------


def test_watchdog_fires_on_stall_and_dumps_stacks():
    reports = []
    rec = SpanRecorder()
    reg = MetricsRegistry()
    dog = Watchdog(0.15, on_stall=reports.append, spans=rec, registry=reg,
                   poll_s=0.02)
    dog.start()
    try:
        dog.arm()
        rec.push_open("train/step")
        deadline = time.time() + 5.0
        while not reports and time.time() < deadline:
            time.sleep(0.02)
    finally:
        rec.pop_open()
        dog.stop()
    assert reports, "watchdog never fired on a stalled heartbeat"
    report = reports[0]
    assert "no step completed" in report
    assert "train/step" in report            # the open span stack
    assert "MainThread" in report            # thread stacks
    assert "live jax arrays" in report
    assert dog.stall_count >= 1
    assert reg.snapshot()["counters"]["watchdog/stalls"] >= 1


def test_watchdog_does_not_fire_while_beating():
    reports = []
    dog = Watchdog(0.2, on_stall=reports.append, poll_s=0.02)
    dog.start()
    try:
        dog.arm()
        for _ in range(10):
            time.sleep(0.05)
            dog.beat()
        dog.disarm()
        time.sleep(0.3)  # disarmed: a silent heartbeat must not fire
    finally:
        dog.stop()
    assert reports == []


def test_explicit_watchdog_secs_implies_telemetry(tmp_path):
    """An explicit ask for hang protection must never silently no-op:
    watchdog_secs= with telemetry unset turns the subsystem on."""
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
        watchdog_secs=30.0,
    )
    try:
        assert runtime.telemetry.enabled
        assert runtime.telemetry.watchdog is not None
        assert runtime.telemetry.watchdog.deadline_s == 30.0
    finally:
        runtime.end_training()


def test_watchdog_fires_on_artificially_stalled_step(tmp_path):
    """Acceptance: a Looper step that hangs past the deadline produces a
    stall dump while the run is still going."""
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
        telemetry=True, watchdog_secs=0.2,
    )
    runtime.telemetry.watchdog._poll_s = 0.02  # fast test cadence

    class Stall(rt.Capsule):
        def __init__(self):
            super().__init__(priority=500)
            self.stalled = False

        def launch(self, attrs=None):
            if not self.stalled:
                self.stalled = True
                deadline = time.time() + 5.0
                dog = self._runtime.telemetry.watchdog
                while dog.stall_count == 0 and time.time() < deadline:
                    time.sleep(0.02)

    data = [{"x": np.float32(i)} for i in range(16)]
    rt.Launcher(
        [rt.Looper([rt.Dataset(data, batch_size=8, fuse_gather=False),
                    Stall()], tag="train", progress=False)],
        num_epochs=1, runtime=runtime,
    ).launch()
    telemetry_doc = json.load(
        open(tmp_path / "runs" / "telemetry" / "telemetry.json")
    )
    assert telemetry_doc["watchdog"]["stalls"] >= 1
    assert telemetry_doc["watchdog"]["report_file"] == "watchdog_stalls.txt"
    dump = (tmp_path / "runs" / "telemetry" / "watchdog_stalls.txt").read_text()
    assert "no step completed" in dump
    # The main thread's stack shows the stalled capsule's launch frame,
    # and the open-span stack names the wave it was inside.
    assert "launch" in dump
    assert "train/wave" in dump


# -- end-to-end ------------------------------------------------------------


def cross_entropy(batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        batch["logits"], batch["label"]
    ).mean()


def _train_tree(runtime, runs_dir, data, num_epochs=2):
    model = MLP(in_features=8, num_classes=4, hidden=(16,))
    module = rt.Module(
        model,
        capsules=[rt.Loss(cross_entropy),
                  rt.Optimizer(optim.adam(), learning_rate=1e-2)],
    )
    return rt.Launcher(
        [rt.Looper(
            [rt.Dataset(data, batch_size=32), module, rt.Profiler(),
             rt.Tracker(project="obs_e2e", directory=runs_dir)],
            tag="train", progress=False,
        )],
        num_epochs=num_epochs, runtime=runtime,
    )


def _dataset(n=128):
    rng = np.random.default_rng(0)
    return [
        {"image": rng.normal(size=8).astype(np.float32),
         "label": np.int32(i % 4)}
        for i in range(n)
    ]


def test_run_writes_telemetry_and_spans_with_strict_guards(tmp_path):
    """The acceptance-criteria run: telemetry + strict mode together.
    telemetry.json parses, goodput sums to wall-clock within 5%, the span
    file is valid Chrome-trace JSON with the expected categories, and the
    obs/* scalars landed in the tracker stream."""
    runs_dir = str(tmp_path / "runs")
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
        strict=True, telemetry=True,
    )
    _train_tree(runtime, runs_dir, _dataset()).launch()

    out_dir = tmp_path / "runs" / "obs_e2e"
    record = json.load(open(out_dir / "telemetry.json"))
    goodput = record["goodput"]
    assert goodput["total_wall_s"] > 0
    assert sum(goodput["categories"].values()) == pytest.approx(
        goodput["total_wall_s"], rel=0.05
    )
    assert goodput["categories"]["step"] > 0
    assert goodput["categories"]["compile"] > 0
    assert record["metrics"]["counters"]["compile/events"] > 0
    # StrictMode's retrace count mirrored into the registry.
    assert any(
        k.startswith("strict/retraces/train_step")
        for k in record["metrics"]["gauges"]
    )

    events = load_chrome_trace(str(out_dir / "spans.trace.json"))
    complete = [e for e in events if e.get("ph") == "X"]
    cats = {e["cat"] for e in complete}
    assert {"step", "compile", "data_wait", "flush"} <= cats
    # Dispatch spans from the Capsule.dispatch choke point.
    assert any(e["name"] == "Dataset.launch" for e in complete)
    assert any(e["name"].startswith("compile/train_step") for e in complete)

    with open(os.path.join(runs_dir, "obs_e2e.jsonl")) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    obs_keys = {k for rec in lines for k in rec if k.startswith("obs/")}
    assert "obs/goodput/step_fraction" in obs_keys
    assert "obs/perf/steps_per_sec" in obs_keys


def test_telemetry_disabled_writes_nothing(tmp_path):
    runs_dir = str(tmp_path / "runs")
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
    )
    _train_tree(runtime, runs_dir, _dataset(64), num_epochs=1).launch()
    assert not (tmp_path / "runs" / "obs_e2e" / "telemetry.json").exists()


def test_prefetch_records_queue_depth(tmp_path):
    from rocket_tpu.data.prefetch import PrefetchIterator

    tel = Telemetry(enabled=True)
    it = PrefetchIterator(iter(range(8)), depth=2, telemetry=tel)
    assert list(it) == list(range(8))
    hist = tel.registry.snapshot()["histograms"]["data/prefetch_depth"]
    assert hist["count"] >= 8  # one observation per dequeue (incl. DONE)
    # Worker-side produce spans on the prefetch thread's trace line.
    assert any(
        name == "data/prefetch_produce" for name, *_ in tel.spans.events()
    )


def test_loader_counts_produced_batches():
    from rocket_tpu.data.loader import DataLoader

    tel = Telemetry(enabled=True)
    data = [{"x": np.float32(i)} for i in range(64)]
    loader = DataLoader(data, batch_size=16, telemetry=tel)
    assert len(list(loader)) == 4
    counters = tel.registry.snapshot()["counters"]
    assert counters["data/batches_produced"] == 4.0
    assert "data/worker_batches" not in counters  # serial path


def test_tracker_backend_closed_by_runtime_teardown(tmp_path):
    """Satellite regression: JsonlBackend file handles must not leak past
    DESTROY — Launcher teardown (Runtime.end_training) closes every
    registered backend even when one of them throws."""
    runs_dir = str(tmp_path / "runs")
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
    )
    tracker = rt.Tracker(project="obs_e2e", directory=runs_dir)
    model = MLP(in_features=8, num_classes=4, hidden=(16,))
    module = rt.Module(
        model,
        capsules=[rt.Loss(cross_entropy),
                  rt.Optimizer(optim.adam(), learning_rate=1e-2)],
    )
    backend_seen = {}

    class Grab(rt.Capsule):
        def __init__(self):
            super().__init__(priority=10)

        def launch(self, attrs=None):
            backend_seen["backend"] = runtime.get_tracker("jsonl")

    launcher = rt.Launcher(
        [rt.Looper(
            [rt.Dataset(_dataset(64), batch_size=32), module, tracker,
             Grab()],
            tag="train", progress=False,
        )],
        num_epochs=1, runtime=runtime,
    )
    launcher.launch()
    backend = backend_seen["backend"]
    assert backend is not None
    assert backend._file.closed, "JsonlBackend handle leaked past teardown"
    assert runtime.trackers == {}
    # The capsule dropped its own reference at DESTROY too.
    assert tracker._backend is None


def test_end_training_survives_a_failing_backend_close(tmp_path):
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
    )

    closed = []

    class Bad:
        def close(self):
            raise RuntimeError("socket gone")

    class Good:
        def close(self):
            closed.append(True)

    runtime.init_tracker("bad", Bad())
    runtime.init_tracker("good", Good())
    runtime.end_training()  # must not raise
    assert closed == [True]
    assert runtime.trackers == {}


def test_report_cli_renders_telemetry_and_span_files(tmp_path):
    runs_dir = str(tmp_path / "runs")
    runtime = Runtime(
        mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path),
        telemetry=True,
    )
    _train_tree(runtime, runs_dir, _dataset(64), num_epochs=1).launch()
    out_dir = tmp_path / "runs" / "obs_e2e"
    for name, expect in (
        ("telemetry.json", "goodput (step fraction)"),
        ("spans.trace.json", "span file:"),
    ):
        proc = subprocess.run(
            [sys.executable, "-m", "rocket_tpu.obs", "report",
             str(out_dir / name)],
            capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        assert expect in proc.stdout

    proc = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "report",
         str(tmp_path / "missing.json")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 2


def test_report_cli_zero_step_run_renders_no_steps_row(tmp_path):
    """Satellite regression: a telemetry.json from a zero-step run (no
    fractions block, zero wall-clock) must render an explicit "no steps
    recorded" row — never crash on the degenerate goodput record."""
    zero = {
        "version": 1,
        "goodput": {
            "total_wall_s": 0.0,
            "categories": {cat: 0.0 for cat in
                           ("compile", "data_wait", "step", "checkpoint",
                            "flush", "other")},
            # No "fractions" key: the CLI must derive them with a guarded
            # division (total == 0 was the ZeroDivision hazard).
        },
        "metrics": {"counters": {}, "gauges": {}, "histograms": {}},
        "spans": {"file": "spans.trace.json", "events": 0, "dropped": 0},
        "watchdog": {"enabled": False, "deadline_s": None, "stalls": 0},
    }
    path = tmp_path / "telemetry.json"
    path.write_text(json.dumps(zero))
    proc = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "report", str(path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "no steps recorded" in proc.stdout
    assert "ZeroDivisionError" not in proc.stderr

    # A freshly constructed (zero-step) Telemetry's own flush renders too.
    tel = Telemetry(enabled=True)
    out = tel.flush(str(tmp_path / "fresh"))
    proc = subprocess.run(
        [sys.executable, "-m", "rocket_tpu.obs", "report",
         os.path.join(out, "telemetry.json")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "no steps recorded" in proc.stdout


def test_span_drops_surface_as_metric_and_teardown_warning(tmp_path, caplog):
    """Satellite: SpanRecorder drops become an obs/spans_dropped registry
    metric and a one-line teardown warning, so a truncated trace is never
    mistaken for a complete one."""
    import logging

    logger = logging.getLogger("rocket_tpu.test_obs_drops")
    tel = Telemetry(enabled=True, max_span_events=2, logger=logger)
    for i in range(5):
        with tel.span(f"s{i}", cat="step"):
            pass
    assert tel.spans.dropped == 3
    assert tel.scalars_snapshot()["obs/spans_dropped"] == 3.0
    assert tel.summary()["metrics"]["gauges"]["obs/spans_dropped"] == 3.0
    with caplog.at_level("WARNING", logger=logger.name):
        tel.close(str(tmp_path), write=False)
    assert any("span(s) dropped" in rec.message for rec in caplog.records)

    # A clean run stays quiet.
    tel2 = Telemetry(enabled=True, logger=logger)
    with tel2.span("ok", cat="step"):
        pass
    caplog.clear()
    with caplog.at_level("WARNING", logger=logger.name):
        tel2.close(str(tmp_path), write=False)
    assert not any("dropped" in rec.message for rec in caplog.records)
