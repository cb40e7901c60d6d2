"""The readers of the program's own spans on a small recorded list
(``fixtures/program_spans.json``, rows in the order of
``rocket_tpu.obs.spans.SpanEvent``), against numbers worked out by hand, and
on a program that has no recorder."""

import json
import sys
from pathlib import Path

import pytest

from benchmark.readers import program_span_count, program_spans
from benchmark.run import HERE, load_json

SPANS = json.loads(
    (Path(__file__).resolve().parent / "fixtures" / "program_spans.json").read_text()
)


def _read(metric, ctx):
    """Through the metric's own file, as ``run.read_per_layer`` does."""
    spec = load_json(HERE / "metrics" / f"{metric}.json")
    reader = {"program_spans": program_spans,
              "program_span_count": program_span_count}[spec["reader"]]
    return reader.read(ctx, **spec["args"])


# By hand. Ticks: 0.35 - 0.32, 0.20 - 0.14, 0.36 - 0.30 of self time; waits
# 0.32, 0.14, 0.30. The stretch is [100.0, 101.06], so the 2 s window is
# [99.06, 101.06]: requests 1 and 2 were submitted in it (99.5, 100.2) and
# request 3 (98.0) was not: waits 0.1 and 0.3, runs 0 and 0.4, first tokens
# 0.65 and 0.7. Compile events before the window cover [1.0, 6.0] once; two
# programs were compiled inside the window, one of them before the traced
# stretch opened (it is a recompilation, and no part of set-up), and the
# reference's after it.
@pytest.mark.parametrize("metric,want", [
    ("tick_host_ms.p50", 60.0),
    ("harvest_wait_ms.p50", 300.0),
    ("prefill_wait_ms.p50", 200.0),
    ("prefill_wait_ms.p90", 280.0),
    ("prefill_run_ms.p90", 360.0),
    ("first_token_ms.p50", 675.0),
    ("setup_compile_s", 5.0),
    ("recompiles.serve", 2.0),
])
def test_serve_metrics_match_the_hand_count(metric, want):
    ctx = {"spans": SPANS["serve"], "cell_seconds": 2.0}
    assert _read(metric, ctx) == pytest.approx(want, rel=1e-9)


# Waves: data 0.01 + 0.02, 0.05, 0.02 + 0.02; dispatches 0.01, 0.02, 0.03.
# The window opens at the driver's first wave (9.5, long before the traced
# stretch at 10.0): the step compiled for 7 s and a helper for 0.3 s before
# it (the helper's would lie inside `end - cell seconds` = 8.6), nothing
# inside, the reference after.
@pytest.mark.parametrize("metric,want", [
    ("step_dispatch_ms.p50", 20.0),
    ("data_wait_ms.p50", 40.0),
    ("setup_compile_s", 7.3),
    ("recompiles.train", 0.0),
])
def test_train_metrics_match_the_hand_count(metric, want):
    ctx = {"spans": SPANS["train"], "cell_seconds": 2.0,
           "host": {"waves": [(9.5, 9.51, 9.6, 9.6, 0.0), (9.7, 9.71, 9.8, 9.8, 0.0)]}}
    assert _read(metric, ctx) == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_nothing_to_read_is_none_and_never_nought():
    # Spans, but no tick or wave among them: no stretch to place them by.
    legs_only = [row for row in SPANS["serve"] if row[0].startswith(("req/", "compile/"))]
    for metric in ("prefill_wait_ms.p50", "setup_compile_s", "recompiles.serve"):
        assert _read(metric, {"spans": legs_only, "cell_seconds": 2.0}) is None
    # A stretch, but no span of the name.
    assert _read("tick_host_ms.p50", {"spans": SPANS["train"], "cell_seconds": 2.0}) is None
    assert _read("data_wait_ms.p50", {"spans": SPANS["serve"], "cell_seconds": 2.0}) is None


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    """The parent of the PR that brought these readers has no
    ``rocket_tpu.obs.spans.recorded``: every metric is left out, none raises."""
    import rocket_tpu.obs.spans as span_module

    monkeypatch.delattr(span_module, "recorded")
    for metric in ("tick_host_ms.p50", "first_token_ms.p50", "setup_compile_s",
                   "recompiles.train", "recompiles.serve", "data_wait_ms.p50"):
        assert _read(metric, {"cell_seconds": 51.0}) is None
    monkeypatch.setitem(sys.modules, "rocket_tpu.obs.spans", None)
    assert _read("harvest_wait_ms.p50", {"cell_seconds": 51.0}) is None


def test_the_recorders_order_of_fields_is_the_readers():
    from rocket_tpu.obs.spans import SpanEvent

    assert SpanEvent._fields == program_spans.Span._fields


def test_the_tick_split_adds_up_to_the_mean_tick():
    """``tools/span_check``'s split of a tick by child: what ``serve/admit``
    and ``serve/grow`` are for (here the fixture has waits and one dispatch)."""
    from benchmark.tools.span_check import tick_split

    split = tick_split({"spans": SPANS["serve"], "cell_seconds": 2.0})
    assert split["ticks"] == 3
    per_tick = split["mean_ms_per_tick"]
    assert set(per_tick) == {"self", "serve/harvest_wait", "serve/dispatch"}
    assert sum(per_tick.values()) == pytest.approx(1e3 * (0.35 + 0.2 + 0.36) / 3)
    assert per_tick["serve/harvest_wait"] == pytest.approx(1e3 * 0.76 / 3)
    assert tick_split({"spans": SPANS["train"], "cell_seconds": 2.0}) is None
