"""The serve tails a check can resolve (PR 33): the gap between tokens judged
per request, the sample the reference runs over, the traced stretch cut out
of a profile that is stopped after the loop has drained, the idle gaps named
by the program's spans, the spread arithmetic, and the files' consistency."""

import importlib
import json
from pathlib import Path

import jax
import pytest

from benchmark import run, trace
from benchmark.drivers import common, serve, serve_latent_moe
from benchmark.tools import spread

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmark"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FIX = Path(__file__).resolve().parent / "fixtures"
FIX_LATENT = Path(__file__).resolve().parent / "fixtures_latent"


# -- tpot_p90_ms on hand-made token times ---------------------------------------

def ticks(start, n, gap):
    return [start + i * gap for i in range(n)]


def test_tpot_is_the_p90_over_requests_of_each_requests_mean_gap():
    # Ten requests of 9 tokens (8 gaps) at gaps of 10, 11, ..., 19 ms.
    times = [ticks(1.0, 9, (10 + r) / 1e3) for r in range(10)]
    got = common.gap_metrics(times, seconds=5.0)
    assert got["tpot_requests"] == 10 and got["gaps"] == 80
    assert got["tpot_p90_ms"] == pytest.approx(18.1)       # linear between 18 and 19
    assert got["tpot_mean_ms"] == pytest.approx(14.5)
    assert got["itl_p95_ms"] == pytest.approx(19.0)


def test_tpot_reads_only_gaps_whose_later_token_lies_in_the_window():
    # Token times 0.9 .. 1.9 in steps of 0.1; the window is [1.0, ...) on the
    # driver's clock, i.e. lo = 0: shift so that the window opens at 0.
    early = ticks(-0.35, 12, 0.1)      # tokens at -0.35 .. 0.75: gaps ending at 0.05 .. 0.75 count (8)
    late = ticks(4.45, 12, 0.1)        # tokens at 4.45 .. 5.55: gaps ending before 5.0 count (5): too few
    got = common.gap_metrics([early, late], seconds=5.0)
    assert got["gaps"] == 8 + 5
    assert got["tpot_requests"] == 1
    assert got["tpot_p90_ms"] == pytest.approx(100.0)
    per_request = common.token_gaps([early, late], 0.0, 5.0)
    assert [len(g) for g in per_request] == [8, 5]


def test_a_request_with_fewer_than_eight_gaps_does_not_count():
    short = ticks(1.0, 8, 0.5)         # 7 gaps of 500 ms
    long = ticks(1.0, 9, 0.02)         # 8 gaps of 20 ms
    got = common.gap_metrics([short, long], seconds=10.0)
    assert got["tpot_requests"] == 1 and got["tpot_p90_ms"] == pytest.approx(20.0)
    # The single-gap tail still sees the short request's gaps.
    assert got["itl_p95_ms"] > 400
    assert common.TPOT_MIN_GAPS == 8


def test_one_slow_gap_is_diluted_in_its_requests_mean():
    # 100 gaps of 10 ms and one of 111 ms: the mean is 11 ms, the gap p95 10.
    times = ticks(0.5, 101, 0.010) + [0.5 + 100 * 0.010 + 0.111]
    got = common.gap_metrics([times], seconds=10.0)
    assert got["tpot_p90_ms"] == pytest.approx(11.0)
    assert got["itl_p95_ms"] == pytest.approx(10.0)


def test_an_empty_run_has_no_gap_statistic():
    for times in ([], [[]], [[1.0]], [ticks(20.0, 30, 0.01)]):
        got = common.gap_metrics(times, seconds=5.0)
        assert got["tpot_p90_ms"] is None and got["tpot_mean_ms"] is None
        assert got["itl_p95_ms"] is None and got["tpot_requests"] == 0


# -- the sample the reference runs over ---------------------------------------

def test_pick_sample_takes_every_kth_from_the_seeds_offset_and_always_the_longest():
    finished = [([0] * p, [0] * a) for p, a in
                [(5, 2), (9, 9), (3, 1), (4, 4), (30, 2), (2, 2), (1, 1)]]
    got = common.pick_sample(finished, 3)
    assert [len(p) for p, _ in got] == [5, 4, 30, 1]        # 0, 3, 6 and the longest (index 4)
    assert [len(p) for p, _ in common.pick_sample(finished, 3, seed=2**31 + 3)] == [3, 30, 2]   # from index 2
    assert [len(p) for p, _ in common.pick_sample(finished[:3], 3)] == [5, 9]
    assert common.pick_sample(finished, 1) == finished
    assert common.pick_sample([], 8) == []


@pytest.mark.parametrize("root, workload, module", [
    (FIX, "tiny.chat", serve), (FIX_LATENT, "tiny-latent.docqa", serve_latent_moe)])
def test_both_drivers_take_their_sample_by_the_one_rule(monkeypatch, root, workload, module):
    """Either driver's run reaches ``common.pick_sample`` once, with the
    cell's ``every``, and the reference sees exactly what it returned."""
    calls, seen = [], []
    pick = common.pick_sample

    def recording(finished, every, seed=0):
        calls.append((len(finished), every, seed))
        return pick(finished, every, seed)[:2]

    monkeypatch.setattr(common, "pick_sample", recording)
    if module is serve:
        reference = serve.reference_gaps
        monkeypatch.setattr(serve, "reference_gaps", lambda c, s, sample, **kw: (
            seen.append(len(sample)), reference(c, s, sample, **kw))[1])
    else:
        numbers = serve_latent_moe.reference_numbers
        monkeypatch.setattr(serve_latent_moe, "reference_numbers", lambda c, s, sample, **kw: (
            seen.append(len(sample)), numbers(c, s, sample, **kw))[1])
    assert not hasattr(serve_latent_moe, "pick_sample")
    line = run.execute(workload, 2**31 + 21, 1.0, False, devices=jax.devices()[:1], root=root)
    cell = json.loads((root / "workloads" / f"{workload}.json").read_text())
    assert calls == [(calls[0][0], cell["compare"]["every"], 2**31 + 21)]
    assert calls[0][0] > 2 and seen == [2]
    assert line["attempted"] == 20


# -- the profiler is stopped after the loop has drained ----------------------------

def test_stop_trace_comes_after_the_last_tick_and_traced_s_is_t1_less_t0(monkeypatch, tmp_path):
    order = []
    turn = serve.Loop.turn

    def counting_turn(self, clock):
        turn(self, clock)
        order.append(("tick", clock()))

    monkeypatch.setattr(serve.Loop, "turn", counting_turn)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: order.append(("start", kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: order.append(("stop", None)))
    _, cell, config = run.load_cell("tiny.chat", FIX)
    # Offered far above what four slots complete: first tokens are still
    # owed at the close, so the loop runs on past it, as a loaded cell's.
    cell = dict(cell, rate_per_s=400.0)
    result = serve.run(cell, config, seed=2**31 + 21, seconds=1.5, trace_dir=str(tmp_path))
    kinds = [k for k, _ in order]
    assert kinds.count("start") == 1 and kinds.count("stop") == 1
    assert order[kinds.index("start")][1]["profiler_options"].python_tracer_level == 0
    assert kinds[-1] == "stop"                        # after the LAST tick
    first = kinds.index("start")
    assert "tick" in kinds[:first]                    # the stretch is the window's END
    host = result["host"]
    t0, t1 = host["stretch"]
    assert host["traced_s"] == pytest.approx(t1 - t0)
    assert host["traced_s"] == pytest.approx(1.0, abs=0.3)       # the cell's trace_seconds
    # The close of the window, not the loop's end, ends the stretch ...
    ticks_after_close = [t for k, t in order if k == "tick" and t is not None and t >= 1.5]
    assert ticks_after_close, "the loop ran on past the close, traced"
    # ... and the gaps handed to itl_gap_ms.p95 are the stretch's alone.
    assert host["itl_gap_ms"] and all(0 < g < 1000 for g in host["itl_gap_ms"])
    assert "prefill_phase_ms" not in host
    assert result["failed"] == 0 and result["end_to_end"]["tpot_p90_ms"] > 0


# -- the trace: cut to the stretch, idle gaps named by the program's spans ------------

def hand_trace():
    us = 1000
    return {
        "/device:TPU:0": {
            "XLA Ops": [["early fusion", 0, 5 * us], ["wave fusion", 110 * us, 20 * us],
                        ["chunk fusion", 160 * us, 30 * us], ["straddle fusion", 195 * us, 10 * us],
                        ["late fusion", 300 * us, 5 * us]],
            "XLA Modules": [["jit_decode_wave(1)", 110 * us, 20 * us],
                            ["jit_decode_wave(1)", 300 * us, 5 * us]],
        },
        "/host:CPU": {"main": [
            ["bench/stretch", 100 * us, 100 * us],
            ["bench/step", 100 * us, 95 * us], ["serve/tick", 101 * us, 93 * us],
            ["serve/dispatch", 102 * us, 6 * us], ["serve/harvest_wait", 130 * us, 40 * us],
            ["serve/tick", 290 * us, 30 * us], ["bench/submit", 95 * us, 4 * us],
        ]},
    }


def test_cut_to_span_keeps_what_starts_in_the_stretch_and_shortens_the_straddler():
    cut = trace.cut_to_span(hand_trace())
    ops = cut["/device:TPU:0"]["XLA Ops"]
    assert [o[0] for o in ops] == ["wave fusion", "chunk fusion", "straddle fusion"]
    assert ops[-1] == ["straddle fusion", 195_000, 5_000]
    assert cut["/device:TPU:0"]["XLA Modules"] == [["jit_decode_wave(1)", 110_000, 20_000]]
    names = [n for n, _, _ in trace.host_spans(cut)]
    assert "bench/stretch" not in names and names.count("serve/tick") == 1
    assert trace.busy_s(cut) == pytest.approx((20 + 30 + 5) * 1e-6)
    # A trace with no such span (the training driver's) is left as it is.
    plain = {"/device:TPU:0": {"XLA Ops": [["a fusion", 0, 10]]}}
    assert trace.cut_to_span(plain) is plain


def test_idle_gaps_name_the_innermost_program_span():
    cut = trace.cut_to_span(hand_trace())
    gaps = dict(trace.idle_gaps(cut, window=(100_000, 200_000)))
    # 100-110: dispatch covers 6 of 10 us -> serve/dispatch; 130-160: inside
    # harvest_wait (innermost of bench/step > serve/tick > it); 190-195:
    # serve/tick's tail (it ends at 194: 4 of 5 us; bench/step covers all 5
    # but is the longer span).
    assert gaps == {"serve/harvest_wait": pytest.approx(30e-6),
                    "serve/dispatch": pytest.approx(10e-6),
                    "serve/tick": pytest.approx(5e-6)}
    assert "bench/step" not in gaps
    assert trace.idle_gaps({"/device:TPU:0": {"XLA Ops": [["a fusion", 0, 10], ["b fusion", 30, 10]]}}) == [
        ["host/other", pytest.approx(20e-9)]]


def test_the_program_spans_prefixes_are_kept():
    assert set(trace.HOST_SPAN_PREFIXES) == {"bench/", "serve/", "train/", "data/"}
    kept = trace.host_spans({"/host:CPU": {"t": [
        ["serve/tick", 0, 5], ["train/wave", 0, 5], ["data/next", 1, 1], ["bench/step", 0, 9],
        ["PjitFunction(f)", 0, 3], ["compile/backend", 0, 2]]}})
    assert sorted(n for n, _, _ in kept) == ["bench/step", "data/next", "serve/tick", "train/wave"]


def test_program_spans_take_the_stretch_the_driver_names():
    from benchmark.readers import program_spans

    rows = [("serve/tick", 10.0, 10.02, None, {}, 1, 1, None),
            ("serve/tick", 19.99, 20.01, None, {}, 1, 2, None),
            ("serve/tick", 20.5, 20.52, None, {}, 1, 3, None)]       # past the close, still recorded
    ctx = {"spans": rows, "host": {"stretch": (10.0, 20.0)}, "cell_seconds": 51.0}
    spans, stretch = program_spans.load(ctx)
    assert stretch == (10.0, 20.0)
    assert len(program_spans.select(ctx, spans, stretch, ["serve/tick"], "stretch")) == 2
    assert program_spans.window_open(ctx, stretch) == pytest.approx(-31.0)
    # Without the key: from the first tick recorded to the last, as before.
    assert program_spans.load({"spans": rows})[1] == (10.0, 20.52)


# -- the spread arithmetic -----------------------------------------------------

def test_spreads_by_the_contracts_rule_and_by_the_issues():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    # statistics.quantiles(n=4), exclusive: Q1 = 100.75, Q3 = 108.0.
    assert spread.quartile_spread(values) == pytest.approx((108.0 - 100.75) / 102.5)
    assert spread.without_farthest(values) == [102.0, 103.0, 101.0, 104.0, 100.0]
    assert spread.range_spread(values) == pytest.approx(4.0 / 102.5)
    assert spread.quartile_spread([5.0]) == 0.0 and spread.range_spread([5.0]) == 0.0
    per_set = {1: [(s, v) for s, v in enumerate(values)],
               2: [(s, v) for s, v in enumerate([100.0, 100.5, 101.0, 101.5, 102.0, 102.5])]}
    row = spread.summarise(per_set, bound=0.02)
    assert row["medians"] == {1: 102.5, 2: 101.25}
    tight = (spread.quartile_spread(values[:5]) + spread.quartile_spread([100.5, 101.0, 101.5, 102.0, 100.0])) / 2
    assert row["tight"] == pytest.approx(tight)
    assert row["too_tight"] is (tight > 0.01) and row["ratio"] == pytest.approx(tight / 0.02)
    assert row["loose"] >= spread.quartile_spread(values)
    assert spread.summarise(per_set, bound=0.01)["too_loose"] is False      # 1 % is never too loose


def test_read_sets_takes_metrics_and_the_statistics_beside_them(tmp_path):
    path = tmp_path / "sets.jsonl"
    rows = [{"set": k, "seed": 7 + i, "line": {
        "correct": True, "metrics": {"tpot_p90_ms": {"value": 17.0 + i + k, "unit": "ms"}},
        "end_to_end": {"tpot_p90_ms": 17.0 + i + k, "itl_p95_ms": 21.0 + i}}}
        for k in (1, 2) for i in range(3)]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    stats = spread.read_sets(path)
    assert stats["tpot_p90_ms"] == {1: [(7, 18.0), (8, 19.0), (9, 20.0)], 2: [(7, 19.0), (8, 20.0), (9, 21.0)]}
    assert stats["itl_p95_ms"][2] == [(7, 21.0), (8, 22.0), (9, 23.0)]
    assert set(spread.bounds()) == {m["name"] for m in BENCH["end_to_end"]}


# -- the files' consistency --------------------------------------------------------

def test_the_three_cells_and_the_serve_cells_end_to_end_metrics():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2-medium.train", "gpt2-large.chat-busy", "dots.vlm1.inst-ep16.docqa"]
    assert all(w["chips"] == 1 for w in BENCH["workloads"]) and BENCH["run_seconds"] == 51
    serve_cells = cells[1:]
    for cell in serve_cells:
        assert {m["name"] for m in run.metrics_of(BENCH, "end_to_end", cell)} == {
            "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    busy = json.loads((HERE / "workloads" / "gpt2-large.chat-busy.json").read_text())
    assert busy["engine"] == {"max_slots": 32, "block_len": 16, "prefill_chunk": 128,
                              "decode_waves_per_dispatch": 1}
    assert (busy["traffic"], busy["traffic_file"], busy["driver"]) == ("chat-busy", "chat", "serve")
    assert busy["lead_in_s"] == 15 and busy["trace_seconds"] == 5 and busy["compare"]["every"] == 8
    assert busy["rate_per_s"] == pytest.approx(20.5, abs=2.5)


def test_every_cell_and_every_per_layer_metric_has_its_files_and_its_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert {p.stem for p in (HERE / "workloads").glob("*.json")} == cells
    for w in BENCH["workloads"]:
        cell = json.loads((HERE / "workloads" / f"{w['name']}.json").read_text())
        assert cell["name"] == w["name"] and (ROOT / configs[cell["config"]]["file"]).is_file()
        assert (HERE / "traffic" / f"{cell['traffic_file']}.json").is_file()
        importlib.import_module(f"benchmark.drivers.{cell['driver']}").run
        assert set(cell["limits"]) and all(v > 0 for v in cell["limits"].values())
    reports = {m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert {p.stem for p in (HERE / "metrics").glob("*.json")} == set(per_layer)
    for name, m in per_layer.items():
        spec = json.loads((HERE / "metrics" / f"{name}.json").read_text())
        assert spec["name"] == name and spec["moves"] == m["moves"] and spec["layer"] == m["layer"]
        assert callable(importlib.import_module(f"benchmark.readers.{spec['reader']}").read)
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert m["moves"] in reports and set(m["workloads"]) <= reports[m["moves"]], name
    assert per_layer["itl_gap_ms.p95"]["moves"] == "tpot_p90_ms"
    assert per_layer["paged_decode_roofline"]["moves"] == per_layer["decode_pages_live_share"]["moves"]
    assert "prefill_phase_ms.p50" not in per_layer
    # Beside every kernel's roofline, the whole step's share of the peak
    # moves the same end-to-end metric in the same cells.
    whole = {(m["moves"], cell) for n, m in per_layer.items() if "mfu" in n.split(".")
             for cell in m["workloads"]}
    for name, m in per_layer.items():
        if name.endswith("_roofline"):
            assert {(m["moves"], cell) for cell in m["workloads"]} <= whole, name


def test_no_file_names_the_retired_cell_or_the_retired_metric():
    """``gpt2-large.chat`` as a cell and ``itl_p95_ms`` as a METRIC are gone
    from the benchmark's data files (the statistic ``itl_p95_ms`` is still
    computed beside ``tpot_p90_ms`` and printed, in ``drivers/common.py``)."""
    data = [ROOT / "BENCHMARK.json"] + [
        p for d in ("workloads", "metrics", "traffic", "configs") for p in (HERE / d).glob("*.json")]
    for path in data:
        text = path.read_text()
        assert "itl_p95_ms" not in text, path
        assert '"gpt2-large.chat"' not in text and "gpt2-large.chat." not in text, path
    for path in list(HERE.rglob("*.py")) + list(HERE.rglob("*.sh")) + [HERE / "README.md"]:
        if path.name == Path(__file__).name:
            continue
        text = path.read_text().replace("gpt2-large.chat-busy", "").replace("gpt2-large.chat-sat", "")
        assert "gpt2-large.chat" not in text, path
