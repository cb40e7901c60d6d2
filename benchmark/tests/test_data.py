"""BENCHMARK.json, the data files and the traffic generator."""

import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

from benchmark.traffic import generator

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_generator_is_a_pure_function_of_the_seed_and_keeps_its_clips():
    mix = generator.load_mix("chat")
    kw = dict(rate_per_s=3.0, seconds=45, lead_in_s=10, vocab_size=50257)
    a = generator.requests(mix, 2**31 + 5, **kw)
    b = generator.requests(mix, 2**31 + 5, **kw)
    c = generator.requests(mix, 7, **kw)
    assert [(x.due_s, x.max_new_tokens) for x in a] == [(x.due_s, x.max_new_tokens) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # The schedule is the mix's own; the seed draws the ids.
    assert [(x.due_s, x.max_new_tokens, len(x.prompt)) for x in a] == [
        (x.due_s, x.max_new_tokens, len(x.prompt)) for x in c]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    other = generator.requests(dict(mix, order_seed=mix["order_seed"] + 1), 7, **kw)
    assert [x.due_s for x in a] != [x.due_s for x in other]
    for x in a + c:
        assert mix["prompt"]["min"] <= len(x.prompt) <= mix["prompt"]["max"]
        assert mix["answer"]["min"] <= x.max_new_tokens <= mix["answer"]["max"]
        assert len(x.prompt) + x.max_new_tokens <= mix["max_total"]
        assert 0 <= x.prompt.min() and x.prompt.max() < 50257
    window = [x for x in a if x.due_s >= 0]
    assert len(window) == 135 and all(0 <= x.due_s < 45 for x in window)
    lead = [x for x in a if x.due_s < 0]
    assert [(x.max_new_tokens, len(x.prompt)) for x in lead] == [
        (x.max_new_tokens, len(x.prompt)) for x in window if x.due_s >= 35]
    assert all(-10 <= x.due_s < 0 for x in a if x.due_s < 0)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    # Another order_seed offers the same set of sizes, in another order.
    window_c = [x for x in other if x.due_s >= 0]
    assert sorted(len(x.prompt) for x in window) == sorted(len(x.prompt) for x in window_c)
    assert sorted(x.max_new_tokens for x in window) == sorted(
        x.max_new_tokens for x in window_c)
    # ... with the large and the small spread evenly: every block of eight
    # neighbours holds one answer from each eighth of the set.
    ranked = sorted(x.max_new_tokens for x in window)
    cuts = [ranked[len(ranked) * s // 8] for s in range(1, 8)]
    for start in range(0, len(window) - 7, 8):
        block = sorted(x.max_new_tokens for x in window[start:start + 8])
        assert all(lo <= cut <= hi for lo, cut, hi in zip(block, cuts, block[1:]))


def test_corpus_is_a_pure_function_of_the_seed():
    mix = generator.load_mix("train")
    a = generator.corpus(mix, 2**31 + 9, 50257, 3)
    assert a.shape == (3 * 8 * 1024,) and a.dtype == np.int32
    assert np.array_equal(a, generator.corpus(mix, 2**31 + 9, 50257, 3))
    assert not np.array_equal(a, generator.corpus(mix, 1, 50257, 3))
    assert 0 <= a.min() and a.max() < 50257


def test_every_name_and_unit_uses_only_the_allowed_characters():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[kind]:
            names.append(entry["name"])
            assert NAME.match(entry["name"]), entry["name"]
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_moves_names_an_end_to_end_metric_its_cells_report():
    cells = [w["name"] for w in BENCH["workloads"]]
    reports = {
        m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]
    }
    assert "setup_s" in reports and reports["setup_s"] == set(cells)
    for m in BENCH["per_layer"]:
        assert m["moves"] in reports, m
        for cell in m.get("workloads", cells):
            assert cell in reports[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in r for r in reports.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])


def test_each_cell_config_metric_and_reader_is_a_file_found_by_name():
    here = ROOT / "benchmark"
    for w in BENCH["workloads"]:
        cell = json.loads((here / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"] == w["traffic"] and cell["why"] == w["why"]
        assert (here / "drivers" / f"{cell['driver']}.py").is_file()
        assert (here / "traffic" / f"{cell['traffic_file']}.json").is_file()
    layers = {}
    for m in BENCH["per_layer"]:
        spec = json.loads((here / "metrics" / f"{m['name']}.json").read_text())
        assert (here / "readers" / f"{spec['reader']}.py").is_file()
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        layers.setdefault(m["layer"], []).append(m["name"])
    for c in BENCH["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["source"] == c["source"] and config["reduced"] == c["reduced"]


def test_the_command_exits_non_zero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "gpt2-medium.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
