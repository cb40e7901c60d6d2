"""The trace reduction on a small recorded trace (a v5e probe: five runs
each of a matmul program and of a flash-attention program, taken with
``benchmark/tools/trace_probe.py``), against numbers worked out by hand."""

import json
from pathlib import Path

import pytest

from benchmark import trace
from benchmark.readers import idle_share, module_time

TRACE = json.loads(
    (Path(__file__).resolve().parent / "fixtures" / "recorded_trace.json").read_text()
)
# By hand, from the file: no two operations overlap, so busy is their sum:
# 5 x 13 (copy-start) + 57508 (copy-done) + 457832 (fusion) + 520991 (flash).
BUSY_NS = 65 + 57508 + 457832 + 520991
WINDOW_NS = (94461015 + 104194) - 40280402


def test_busy_and_idle_share_match_the_hand_count():
    assert trace.device_planes(TRACE) == ["/device:TPU:0"]
    assert trace.window_ns(TRACE) == (40280402, 94565209)
    assert trace.busy_s(TRACE) == pytest.approx(BUSY_NS / 1e9, rel=1e-12)
    ctx = {"trace": TRACE, "host": {"traced_s": WINDOW_NS / 1e9}}
    assert idle_share.read(ctx) == pytest.approx(100 * (1 - BUSY_NS / WINDOW_NS))
    assert idle_share.read({"trace": TRACE, "host": {}}) is None


def test_module_times_match_the_hand_count():
    got = trace.module_durations_s(TRACE, r"^jit_step_probe\(")
    assert sorted(round(d * 1e9) for d in got) == [103038, 103046, 103072, 103123, 103160]
    ctx = {"trace": TRACE}
    assert module_time.read(ctx, pattern=r"^jit_step_probe\(", q=50) == pytest.approx(0.103072)
    assert module_time.read(ctx, pattern=r"^jit_flash_probe\(", q=50) == pytest.approx(0.104166)
    # A reader that finds nothing to read returns nothing, never 0.
    assert module_time.read(ctx, pattern=r"^jit_absent\(", q=50) is None


def test_top_ops_and_idle_gaps():
    ops = dict(trace.top_ops(TRACE))
    assert ops["flash_probe.1 custom-call"] == pytest.approx(520991e-9)
    assert ops["convolution_tanh_fusion fusion"] == pytest.approx(457832e-9)
    gaps = dict(trace.idle_gaps(TRACE))
    # All but a few nanoseconds of the idle time falls into the host's sleeps.
    assert gaps["bench/data_wait"] == pytest.approx((WINDOW_NS - BUSY_NS) / 1e9, rel=1e-4)
    assert sum(gaps.values()) == pytest.approx((WINDOW_NS - BUSY_NS) / 1e9, rel=1e-9)


def test_union_merges_nested_and_overlapping_operations():
    nested = {"/device:TPU:0": {"XLA Ops": [
        ["while while", 0, 100], ["body fusion", 10, 20], ["tail fusion", 90, 30],
        ["far fusion", 200, 50],
    ]}}
    assert trace.busy_s(nested) == pytest.approx(170e-9)
    two = dict(nested, **{"/device:TPU:1": {"XLA Ops": [["x fusion", 0, 70]]}})
    assert trace.busy_s(two) == pytest.approx((170 + 70) / 2 * 1e-9)


def test_op_names_are_cut_to_name_and_opcode():
    line = ("%flash_probe.1 = (bf16[2,1024,1024]{2,1,0:T(8,128)(2,1)}, "
            "f32[2,4,4,1024]{3,2,1,0:T(4,128)}) custom-call(bf16[2,1024,3072] %x)")
    assert trace.short_op_name(line) == "flash_probe.1 custom-call"
    assert trace.short_op_name("no equals sign") == "no equals sign"


def test_summary_lines_sum_by_opcode_stem_and_program():
    lines = trace.summary_lines(TRACE)
    assert any(l.startswith("opcode") and l.endswith(" custom-call") for l in lines)
    assert any(l.endswith("x1 flash_probe custom-call") for l in lines)
    assert any(l.endswith("x5 jit_step_probe") for l in lines)
