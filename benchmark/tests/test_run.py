"""The rest of a run behind the look for a chip, at a tiny size on the CPU:
sound runs come out correct, and each fault planted under the timed path
comes out NOT correct."""

from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import common

FIX = Path(__file__).resolve().parent / "fixtures"


def execute(workload, seed=2**31 + 21):
    return run.execute(workload, seed, 1.0, False, devices=jax.devices()[:1], root=FIX)


def test_a_sound_training_run_is_correct_and_prints_the_contracts_line():
    line = execute("tiny.train")
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks" and set(line["checks"]) == {
        "loss1_rel", "loss2_rel", "loss3_rel", "grad1_leaf_gap", "delta3_leaf_gap"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from rocket_tpu.core.module import Module

    build = Module._build_train_step

    def broken(self, *args, **kwargs):
        build(self, *args, **kwargs)
        step = self._train_step
        self._train_step = jax.jit(lambda state, batch: (state, step(state, batch)[1]))

    monkeypatch.setattr(Module, "_build_train_step", broken)
    line = execute("tiny.train")
    assert line["correct"] is False
    # Adam's first moment stays nought and nothing moves: both gaps read 1.
    assert line["checks"]["grad1_leaf_gap"]["value"] == pytest.approx(1.0)
    assert line["checks"]["delta3_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from rocket_tpu.models.transformer import TransformerLM

    apply = TransformerLM.apply

    def half(self, variables, batch, **kwargs):
        batch = dict(batch)
        batch["tokens"] = batch["tokens"][: batch["tokens"].shape[0] // 2]
        return apply(self, variables, batch, **kwargs)

    monkeypatch.setattr(TransformerLM, "apply", half)
    line = execute("tiny.train")
    assert line["correct"] is False
    failed = [n for n, c in line["checks"].items() if not c["value"] <= c["limit"]]
    assert "grad1_leaf_gap" in failed


def test_a_sound_serving_run_is_correct():
    line = execute("tiny.chat")
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] == 20 and line["failed"] == 0
    assert list(line["checks"]) == ["token_gap_max"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from rocket_tpu.serve.engine import SlotEngine

    harvest = SlotEngine.harvest

    def altered(self, handle):
        toks, done, emitted = harvest(self, handle)
        return (np.asarray(toks) + 1) % 512, done, emitted

    monkeypatch.setattr(SlotEngine, "harvest", altered)
    line = execute("tiny.chat")
    assert line["correct"] is False
    assert line["checks"]["token_gap_max"]["value"] > line["checks"]["token_gap_max"]["limit"]


def test_the_fp8_control_fails_the_serving_comparison():
    """The control at a size a test run can hold: the reference's own
    greedy choice under fp8 matmuls lies further below the float32 best
    than the limit the fixture cell sets."""
    import json

    from benchmark.drivers import serve
    from benchmark.reference import gpt2 as ref

    config = json.loads((FIX / "configs" / "tiny.json").read_text())
    rng = np.random.default_rng(5)
    sample = [(rng.integers(0, 512, size=40, dtype=np.int32),
               rng.integers(0, 512, size=32, dtype=np.int32)) for _ in range(4)]
    gaps = serve.reference_gaps(config, 2**31 + 21, sample, span=32,
                                quant=ref.fp8, control=True)
    limit = json.loads((FIX / "workloads" / "tiny.chat.json").read_text())["limits"]
    assert max(gaps) > 3 * limit["token_gap_max"]


def test_judge_refuses_a_missing_number_a_missing_limit_and_a_nan():
    ok, checks = common.judge({"a": 0.1}, {"a": 0.2})
    assert ok and checks == {"a": {"value": 0.1, "limit": 0.2}}
    assert not common.judge({"a": 0.3}, {"a": 0.2})[0]
    assert not common.judge({"a": float("nan")}, {"a": 0.2})[0]
    assert not common.judge({"a": 0.1}, {"a": 0.2, "b": 1.0})[0]
    assert not common.judge({"a": 0.1, "b": 0.1}, {"a": 0.2})[0]


def test_worst_leaf_gap_measures_against_the_larger_of_leaf_and_median():
    ref_norms = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, at = common.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-3}, ref_norms)
    # c's gap is measured against the median leaf (1.0), not its own 1e-9.
    assert at == "a" and gap == pytest.approx(0.1)
    gap, at = common.worst_leaf_gap({"a": 1.0, "b": 0.0, "c": 0.0}, ref_norms, skip={"c"})
    assert at == "b" and gap == pytest.approx(1.0)


def test_percentile_is_linear_between_order_statistics():
    assert common.percentile([1, 2, 3, 4, 5], 50) == 3
    assert common.percentile([0, 10], 90) == pytest.approx(9.0)
    assert common.percentile([7], 95) == 7
