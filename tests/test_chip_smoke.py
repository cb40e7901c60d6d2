"""chip_smoke.py rehearsed on the CPU mesh.

The script's phase functions run here at a tiny width (XLA attention, the
pallas kernels interpreted where one is pinned) so that wrong paths,
arguments and control flow are found without chip time. What only the
chip can show — the kernels lowering, the times — is not tested here;
``tests/test_tpu_compile.py`` covers the lowering. The device check in
``main`` stays strict: on this backend it must refuse.
"""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402
from rocket_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    TransformerLM,
)


def tiny_config(vocab_size=512):
    # 4 heads x 32 = 128 lanes: the least pool row the fused kernel copies.
    return TransformerConfig(
        vocab_size=vocab_size, max_seq_len=128, dim=128, num_layers=2,
        num_heads=4, dropout=0.0, activation_dtype="bfloat16", loss_chunk=32,
    )


def test_train_phase_tiny():
    record = chip_smoke.train_phase(
        tiny_config(), batch=4, steps=5, seed=0,
        devices=jax.devices()[:1], require_kernels=False,
    )
    assert chip_smoke.phase_ok(record), record["checks"]
    assert record["checks"]["flash_impl"] is None  # CPU: does not apply
    assert len(record["losses"]) == 5 and len(record["step_s"]) == 4
    assert record["losses"][-1] < record["losses"][0]
    assert record["first_loss_abs_diff"] <= chip_smoke.LOSS_TOL
    json.dumps(record)  # every phase record is one JSON line


@pytest.mark.parametrize("pinned", [None, "pallas"])
def test_serve_phase_tiny(monkeypatch, pinned):
    """Unpinned the CPU takes the XLA gather; pinned, the fused kernel runs
    interpreted — both must reproduce the dense greedy path."""
    if pinned:
        monkeypatch.setenv("ROCKET_TPU_PAGED_DECODE", pinned)
    record = chip_smoke.serve_phase(
        tiny_config(), seed=0, max_slots=4, block_len=16, prefill_chunk=16,
        prompt_lens=(3, 20, 40), max_new_tokens=8, require_kernels=False,
    )
    assert chip_smoke.phase_ok(record), record
    assert [r["prompt_len"] for r in record["requests"]] == [3, 20, 40]
    assert all(r["new_tokens"] == 8 for r in record["requests"])
    assert record["compiled"]["decode_traces"] == 1
    json.dumps(record)


def test_divergence_gap_is_small_between_paths(monkeypatch):
    """The fallback that decides a flipped near-tie: paged (kernel
    interpreted) and dense logits after one prefix agree to bf16."""
    monkeypatch.setenv("ROCKET_TPU_PAGED_DECODE", "pallas")
    model = TransformerLM(tiny_config())
    params = jax.jit(model.init)(jax.random.key(0))["params"]
    gap = chip_smoke.divergence_gap(
        model, params, np.arange(1, 38, dtype=np.int32), block_len=16
    )
    assert 0.0 <= gap <= chip_smoke.LOGIT_TOL


def test_mesh_phases_tiny_on_four_devices():
    """The --chips 4 phase on four of the virtual devices, with a vocab the
    model axis does not divide (as GPT-2's 50257): the table stays
    replicated, everything the rules do split is spread."""
    ref, dp, tp = chip_smoke.mesh_phases(
        tiny_config(vocab_size=513), batch=8, steps=3, seed=0,
        require_kernels=False,
    )
    for record in (ref, dp, tp):
        assert chip_smoke.phase_ok(record), (record["phase"], record["checks"])
    assert ref["mesh"] == {"data": 1}
    assert dp["mesh"] == {"data": 4}
    assert tp["mesh"] == {"data": 2, "model": 2}
    assert dp["checks"]["state_on_every_device"] is True
    assert tp["checks"]["params_spread"] and tp["checks"]["moments_spread"]
    assert tp["params"]["max_share_on_one_device"] == 0.5
    assert tp["max_loss_diff_vs_1dev"] <= chip_smoke.MESH_LOSS_TOL


def test_last_line_format_and_phase_verdict():
    line = chip_smoke.device_line(True, jax.devices())
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": 8},
    }
    assert json.loads(chip_smoke.device_line(False, jax.devices()))["ok"] \
        is False
    # None = the check does not apply here; only False fails a phase.
    assert chip_smoke.phase_ok({"checks": {"a": True, "b": None}})
    assert not chip_smoke.phase_ok({"checks": {"a": True, "b": False}})
    assert chip_smoke.phase_ok(chip_smoke.tune_tables_note())


def test_main_refuses_a_machine_without_a_tpu(capsys):
    """No accelerator: non-zero exit, no result on stdout, no model run."""
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_runtime_places_the_compile_cache(tmp_path):
    """chip_smoke.py and bench.py get their compile cache by building a
    Runtime: a directory named from outside (JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` into this config value at import) is left
    alone; with none named, the cache goes to one fixed, git-ignored
    directory at the root of the checkout."""
    from rocket_tpu.runtime.context import Runtime

    repo = Path(__file__).resolve().parent.parent
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        Runtime(project_dir=str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
        Runtime(project_dir=str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
