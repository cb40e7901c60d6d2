"""rocket_tpu.tune — tuner core, table lookup, parity gates (ISSUE 10).

CPU tier-1 coverage of the autotuner's correctness spine:

* table round-trip + longest-prefix device-kind matching (the same
  ``utils/perf._longest_prefix`` semantics as the peak tables);
* fallback-to-default when no entry matches — kernels must be BITWISE
  behavior-identical to an untuned checkout (the acceptance criterion
  for CPU / unknown devices);
* parity-rejection: a deliberately-wrong candidate is rejected by the
  sweep no matter how fast it is;
* fwd/bwd numerical parity of every checked-in table config vs the
  defaults (interpret mode) — plus the same check over representative
  candidate blocks so the guarantee is exercised even while the shipped
  tables are empty;
* the CI table gate: clean on the shipped tables, firing on the
  seeded-bad fixture (unknown device kind, illegal causal blocks, stale
  bucket).
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu import tune
from rocket_tpu.tune.space import TUNE_SPACES
from rocket_tpu.tune.tuner import TuneCase, check_parity, sweep_case

REPO = Path(__file__).resolve().parent.parent
BAD_TABLE_DIR = str(REPO / "tests" / "fixtures" / "tune" / "bad_table")

FLASH_SHAPE = {"t": 256, "d": 64, "h": 2, "h_kv": 2, "causal": True}


@pytest.fixture
def table_dir(tmp_path, monkeypatch):
    """Point the runtime lookup at a scratch table dir for the test."""
    monkeypatch.setenv("ROCKET_TPU_TUNE_DIR", str(tmp_path))
    tune.reset_table_cache()
    tune.reset_lookup_log()
    yield str(tmp_path)
    tune.reset_table_cache()


def _flash_entry(device_kind, config, shape=FLASH_SHAPE, dtype="float32"):
    return {
        "device_kind": device_kind,
        "dtype": dtype,
        "shape": dict(shape),
        "shape_bucket": TUNE_SPACES["flash_fwd"].bucket(shape),
        "config": dict(config),
        "speedup": 1.1,
    }


# -- table round-trip + lookup ------------------------------------------------


def test_table_round_trips(table_dir):
    entry = _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128})
    path = tune.write_table("flash_fwd", [entry], configs_dir=table_dir)
    table = json.loads(Path(path).read_text())
    assert table["kernel"] == "flash_fwd" and table["version"] == 1
    assert table["entries"] == [entry]
    assert tune.load_table("flash_fwd", table_dir,
                           use_cache=False)["entries"] == [entry]


def test_lookup_longest_prefix_device_kind(table_dir):
    """"TPU v5 lite" must beat the "TPU v5" family entry for a v5e, the
    family entry must catch future suffixed kinds, and an unmatched kind
    must fall through to None — the utils/perf peak-table semantics."""
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5", {"block_q": 256, "block_k": 256}),
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)

    def config_for(kind):
        return tune.get_config(
            "flash_fwd", shape=FLASH_SHAPE, dtype=jnp.float32,
            device_kind=kind,
        )

    assert config_for("TPU v5 lite")["block_q"] == 128
    assert config_for("TPU v5p slice")["block_q"] == 256  # family prefix
    assert config_for("TPU v4") is None
    assert config_for("cpu") is None


def test_lookup_exact_bucket_and_dtype(table_dir):
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    hit = tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                          dtype=jnp.float32, device_kind="TPU v5 lite")
    assert hit == {"block_q": 128, "block_k": 128}
    # Different T bucket / dtype -> default fallback, never a near-match.
    other = dict(FLASH_SHAPE, t=512)
    assert tune.get_config("flash_fwd", shape=other, dtype=jnp.float32,
                           device_kind="TPU v5 lite") is None
    assert tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                           dtype=jnp.bfloat16,
                           device_kind="TPU v5 lite") is None


def test_lookup_disabled_by_env(table_dir, monkeypatch):
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    monkeypatch.setenv("ROCKET_TPU_TUNE", "0")
    assert tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                           dtype=jnp.float32,
                           device_kind="TPU v5 lite") is None


def test_priced_device_kind_override(table_dir):
    """The auditors' seam: inside the context every lookup resolves
    against the audited target's kind, not the local device's."""
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    assert tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                           dtype=jnp.float32) is None  # local kind: cpu
    with tune.priced_device_kind("TPU v5 lite"):
        hit = tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                              dtype=jnp.float32)
    assert hit == {"block_q": 128, "block_k": 128}


def test_lookup_log_records_provenance(table_dir):
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    tune.reset_lookup_log()
    tune.get_config("flash_fwd", shape=FLASH_SHAPE, dtype=jnp.float32,
                    device_kind="TPU v5 lite")
    tune.get_config("moe_gmm", shape={"m": 1024, "k": 256, "n": 512},
                    dtype=jnp.bfloat16, device_kind="TPU v5 lite")
    tune.get_config("moe_gmm", shape={"m": 1024, "k": 256, "n": 512},
                    dtype=jnp.bfloat16, device_kind="TPU v5 lite")
    summary = tune.lookup_log_summary()
    assert len(summary) == 2  # deduplicated
    by_kernel = {r["kernel"]: r for r in summary}
    assert by_kernel["flash_fwd"]["source"] == "table"
    assert by_kernel["flash_fwd"]["config"] == {"block_q": 128,
                                                "block_k": 128}
    assert by_kernel["moe_gmm"]["source"] == "default"


def test_unknown_kernel_raises():
    with pytest.raises(KeyError, match="unknown kernel"):
        tune.get_config("nope", shape={}, dtype=jnp.float32)


# -- fallback behavior identity ----------------------------------------------


def test_no_table_is_bitwise_identical_to_explicit_defaults():
    """The acceptance criterion: with no table entry (CPU / unknown
    device) the table-resolving default path must be BITWISE identical
    to pinning today's hand-picked blocks explicitly."""
    from rocket_tpu.ops.flash_native import flash_fused

    rng = np.random.default_rng(0)
    fused = jnp.asarray(
        rng.normal(size=(2, 256, 3 * 2 * 64)).astype(np.float32)
    )
    tuned_path = flash_fused(fused, 2, causal=True, interpret=True)
    pinned = flash_fused(fused, 2, causal=True, block_q=512, block_k=512,
                         interpret=True)
    assert np.array_equal(np.asarray(tuned_path), np.asarray(pinned))

    def loss(fn_kwargs):
        def inner(f):
            return (flash_fused(f, 2, causal=True, interpret=True,
                                **fn_kwargs) ** 2).sum()
        return jax.grad(inner)(fused)

    g_tuned = loss({})
    g_pinned = loss({"block_q": 512, "block_k": 512})
    assert np.array_equal(np.asarray(g_tuned), np.asarray(g_pinned))


def test_table_entry_changes_resolved_blocks(table_dir):
    """A matching entry actually steers the kernel: an illegal tuned
    config (causal bq != bk) must blow up in the kernel entry's loud
    check, proving the table value reached the launch path."""
    bad = _flash_entry("TPU v5 lite", {"block_q": 256, "block_k": 128},
                       shape=FLASH_SHAPE)
    # write_table is schema-agnostic; the CI gate is what rejects this.
    tune.write_table("flash_fwd", [bad], configs_dir=table_dir)
    from rocket_tpu.ops.flash_attention import resolve_tuned_blocks

    with tune.priced_device_kind("TPU v5 lite"):
        blocks = resolve_tuned_blocks(
            256, 64, 2, 2, jnp.float32, True, None, None, None, None
        )
    # _resolve_blocks clamps causal blocks to the aligned minimum rather
    # than launching an illegal kernel; the table's values were read.
    assert blocks[:2] == (128, 128)


def test_explicit_fwd_blocks_suppress_bwd_table(table_dir):
    """Pinning the forward blocks must pin the backward too (pre-tuner
    behavior): a flash_bwd table entry must NOT override an explicitly
    pinned call — A/Bs and repro tests run exactly the blocks they
    name."""
    from rocket_tpu.ops.flash_attention import resolve_tuned_blocks

    tune.write_table("flash_bwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    with tune.priced_device_kind("TPU v5 lite"):
        pinned = resolve_tuned_blocks(
            256, 64, 2, 2, jnp.float32, True, 256, 256, None, None
        )
        unpinned = resolve_tuned_blocks(
            256, 64, 2, 2, jnp.float32, True, None, None, None, None
        )
    assert pinned == (256, 256, 256, 256)   # bwd rides the pinned fwd
    assert unpinned[2:] == (128, 128)       # unpinned bwd reads the table


def test_tuning_disabled_context(table_dir):
    tune.write_table("flash_fwd", [
        _flash_entry("TPU v5 lite", {"block_q": 128, "block_k": 128}),
    ], configs_dir=table_dir)
    with tune.tuning_disabled():
        assert tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                               dtype=jnp.float32,
                               device_kind="TPU v5 lite") is None
    assert tune.get_config("flash_fwd", shape=FLASH_SHAPE,
                           dtype=jnp.float32,
                           device_kind="TPU v5 lite") is not None


# -- the sweep: parity rejection ---------------------------------------------


def _fake_case(wrong_moment_scale):
    """A synthetic fused_bn case whose "separate" candidate multiplies
    the output by ``wrong_moment_scale`` — a deliberately-wrong (and
    instant, i.e. "fast") kernel the sweep must reject on parity."""
    x = jnp.asarray(np.linspace(0.0, 1.0, 64, dtype=np.float32))

    def build():
        def run(config):
            moments = (config or {}).get("moments", "stacked")
            scale = 1.0 if moments == "stacked" else wrong_moment_scale
            return x * scale

        return run

    return TuneCase(name="bn/fake", kernel="fused_bn", shape={"c": 64},
                    dtype="float32", build=build)


def test_sweep_rejects_wrong_candidate():
    report = sweep_case(_fake_case(1.5), iters=1)
    assert report.winner is None
    (result,) = [r for r in report.results
                 if r.config == {"moments": "separate"}]
    assert not result.parity_ok
    assert result.max_err > 1.0
    assert result.mean_us is None  # rejected BEFORE timing enters ranking


def test_sweep_accepts_parity_equal_candidate():
    report = sweep_case(_fake_case(1.0), iters=1, min_speedup=1.0)
    (result,) = [r for r in report.results
                 if r.config == {"moments": "separate"}]
    assert result.parity_ok and result.mean_us is not None


def test_sweep_baseline_is_explicit_default_and_table_blind(table_dir):
    """The baseline must be the TuneSpace default passed EXPLICITLY, and
    the sweep must run with table lookups disabled — on a previously
    tuned device the old winner must not stand in for the default."""
    seen = []

    def build():
        def run(config):
            assert config is not None  # never None-resolved
            # Any lookup inside the sweep must miss (tuning_disabled).
            assert tune.get_config(
                "fused_bn", shape={"c": 64}, dtype=jnp.float32,
                device_kind="TPU v5 lite",
            ) is None
            seen.append(dict(config))
            return jnp.zeros((4,))

        return run

    tune.write_table("fused_bn", [{
        "device_kind": "TPU v5 lite", "dtype": "float32",
        "shape": {"c": 64}, "shape_bucket": "c64",
        "config": {"moments": "separate"},
    }], configs_dir=table_dir)
    case = TuneCase(name="bn/blind", kernel="fused_bn", shape={"c": 64},
                    dtype="float32", build=build)
    sweep_case(case, iters=1)
    assert seen[0] == {"moments": "stacked"}  # the space default, explicit


def test_check_parity_tolerances():
    a = np.ones((8, 8), np.float32)
    ok, err = check_parity(a, a, "float32")
    assert ok and err == 0.0
    ok, _ = check_parity(a, a * (1 + 5e-6), "float32")
    assert ok  # within f32 tolerance
    ok, err = check_parity(a, a * 1.01, "float32")
    assert not ok and err > 1.0
    ok, _ = check_parity(a, a * 1.01, "bfloat16")
    assert ok  # bf16 tolerance is looser
    ok, err = check_parity(a, np.full_like(a, np.nan), "bfloat16")
    assert not ok  # non-finite candidate is always rejected


# -- checked-in config parity (interpret mode) --------------------------------


def _run_flash(entry_shape, dtype, fwd_cfg, bwd_cfg):
    """fwd output + grads of the native-layout kernel at an entry's
    shape under the given block configs (None = defaults)."""
    from rocket_tpu.ops.flash_native import flash_bthd

    t, d = entry_shape["t"], entry_shape["d"]
    h, h_kv = entry_shape["h"], entry_shape["h_kv"]
    causal = entry_shape.get("causal", True)
    b = 1 if t > 1024 else 2
    rng = np.random.default_rng(1)
    q2 = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32)
                     ).astype(dtype)
    k2 = jnp.asarray(rng.normal(size=(b, t, h_kv * d)).astype(np.float32)
                     ).astype(dtype)
    v2 = k2 * 0.5
    kwargs = {}
    if fwd_cfg:
        kwargs.update(block_q=fwd_cfg["block_q"], block_k=fwd_cfg["block_k"])
    if bwd_cfg:
        kwargs.update(bwd_block_q=bwd_cfg["block_q"],
                      bwd_block_k=bwd_cfg["block_k"])

    def loss(q, k, v):
        out = flash_bthd(q, k, v, h, h_kv, causal=causal, interpret=True,
                         **kwargs)
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True
    )(q2, k2, v2)
    return (out,) + grads


@pytest.mark.parametrize("blocks", [(128, 128), (256, 256)])
def test_candidate_blocks_fwd_bwd_parity(blocks):
    """Representative tuner candidates must match the default blocks'
    fwd outputs AND grads within dtype tolerance — the guarantee every
    shipped table entry rides (exercised even while tables are empty)."""
    shape = {"t": 256, "d": 64, "h": 2, "h_kv": 2, "causal": True}
    ref = _run_flash(shape, jnp.float32, None, None)
    cfg = {"block_q": blocks[0], "block_k": blocks[1]}
    for fwd_cfg, bwd_cfg in ((cfg, None), (None, cfg), (cfg, cfg)):
        got = _run_flash(shape, jnp.float32, fwd_cfg, bwd_cfg)
        ok, err = check_parity(ref, got, "float32")
        assert ok, (fwd_cfg, bwd_cfg, err)


def test_every_checked_in_flash_config_is_parity_clean():
    """Every entry the repo SHIPS must pass the fwd/bwd parity check in
    interpret mode — a hand-edited or stale table row that changes
    numerics fails tier-1, not just the tuner's own gate."""
    checked = 0
    for kernel in ("flash_fwd", "flash_bwd"):
        table = tune.load_table(kernel, tune.CONFIGS_DIR, use_cache=False)
        assert table is not None, f"{kernel}.json must ship"
        for entry in table["entries"]:
            shape, dtype = entry["shape"], entry["dtype"]
            if shape["t"] > 1024:
                continue  # interpret-mode cost; covered on-device
            ref = _run_flash(shape, dtype, None, None)
            cfg = entry["config"]
            got = _run_flash(
                shape, dtype,
                cfg if kernel == "flash_fwd" else None,
                cfg if kernel == "flash_bwd" else None,
            )
            ok, err = check_parity(ref, got, dtype)
            assert ok, (kernel, entry, err)
            checked += 1
    # With empty tables this loop is vacuous by design (no wins found on
    # this hardware yet); the candidate-parity test above keeps the
    # machinery honest either way.
    assert checked >= 0


def test_decode_attention_rows_parity():
    """The tunable write-back tile height must not change decode output
    or the written caches."""
    from rocket_tpu.ops.decode_attention import decode_attention

    rng = np.random.default_rng(2)
    b, hq, h_kv, d, t = 2, 4, 2, 64, 128
    q = jnp.asarray(rng.normal(size=(b, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, h_kv, d)).astype(np.float32))
    v_new = k_new * 0.5
    k_cache = jnp.asarray(
        rng.normal(size=(b, h_kv, t, d)).astype(np.float32)
    )
    v_cache = k_cache * 0.5
    outs = {}
    for rows in (8, 16, 32):
        outs[rows] = decode_attention(
            q, k_new, v_new, k_cache, v_cache, jnp.int32(37),
            interpret=True, rows=rows,
        )
    for rows in (16, 32):
        for ref, got in zip(outs[8], outs[rows]):
            np.testing.assert_allclose(
                np.asarray(ref), np.asarray(got), rtol=1e-6, atol=1e-6
            )
    with pytest.raises(ValueError, match="rows"):
        decode_attention(q, k_new, v_new, k_cache, v_cache,
                         jnp.int32(1), interpret=True, rows=12)


def test_bn_moments_variants_parity():
    """Both moment forms of the fused BN compute the same statistics:
    outputs, stats and grads must agree."""
    from rocket_tpu.nn.layers import _bn_train

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 8, 8, 4)).astype(np.float32) + 1.0)
    scale = jnp.ones((4,), jnp.float32) * 1.5
    bias = jnp.ones((4,), jnp.float32) * 0.25

    def run(moments):
        def loss(x, scale, bias):
            y, stats = _bn_train(x, scale, bias, 1e-5, moments)
            return (y ** 2).sum(), (y, stats)

        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(x, scale, bias)
        return aux + grads

    ok, err = check_parity(run("stacked"), run("separate"), "float32")
    assert ok, err


def test_gmm_tiling_resolution(table_dir):
    from rocket_tpu.nn.moe import _gmm_tiling

    # No table: the hand-picked 512s, clamped to the operand dims.
    assert _gmm_tiling(16384, 768, 3072, jnp.bfloat16) == (512, 512, 512)
    assert _gmm_tiling(256, 768, 3072, jnp.bfloat16) == (256, 512, 512)
    shape = {"m": 16384, "k": 768, "n": 3072}
    tune.write_table("moe_gmm", [{
        "device_kind": "TPU v5 lite", "dtype": "bfloat16",
        "shape": shape,
        "shape_bucket": TUNE_SPACES["moe_gmm"].bucket(shape),
        "config": {"tile_m": 1024, "tile_k": 256, "tile_n": 512},
    }], configs_dir=table_dir)
    with tune.priced_device_kind("TPU v5 lite"):
        assert _gmm_tiling(16384, 768, 3072, jnp.bfloat16) == \
            (1024, 256, 512)


# -- the CI table gate --------------------------------------------------------


def test_shipped_tables_validate_clean():
    assert tune.validate_tables(tune.CONFIGS_DIR) == []


def test_bad_table_fixture_fires_the_gate():
    """The seeded-bad fixture must trip every gate clause: unknown
    device kind, illegal config (causal block mismatch), stale bucket."""
    problems = "\n".join(tune.validate_tables(BAD_TABLE_DIR))
    assert "unknown device kind 'TPU v99 imaginary'" in problems
    assert "causal requires block_q == block_k" in problems
    assert "does not match shape" in problems


def test_gate_flags_missing_and_stale_tables(tmp_path):
    problems = "\n".join(tune.validate_tables(str(tmp_path)))
    for kernel in TUNE_SPACES:
        assert f"{kernel}.json: missing" in problems
    for kernel in TUNE_SPACES:
        tune.write_table(kernel, [], configs_dir=str(tmp_path))
    (tmp_path / "ghost_kernel.json").write_text("{}")
    problems = "\n".join(tune.validate_tables(str(tmp_path)))
    assert "no TuneSpace named 'ghost_kernel'" in problems


def test_check_table_cli_exit_codes():
    from rocket_tpu.tune.__main__ import main

    assert main(["--check-table"]) == 0
    assert main(["--check-table", "--table-dir", BAD_TABLE_DIR]) == 1


def test_spaces_reject_vmem_overflow_and_enumerate_legal():
    """Candidate enumeration prunes the VMEM budget and the causal
    diagonal constraint before anything is timed."""
    from rocket_tpu.utils.perf import device_spec

    spec = device_spec("TPU v5 lite")
    space = TUNE_SPACES["flash_fwd"]
    shape = {"t": 4096, "d": 64, "h": 16, "h_kv": 16, "causal": True}
    candidates = space.candidates(shape, spec, "bfloat16")
    assert {"block_q": 512, "block_k": 512} in candidates
    for config in candidates:
        assert config["block_q"] == config["block_k"]  # causal diagonal
    # 1024-row blocks at qw = 16*64 = 1024 lanes double-buffer to 16 MiB
    # of streamed blocks alone — over the v5e budget once the f32
    # accumulator scratch is added.
    assert {"block_q": 1024, "block_k": 1024} not in candidates
    assert space.violations(
        {"block_q": 640, "block_k": 640}, shape, spec, "bfloat16"
    )  # not a candidate value


PAGED_SHAPE = {"s": 8, "mb": 16, "bl": 16, "hkv": 4, "hq": 4, "d": 64}


def test_paged_decode_space_axes_and_legality():
    """The paged_decode TuneSpace carries REAL axes (the `variant`
    placeholder is retired): a structural impl axis and the streamed
    block_kv tile, with sublane/divisibility legality."""
    from rocket_tpu.utils.perf import device_spec

    space = TUNE_SPACES["paged_decode"]
    assert set(space.axes) == {"impl", "block_kv"}
    assert "variant" not in space.axes
    assert set(space.axes["impl"]) == {"pallas", "xla"}
    spec = device_spec("TPU v5 lite")
    candidates = space.candidates(PAGED_SHAPE, spec, "bfloat16")
    # bf16 sublane is 16 and bl=16: a tile is whole pages — 16 rows and
    # every multiple on the axis — and the XLA path is enumerated once,
    # at the default.
    assert candidates == [
        {"block_kv": kv, "impl": "pallas"}
        for kv in (16, 32, 64, 128, 256, 512)
    ] + [{"block_kv": 512, "impl": "xla"}]
    f32 = space.candidates(PAGED_SHAPE, spec, "float32")
    assert {"block_kv": 8, "impl": "pallas"} in f32      # half a page
    assert space.violations(
        {"impl": "pallas", "block_kv": 12}, PAGED_SHAPE, spec, "float32"
    )  # not an axis member
    assert space.violations(
        {"impl": "pallas", "block_kv": 32}, dict(PAGED_SHAPE, bl=48), spec,
        "float32",
    )  # neither divides bl=48 nor is a multiple of it
    assert any("128" in v for v in space.violations(
        {"impl": "pallas", "block_kv": 16}, dict(PAGED_SHAPE, hkv=3), spec,
        "float32",
    ))  # rows of 192 lanes: the kernel cannot copy them out of HBM
    # Default = untuned behavior: the fused kernel, the tile height the
    # op derives (512 rows here; 128 at the chat cell's 2 x 1280 lanes).
    assert space.default(PAGED_SHAPE) == {"impl": "pallas", "block_kv": 512}
    assert space.default(
        {"s": 32, "mb": 64, "bl": 16, "hkv": 20, "hq": 20, "d": 64}
    )["block_kv"] == 128
    assert "s" in space.shape_keys and "hq" in space.shape_keys


def test_paged_decode_table_resolution(table_dir):
    """A table entry must steer the live dispatch: pin impl=xla for the
    exact serve shape and paged_attention must take the gather path on
    a geometry the kernel supports."""
    from rocket_tpu.ops.paged_attention import paged_attention

    shape = {"s": 2, "mb": 2, "bl": 16, "hkv": 2, "hq": 2, "d": 64}
    tune.write_table("paged_decode", [{
        "device_kind": "TPU v5 lite", "dtype": "float32",
        "shape": shape,
        "shape_bucket": TUNE_SPACES["paged_decode"].bucket(shape),
        "config": {"impl": "xla", "block_kv": 16},
    }], configs_dir=table_dir)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 1, 2, 64)).astype(np.float32))
    kn = jnp.asarray(rng.normal(size=(2, 1, 2, 64)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(1, 5, 16, 2 * 64)).astype(np.float32))
    table = jnp.asarray(np.asarray([[1, 2], [3, 4]], np.int32))
    pos = jnp.asarray([3, 17], jnp.int32)
    valid = jnp.ones((2,), jnp.int32)
    with tune.priced_device_kind("TPU v5 lite"):
        out_t, _, _ = paged_attention(q, kn, kn * 0.5, kp, kp * 0.25,
                                      table, pos, valid)
    out_x, _, _ = paged_attention(q, kn, kn * 0.5, kp, kp * 0.25,
                                  table, pos, valid, impl="xla")
    np.testing.assert_array_equal(np.asarray(out_t), np.asarray(out_x))
    log = tune.lookup_log_summary()
    hits = [r for r in log if r["kernel"] == "paged_decode"
            and r["source"] == "table"]
    assert hits and hits[0]["config"]["impl"] == "xla"


def test_paged_decode_cases_mirror_serve_shapes():
    """The sweep catalog carries the serve-engine wave shapes (charlm ==
    bench serve_summary / serve_audit charlm; gpt2_geom the GQA target)
    plus a CPU smoke case, and the smoke sweep is parity-clean."""
    from rocket_tpu.tune.tuner import load_cases

    cases = load_cases()
    charlm = cases["paged/charlm"]
    assert charlm.kernel == "paged_decode"
    assert charlm.shape == {"s": 8, "mb": 16, "bl": 16, "hkv": 4,
                            "hq": 4, "d": 64}
    assert charlm.dtype == "bfloat16"
    gpt2 = cases["paged/gpt2_geom"]
    assert gpt2.shape["bl"] == 32 and gpt2.shape["hq"] == 12
    assert cases["paged/smoke"].smoke


@pytest.mark.slow
def test_paged_decode_smoke_sweep_parity_clean(table_dir):
    """The full CPU smoke sweep of the paged case: every candidate
    (both impls, interpret mode) must pass parity against the default."""
    from rocket_tpu.tune.tuner import load_cases

    report = sweep_case(load_cases()["paged/smoke"], iters=1)
    assert report.default_config["impl"] == "pallas"
    assert report.results, "no candidates enumerated"
    for result in report.results:
        assert result.error is None, result.error
        assert result.parity_ok, (result.config, result.max_err)


def test_update_tables_merges_other_device_kinds(tmp_path):
    """Re-tuning one device kind must not drop another's rows."""
    from rocket_tpu.tune.tuner import CandidateResult, CaseReport, \
        update_tables

    keep = _flash_entry("TPU v4", {"block_q": 256, "block_k": 256})
    tune.write_table("flash_fwd", [keep], configs_dir=str(tmp_path))
    case = TuneCase(name="flash_fwd/x", kernel="flash_fwd",
                    shape=FLASH_SHAPE, dtype="float32", build=lambda: None)
    report = CaseReport(case=case, device_kind="TPU v5 lite")
    report.default_config = {"block_q": 512, "block_k": 512}
    report.default_us = 100.0
    report.winner = CandidateResult(
        config={"block_q": 128, "block_k": 128}, mean_us=80.0,
    )
    update_tables([report], configs_dir=str(tmp_path))
    entries = tune.load_table("flash_fwd", str(tmp_path),
                              use_cache=False)["entries"]
    kinds = {e["device_kind"] for e in entries}
    assert kinds == {"TPU v4", "TPU v5 lite"}
    new = [e for e in entries if e["device_kind"] == "TPU v5 lite"][0]
    assert new["speedup"] == 1.25 and new["config"]["block_q"] == 128


# -- structural axes (ISSUE 14) ----------------------------------------------


def test_fused_conv_space_axes_and_inert_pinning():
    """impl/schedule are structural; impl=reference pins the launch
    axes inert so the cross product never times byte-identical
    programs."""
    from rocket_tpu.utils.perf import device_spec

    space = TUNE_SPACES["fused_conv"]
    assert set(space.axes) == {"impl", "schedule", "block_rows"}
    assert set(space.structural) == {"impl", "schedule"}
    shape = {"n": 262144, "c": 64}
    assert space.default(shape) == {
        "impl": "reference", "schedule": "twopass", "block_rows": 512,
    }
    spec = device_spec("TPU v5 lite")
    candidates = space.candidates(shape, spec, "bfloat16")
    refs = [c for c in candidates if c["impl"] == "reference"]
    assert refs == [space.default(shape)]  # one reference candidate
    assert {"impl": "pallas", "schedule": "stats_xla",
            "block_rows": 256} in candidates
    # block_rows must divide N for the pallas variant.
    assert space.violations(
        {"impl": "pallas", "schedule": "twopass", "block_rows": 512},
        {"n": 1000, "c": 64}, spec, "bfloat16",
    )


def test_block_attn_space_axes_and_inert_pinning():
    from rocket_tpu.utils.perf import device_spec

    space = TUNE_SPACES["block_attn"]
    assert set(space.axes) == {"impl", "epilogue", "block_b"}
    assert set(space.structural) == {"impl", "epilogue"}
    shape = {"b": 64, "t": 256, "d": 256, "h": 4}
    spec = device_spec("TPU v5 lite")
    candidates = space.candidates(shape, spec, "bfloat16")
    refs = [c for c in candidates if c["impl"] == "reference"]
    assert refs == [space.default(shape)]
    fused = [c for c in candidates if c["impl"] == "fused"]
    assert {c["epilogue"] for c in fused} == {"fused", "separate"}
    assert space.violations(
        {"impl": "fused", "epilogue": "fused", "block_b": 8},
        {"b": 4, "t": 256, "d": 256, "h": 4}, spec, "bfloat16",
    )  # block_b does not divide B


def test_moe_gmm_impl_axis():
    """moe_gmm grew the structural impl axis: 'gmm' stays the default
    (bitwise pre-existing behavior) and 'fused' pins tile_k inert."""
    from rocket_tpu.utils.perf import device_spec

    space = TUNE_SPACES["moe_gmm"]
    assert space.structural == ("impl",)
    shape = {"m": 16384, "k": 768, "n": 3072}
    assert space.default(shape)["impl"] == "gmm"
    spec = device_spec("TPU v5 lite")
    candidates = space.candidates(shape, spec, "bfloat16")
    fused = [c for c in candidates if c["impl"] == "fused"]
    assert fused and all(c["tile_k"] == 512 for c in fused)
    assert space.violations(
        {"impl": "fused", "tile_m": 512, "tile_k": 256, "tile_n": 512},
        shape, spec, "bfloat16",
    )  # tile_k inert for the fused variant


def test_stale_structural_winner_fails_loudly(tmp_path):
    """A table entry pinning a variant that no longer exists must be a
    named gate failure, not a silent fallback."""
    shape = {"b": 64, "t": 256, "d": 256, "h": 4}
    for kernel in TUNE_SPACES:
        tune.write_table(kernel, [{
            "device_kind": "TPU v5 lite", "dtype": "bfloat16",
            "shape": shape,
            "shape_bucket": TUNE_SPACES["block_attn"].bucket(shape),
            "config": {"impl": "whole_block_v0", "epilogue": "fused",
                       "block_b": 1},
        }] if kernel == "block_attn" else [], configs_dir=str(tmp_path))
    problems = "\n".join(tune.validate_tables(str(tmp_path)))
    assert "stale structural winner" in problems
    assert "whole_block_v0" in problems


def test_bad_table_fixture_flags_stale_structural_winner():
    problems = "\n".join(tune.validate_tables(BAD_TABLE_DIR))
    assert "stale structural winner" in problems


def test_sweep_rejects_wrong_fast_structural_variant():
    """The true-positive leg the whole structural search rests on: a
    deliberately wrong-but-fast variant in a test-only TuneSpace must
    be discarded by the parity gate BEFORE timing enters the ranking."""
    from rocket_tpu.tune.space import TuneSpace

    space = TuneSpace(
        kernel="test_fake_variant",
        axes={"impl": ("reference", "wrongfast")},
        shape_keys=("n",),
        default=lambda shape: {"impl": "reference"},
        structural=("impl",),
    )
    TUNE_SPACES[space.kernel] = space
    try:
        x = jnp.asarray(np.linspace(0.0, 1.0, 128, dtype=np.float32))

        def build():
            def run(config):
                if (config or {}).get("impl") == "wrongfast":
                    return x * 1.5
                return x

            return run

        case = TuneCase(name="fake/wrongfast", kernel="test_fake_variant",
                        shape={"n": 128}, dtype="float32", build=build)
        report = sweep_case(case, iters=1, min_speedup=1.0)
        (bad,) = [r for r in report.results
                  if r.config == {"impl": "wrongfast"}]
        assert not bad.parity_ok
        assert bad.mean_us is None  # rejected before timing
        assert report.winner is None
    finally:
        del TUNE_SPACES[space.kernel]


def test_tables_summary_reports_structural_wins(tmp_path):
    shape = {"b": 64, "t": 256, "d": 256, "h": 4}
    for kernel in TUNE_SPACES:
        tune.write_table(kernel, [{
            "device_kind": "TPU v5 lite", "dtype": "bfloat16",
            "shape": shape,
            "shape_bucket": TUNE_SPACES["block_attn"].bucket(shape),
            "config": {"impl": "fused", "epilogue": "separate",
                       "block_b": 2},
            "speedup": 1.42, "case": "block_attn/charlm",
        }] if kernel == "block_attn" else [], configs_dir=str(tmp_path))
    summary = tune.tables_summary(str(tmp_path))
    (win,) = summary["structural_wins"]
    assert win["kernel"] == "block_attn"
    assert win["variant"] == {"impl": "fused", "epilogue": "separate"}
    assert win["speedup"] == 1.42
    assert summary["kernels"]["block_attn"]["structural_axes"] == [
        "impl", "epilogue",
    ]
    # Launch-config-only tuning (the default impl) is NOT a structural
    # win.
    tune.write_table("block_attn", [{
        "device_kind": "TPU v5 lite", "dtype": "bfloat16",
        "shape": shape,
        "shape_bucket": TUNE_SPACES["block_attn"].bucket(shape),
        "config": {"impl": "reference", "epilogue": "fused",
                   "block_b": 1},
    }], configs_dir=str(tmp_path))
    assert tune.tables_summary(str(tmp_path))["structural_wins"] == []


def test_list_cli_marks_structural_axes(capsys):
    from rocket_tpu.tune.__main__ import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "impl*=" in out            # structural axes starred
    assert "structural axes" in out
    assert "block_attn" in out and "fused_conv" in out
    assert "fused_conv/smoke" in out  # case catalog carries the smokes


def test_check_alias_matches_check_table():
    from rocket_tpu.tune.__main__ import main

    assert main(["--check"]) == 0
    assert main(["--check", "--table-dir", BAD_TABLE_DIR]) == 1
