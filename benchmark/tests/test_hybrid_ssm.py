"""The state-space hybrid configuration's share of the benchmark: its counts
by hand, its reference against a Python loop, its readers on hand-made
events and on nothing, its cell end to end at a tiny size on the CPU —
sound runs come out correct, and a part of the mathematics dropped under
the timed path comes out NOT correct."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counts_ssm as counts
from benchmark import run
from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.readers import op_share, ssm_roofline
from benchmark.reference import jamba_lm as ref

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures_hybrid"
REAL = json.loads((ROOT / "benchmark/configs/AI21-Jamba2-3B.json").read_text())
TINY = json.loads((FIX / "configs/tiny-hybrid.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- counts --------------------------------------------------------------------

def test_ssm_step_count_by_hand():
    # One state layer, 3 decoded tokens: h (5120 x 16 float32) read and
    # written, the convolution's tail (3 x 5120 bfloat16) read and written,
    # the rows u, z, y (bfloat16), delta (float32), B and C (16 float32).
    work = counts.ssm_step(REAL, 3)
    per_token = 2 * 327_680 + 2 * 30_720 + 5120 * 10 + 128
    assert per_token == 768_128
    assert work["bytes"] == 3 * per_token == 2_304_384
    assert work["flops"] == 7 * 3 * 5120 * 16 == 1_720_320
    assert counts.state_layers(REAL) == 26


def test_ssm_scan_count_by_hand():
    # Two chunks of 512 and 100 real rows: each reads and writes h once and
    # moves its real rows.
    work = counts.ssm_scan(REAL, [512, 100])
    assert work["bytes"] == 612 * (5120 * 10 + 128) + 2 * 2 * 327_680 == 32_723_456
    assert work["flops"] == 7 * 612 * 5120 * 16 == 350_945_280


def test_serve_flops_and_parameters_by_hand():
    mixer = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert (mixer, attn) == (41_123_840, 13_762_560)
    per_token = 26 * mixer + 2 * attn + 28 * 3 * 2560 * 8192 + 2560 * 65536
    assert per_token == 3_026_124_800 == ref.matmul_params_per_token(REAL)
    recurrence = 26 * 5120 * (7 * 16 + 2 * 4 + 4)
    assert recurrence == 16_506_880 == ref.recurrence_flops_per_token(REAL)
    # Positions 0 and 9 attend 1 + 10 rows in each of the 2 attention layers.
    assert ref.serve_flops(REAL, [0, 9]) == \
        2 * (2 * per_token + recurrence) + 4 * 2 * 2560 * 11 == 12_137_738_240


def test_the_whole_model_is_3_03_billion_parameters():
    shapes = jax.eval_shape(
        lambda k: ref.make_params(k, REAL, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_029_337_472
    program = jax.eval_shape(lambda p: ref.program_params(p, REAL), shapes)
    assert sum(a.size for a in jax.tree.leaves(program)) == 3_029_337_472
    model_shapes = jax.eval_shape(
        lambda k: _model(REAL).init(k)["params"], jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, program) == \
        jax.tree.map(lambda a: a.shape, model_shapes)


#: The catalog's ``config`` of ``AI21-Jamba2-3B`` (model-configs guide,
#: ``architectures.jsonl``), as published.
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
}


def test_the_configuration_holds_every_published_key_and_cuts_nothing():
    assert {k: REAL[k] for k in PUBLISHED} == PUBLISHED
    assert REAL["reduced"] == []
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == "AI21-Jamba2-3B"][0]
    assert entry["source"] == REAL["source"] and entry["reduced"] == []
    tc = driver.transformer_config(REAL)
    assert (tc.num_layers, tc.cache_layers, tc.state_layers) == (28, 2, 26)
    assert [i for i in range(28) if not tc.is_state_layer(i)] == [7, 21]
    assert tc.kv_pool_lanes == (128, 128)
    assert tc.slot_state_shapes == ((26, (16, 5120), "float32"),
                                    (26, (15360,), "bfloat16"))


# -- the reference -------------------------------------------------------------

def test_the_reference_mixer_is_the_recurrence_written_out():
    """``ref.mamba`` against a Python loop over tokens, channels as numpy
    arrays: the convolution tap by tap, ``h`` updated in place."""
    p = jax.tree.map(np.asarray, ref.layer_params(jax.random.key(3), TINY, False)["mamba"])
    x = np.asarray(jax.random.normal(jax.random.key(4), (9, 64)), np.float64)
    p = jax.tree.map(lambda a: a.astype(np.float64), p)
    di, n, r, eps = 128, 16, 8, TINY["rms_norm_eps"]
    silu = lambda a: a / (1 + np.exp(-a))
    norm = lambda a, w: a / np.sqrt(np.mean(a * a) + eps) * w
    h = np.zeros((di, n))
    past = [np.zeros(di)] * 3
    want = []
    for t in range(9):
        uz = x[t] @ p["w_in"]
        u_in, z = uz[:di], uz[di:]
        window = past + [u_in]
        u = silu(sum(p["conv_w"][k] * window[k] for k in range(4)) + p["conv_b"])
        past = window[1:]
        dbc = u @ p["w_x"]
        dt, b, c = (norm(dbc[:r], p["dt_norm"]), norm(dbc[r:r + n], p["b_norm"]),
                    norm(dbc[r + n:], p["c_norm"]))
        delta = np.log1p(np.exp(dt @ p["w_dt"] + p["dt_bias"]))
        h = np.exp(delta[:, None] * -np.exp(p["a_log"])) * h + (delta * u)[:, None] * b[None, :]
        y = h @ c + p["d_skip"] * u
        want.append((y * silu(z)) @ p["w_out"])
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a.astype(np.float32)), p)
        got, state = ref.mamba(p32, jnp.asarray(x, jnp.float32), TINY)
        # The same 9 rows and 3 of padding: the state stops where told.
        padded = jnp.concatenate([jnp.asarray(x, jnp.float32), jnp.ones((3, 64))])
        _, stopped = ref.mamba(p32, padded, TINY, length=9)
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(state, h, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(stopped, state, atol=1e-6)     # 12 rows a matmul, not 9


def test_the_state_carried_in_bfloat16_moves_the_reference():
    params = ref.make_params(jax.random.key(0), TINY, jnp.float32)
    tokens = jnp.arange(40) % 96
    full = ref.logits(params, tokens, TINY)
    low = ref.logits(params, tokens, TINY, state_dtype=jnp.bfloat16)
    assert 1e-4 < float(jnp.abs(full - low).max()) < 0.5


# -- the readers ---------------------------------------------------------------

def _ctx(ops, modules=(), spans=(), decode=()):
    trace = {"/device:TPU:0": {
        "XLA Ops": [[n, s, d] for n, s, d in ops],
        "XLA Modules": [[n, s, d] for n, s, d in modules],
    }}
    return {"trace": trace, "host": {"traced_decode": list(decode)},
            "config": REAL, "peaks": PEAKS, "device_kind": "TPU v5 lite",
            "spans": list(spans), "cell_seconds": 4.0}


def _metric(name):
    spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
    reader = {"ssm_roofline": ssm_roofline, "op_share": op_share}[spec["reader"]]
    return lambda ctx: reader.read(ctx, **spec["args"])


def test_ssm_step_roofline_on_hand_made_events():
    # Two kernel calls of 50 us for 4 decoded tokens: 26 layers x 4 x
    # 768,128 bytes at 819 GB/s = 97.5 us of 100.
    ops = [("ssm_step.3 custom-call", 0, 50_000), ("ssm_step.3 custom-call", 90_000, 50_000),
           ("fusion.1 fusion", 60_000, 10_000)]
    got = _metric("ssm_step_roofline")(_ctx(ops, decode=[300, 400, 500, 600]))
    assert got == pytest.approx(100 * 26 * 4 * 768_128 / 819e9 / 100e-6)
    assert _metric("ssm_step_roofline")(_ctx(ops)) is None            # no token
    assert _metric("ssm_step_roofline")(_ctx(ops[2:], decode=[1])) is None  # no event


def test_ssm_scan_roofline_reads_the_chunks_rows_from_the_programs_spans():
    tick = ("serve/tick", 0.0, 4.0, None, {}, 1, 0, None)
    chunks = [("serve/prefill_enqueue", 1.0 + i, 1.1 + i, 0,
               {"rid": 1, "start": 512 * i, "valid": v}, 1, 1 + i, None)
              for i, v in enumerate((512, 100))]
    late = ("serve/prefill_enqueue", 9.0, 9.1, None, {"rid": 2, "start": 0, "valid": 512}, 1, 9, None)
    ops = [("ssm_scan.7 custom-call", 0, 2_000_000)]
    ctx = _ctx(ops, spans=[tick, *chunks, late])
    assert ssm_roofline.chunk_rows(ctx) == [512, 100]
    got = _metric("ssm_scan_roofline")(ctx)
    assert got == pytest.approx(100 * 26 * 32_723_456 / 819e9 / 2e-3)
    assert _metric("ssm_scan_roofline")(_ctx(ops, spans=[tick])) is None
    assert _metric("ssm_scan_roofline")(_ctx([], spans=[tick, *chunks])) is None


def test_the_shares_divide_kernel_time_by_program_time():
    ops = [("ssm_step.3 custom-call", 0, 300), ("ssm_scan.7 custom-call", 0, 100),
           ("fusion.9 fusion", 0, 999)]
    modules = [("jit_decode_wave(123)", 0, 1000), ("jit_prefill_chunk_fn(45)", 0, 400)]
    ctx = _ctx(ops, modules)
    assert _metric("ssm_wave_share")(ctx) == pytest.approx(30.0)
    assert _metric("ssm_chunk_share")(ctx) == pytest.approx(25.0)
    # A program without the named kernels (the parent) has nothing to read.
    assert _metric("ssm_wave_share")(_ctx(ops[2:], modules)) is None
    assert _metric("ssm_chunk_share")(_ctx(ops, modules[:1])) is None


def test_the_new_metrics_and_the_cell_are_appended_to_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = "AI21-Jamba2-3B.reason"
    assert [w["name"] for w in bench["workloads"]].count(cell) == 1
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", cell)}
    assert {"ssm_step_roofline", "ssm_scan_roofline", "ssm_wave_share",
            "ssm_chunk_share", "serve_step.mfu", "serve_busy.mfu",
            "device_idle.serve", "recompiles.serve"} <= listed
    assert "paged_decode_roofline" not in listed
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end", cell)} == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    _, loaded, config = run.load_cell(cell)
    assert loaded["engine"]["max_model_len"] == 4096 and config is not None


# -- the cell end to end -------------------------------------------------------

def _model(config):
    from rocket_tpu.models.transformer import TransformerLM

    return TransformerLM(driver.transformer_config(config))


def execute(seed=2**31 + 21):
    return run.execute("tiny-hybrid.reason", seed, 1.0, False,
                       devices=jax.devices()[:1], root=FIX)


def test_a_sound_serving_run_is_correct():
    line = execute()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] == 20 and line["failed"] == 0
    assert list(line["checks"]) == ["state_gap", "token_gap_max", "token_gap_mean"]


def test_the_probe_alone_reads_the_state_gap_a_whole_run_reads():
    """The probe is served alone before any traffic, so its number does not
    depend on what a loaded machine finished: the control tool's
    ``--probe-only`` reads it without a window."""
    from benchmark.tools import control_hybrid_ssm as control

    seed = 2**31 + 21
    _, cell, config = run.load_cell("tiny-hybrid.reason", root=FIX)
    alone = control.probe_only(cell, config, seed, span=32)
    assert alone["numbers"]["state_gap"] == execute(seed)["checks"]["state_gap"]["value"]
    assert len(alone["state_gaps"]) == 2 and alone["numbers"]["token_gap_max"] is None


def test_a_state_carried_in_bfloat16_fails_the_state_gap_alone(monkeypatch):
    """The control ``tools/control_hybrid_ssm`` runs on the chip: ``h``
    rounded to bfloat16 after every token. The tokens hardly move; the
    state's own number does."""
    import rocket_tpu.nn.ssm as ssm
    from benchmark.tools import control_hybrid_ssm as control

    def forget():       # a recurrence traced before keeps the h it was traced with
        ssm.ssm_scan.clear_cache()
        ssm.ssm_step.clear_cache()

    monkeypatch.setattr(ssm, "_advance", ssm._advance)      # restored after
    control.low_state_program()
    forget()
    try:
        checks = execute()["checks"]
    finally:
        forget()
    assert checks["state_gap"]["value"] > 1.3 * checks["state_gap"]["limit"], checks
    assert checks["token_gap_mean"]["value"] <= checks["token_gap_mean"]["limit"], checks


@pytest.mark.parametrize("fault", ["inner_norms", "stale_state", "fp8_activations"])
def test_a_part_of_the_mathematics_dropped_is_not_correct(monkeypatch, fault):
    if fault == "inner_norms":
        # Plain Mamba: dt, B and C used as x_proj gives them.
        import rocket_tpu.nn.ssm as ssm

        init = ssm.MambaMixer.__init__

        def plain(self, *a, **k):
            init(self, *a, **k)
            self.norms = {}

        monkeypatch.setattr(ssm.MambaMixer, "__init__", plain)
    elif fault == "stale_state":
        # A slot's state is never reset: a request inherits what the
        # slot's last one left.
        import rocket_tpu.nn.ssm as ssm

        apply_state = ssm.MambaMixer.apply_state

        def stale(self, params, x, state, positions, valid, **kw):
            return apply_state(self, params, x, state, positions + 1, valid, **kw)

        monkeypatch.setattr(ssm.MambaMixer, "apply_state", stale)
    else:
        # A lower precision than the file states: the residual stream
        # rounded to fp8 after every state layer — at a scale of each row's
        # own, so that what a request reads does not depend on which other
        # slots a loaded machine ran beside it.
        from rocket_tpu.models.transformer import Block

        stateful = Block.apply_state

        def low(self, *args, **kwargs):
            x, state, counts = stateful(self, *args, **kwargs)
            return jax.vmap(jax.vmap(ref.fp8))(x).astype(x.dtype), state, counts

        monkeypatch.setattr(Block, "apply_state", low)
    line = execute()
    assert line["correct"] is False, (fault, line["checks"])
    # By a margin, whichever requests a loaded machine finished in the window.
    worst = max(c["value"] / c["limit"] for c in line["checks"].values())
    assert worst > 2, (fault, line["checks"])
