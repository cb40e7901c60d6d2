"""MiniCPM-SALA's share of the benchmark: its counts by hand, its
configuration against the catalog's, its readers on hand-made events and
spans, its cell end to end at a tiny size on the CPU — a sound run comes out
correct, the three controls and a part of the mathematics dropped under the
timed path come out NOT correct."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import counts_sala, run
from benchmark.drivers import serve_sala as driver
from benchmark.readers import op_share, served_roofline, sparse_pages
from benchmark.reference import minicpm_sala_lm as ref

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures_sala"
NAME = "MiniCPM-SALA-pp4"
CELL = f"{NAME}.longdoc"
REAL = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- counts --------------------------------------------------------------------

def test_lightning_counts_by_hand():
    # A token: S (32 x 128 x 128 float32, 2 MiB) read and written, q, k, v
    # in bfloat16 and o in float32 (4096 lanes each); 5 operations a state
    # element.
    assert counts_sala.lightning_layers(REAL) == 6 and counts_sala.sparse_layers(REAL) == 2
    step = counts_sala.lightning_step(REAL, 3)
    assert step["bytes"] == 3 * (2 * 2_097_152 + 3 * 8192 + 16_384) == 12_705_792
    assert step["flops"] == 3 * 5 * 32 * 128 * 128 == 7_864_320
    chunk = counts_sala.lightning_chunk(REAL, [1024, 300])
    assert chunk["bytes"] == 1324 * 40_960 + 2 * 2 * 2_097_152
    assert chunk["flops"] == 5 * 1324 * 524_288


def test_sparse_decode_count_by_hand():
    # Two decoded tokens: live contexts 5,000 (position 4,999, dense: 5,000
    # rows) and 20,000 (position 19,999, sparse: 63 whole pages and the 32
    # rows of the last, 4,064); K and V of 2 heads of 128 in bfloat16, q
    # and out of 32 heads of 128; 4 operations a row and query lane.
    assert counts_sala.attended_rows(REAL, 4999) == 5000
    assert counts_sala.attended_rows(REAL, 19_999) == 63 * 64 + 32 == 4064
    work = counts_sala.sparse_decode(REAL, [5000, 20_000])
    assert work["bytes"] == 2 * (2 * 9064 * 256 + 2 * 2 * 4096) == 9_314_304
    assert work["flops"] == 4 * 9064 * 4096 == 148_504_576


def test_sparse_prefill_count_by_hand():
    # A chunk of 2 rows at 8,191 (dense: 8,192 keys) and 8,192 (sparse: 63
    # whole pages and the first row of the last, 4,033); the last row's
    # keys read once.
    work = counts_sala.sparse_prefill(REAL, [(8191, 2)])
    assert work["flops"] == 4 * (8192 + 4033) * 4096
    assert work["bytes"] == 2 * (2 * 2 * 4096 + 2 * 4033 * 256)


def test_serve_flops_by_hand():
    sparse = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
    light = 5 * 4096 * 4096
    assert (sparse, light) == (52_428_800, 83_886_080)
    per_token = 2 * sparse + 6 * light + 8 * 3 * 4096 * 16384 + 4096 * 73448
    assert per_token == 2_519_629_824 == ref.matmul_params_per_token(REAL)
    rule = 5 * 6 * 32 * 128 * 128
    # Positions 0 (1 row, no unit) and 9,000 (sparse: 63 whole pages and
    # 41 rows of the last, 561 complete units scored) in the 2 sparse
    # layers of 32 heads.
    assert ref.attended_rows(REAL, 9000) == 4073 and ref.scored_units(REAL, 9000) == 561
    attention = 2 * 32 * 128 * (4 * (1 + 4073) + 2 * 561)
    assert ref.serve_flops(REAL, [0, 9000]) == 2 * (2 * per_token + rule) + attention


def test_the_chips_share_is_2_821_billion_parameters():
    shapes = jax.eval_shape(
        lambda k: ref.make_params(k, REAL, jnp.bfloat16), jax.random.key(0))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    assert [size(layers[str(i)]) for i in range(8)] == \
        [285_225_216] * 3 + [253_763_840] * 2 + [285_225_216] * 3
    assert size(shapes) == 2_820_569_088
    program = jax.eval_shape(lambda p: ref.program_params(p, REAL), shapes)
    model_shapes = jax.eval_shape(
        lambda k: _model(REAL).init(k)["params"], jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, program) == \
        jax.tree.map(lambda a: a.shape, model_shapes)


#: The published ``config.json`` of MiniCPM-SALA, as the catalog of model
#: configurations holds it.
PUBLISHED_SOURCE = "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
_L, _M = "lightning-attn", "minicpm4"
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [_M] + [_L] * 8 + [_M] + [_L] * 6 + [_M] * 2 + [_L] * 4 + [_M]
                   + [_L] * 6 + [_M] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2,
    "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}


def test_the_configuration_holds_the_catalog_config_and_the_three_cuts():
    cut = {"num_hidden_layers": 8, "max_position_embeddings": 65536}
    assert REAL["source"] == PUBLISHED_SOURCE
    for key, value in PUBLISHED.items():
        if key == "mixer_types":
            assert REAL[key] == value[13:21] and REAL["published"][key] == value
        elif key in cut:
            assert REAL[key] == cut[key] and REAL["published"][key] == value
        else:
            assert REAL[key] == value, key
    assert REAL["reduced"] == ["num_hidden_layers", "mixer_types", "max_position_embeddings"]
    assert set(REAL["reduced_why"]) == set(REAL["reduced"]) == set(REAL["published"])
    assert REAL["layer_indices"] == list(range(13, 21))
    assert REAL["mixer_types"] == ["lightning-attn"] * 3 + ["minicpm4"] * 2 + ["lightning-attn"] * 3
    assert REAL["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                     "topk": 64, "init_blocks": 1, "window_size": 2048,
                                     "dense_len": 8192}
    for key in ("sparse_config", "scoring", "dense_switch", "lightning_decay", "gates_and_norms",
                "mup", "idle_keys", "precision", "weights"):
        assert key in REAL["assumed"], key
    assert "10.5 GB" in REAL["deployment"] and "2,820,569,088" in REAL["deployment"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["source"] == REAL["source"] and entry["reduced"] == REAL["reduced"]
    tc = driver.transformer_config(REAL)
    assert (tc.num_layers, tc.cache_layers, tc.state_layers, tc.sparse_layers) == (8, 2, 6, 2)
    assert tc.kv_pool_lanes == (256, 256)
    assert tc.slot_state_shapes == ((6, (32, 128, 128), "float32"),
                                    (2, (4096, 256), "bfloat16"))
    assert tc.lightning.first_layer == 13 and tc.lightning.published_layers == 32
    assert (tc.embed_scale, tc.logit_divisor) == (12.0, 16.0)
    assert tc.residual_scale == pytest.approx(1.4 / 32 ** 0.5)


# -- the readers ---------------------------------------------------------------

def _ctx(ops, modules=(), decode=(), spans=None):
    trace = {"/device:TPU:0": {
        "XLA Ops": [[n, s, d] for n, s, d in ops],
        "XLA Modules": [[n, s, d] for n, s, d in modules],
    }}
    ctx = {"trace": trace, "host": {"traced_decode": list(decode)},
           "config": REAL, "peaks": PEAKS, "device_kind": "TPU v5 lite",
           "cell_seconds": 4.0}
    ctx["spans"] = spans if spans is not None else []
    return ctx


def _metric(name):
    spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
    reader = {"served_roofline": served_roofline, "op_share": op_share,
              "sparse_pages": sparse_pages}[spec["reader"]]
    return lambda ctx: reader.read(ctx, **spec["args"])


def test_sparse_decode_roofline_on_hand_made_events():
    # Two kernel calls of 10 us for the two tokens above: 2 layers x
    # 9,314,304 bytes at 819 GB/s = 22.7 us of 20.
    ops = [(f"sparse_decode.{i} custom-call", 20_000 * i, 10_000) for i in range(2)]
    ops.append(("lightning_step.3 custom-call", 0, 50_000))
    got = _metric("sparse_decode_roofline")(_ctx(ops, decode=[5000, 20_000]))
    assert got == pytest.approx(100 * 2 * 9_314_304 / 819e9 / 20e-6)
    assert _metric("sparse_decode_roofline")(_ctx(ops)) is None               # no token
    assert _metric("sparse_decode_roofline")(_ctx(ops[2:], decode=[7])) is None


def test_lightning_step_roofline_on_hand_made_events():
    ops = [(f"lightning_step.{i} custom-call", 0, 100_000) for i in range(6)]
    got = _metric("lightning_step_roofline")(_ctx(ops, decode=[100, 200, 300]))
    assert got == pytest.approx(100 * 6 * 12_705_792 / 819e9 / 600e-6)


def test_sparse_prefill_roofline_reads_the_chunks_positions():
    # One chunk of the stretch (start 8,191, 2 rows): 2 layers x 4.16 MB
    # at 819 GB/s = 10.2 us of 50 (its 200 MFLOP a layer take 1.0 us); a
    # chunk enqueued after the stretch counts for nothing.
    from benchmark.readers import chunk_roofline

    rows = [
        ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None),
        ("serve/prefill_enqueue", 0.5, 0.6, 0, {"rid": 1, "start": 8191, "valid": 2}, 1, 1, None),
        ("serve/prefill_enqueue", 3.5, 3.6, None, {"rid": 2, "start": 0, "valid": 9}, 1, 2, None),
    ]
    ops = [(f"sparse_prefill.{i} custom-call", 0, 25_000) for i in range(2)]
    spec = run.load_json(run.HERE / "metrics" / "sparse_prefill_roofline.json")
    got = chunk_roofline.read(_ctx(ops, spans=rows), **spec["args"])
    assert got == pytest.approx(100 * 2 * 2 * (2 * 2 * 4096 + 2 * 4033 * 256) / 819e9 / 50e-6)
    assert chunk_roofline.read(_ctx(ops[:0], spans=rows), **spec["args"]) is None
    assert chunk_roofline.read(_ctx(ops, spans=rows[:1]), **spec["args"]) is None


def test_the_wave_and_chunk_shares_divide_kernel_time_by_the_program():
    ops = [("lightning_step.3 custom-call", 0, 300), ("sparse_decode.1 custom-call", 0, 100),
           ("paged_decode.7 custom-call", 0, 100), ("lightning_chunk.2 custom-call", 0, 50),
           ("sparse_prefill.4 custom-call", 0, 150)]
    modules = [("jit_decode_wave(123)", 0, 1000), ("jit_prefill_chunk_fn(45)", 0, 400)]
    assert _metric("sala_wave_share")(_ctx(ops, modules)) == pytest.approx(40.0)
    assert _metric("sala_chunk_share")(_ctx(ops, modules)) == pytest.approx(50.0)
    # A program without the kernels (the parent) has nothing to read.
    assert _metric("sala_wave_share")(_ctx(ops[2:3], modules)) is None


def test_sparse_pages_read_share_on_recorded_spans():
    # Two waves in the stretch: 128 of 700 and 256 of 900 pages; a record
    # without the sparse ids (another model's) and one after the stretch
    # count for nothing.
    rows = [
        ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None),
        ("serve/decode_pages", 0.5, 0.5, 0, {"tick": 0, "live": 3, "table": 64,
                                             "sparse_pages": 128, "sparse_live": 700}, 1, 1, None),
        ("serve/tick", 1.0, 2.0, None, {}, 1, 2, None),
        ("serve/decode_pages", 1.5, 1.5, 2, {"tick": 1, "live": 5, "table": 64,
                                             "sparse_pages": 256, "sparse_live": 900}, 1, 3, None),
        ("serve/decode_pages", 1.6, 1.6, 2, {"tick": 1, "live": 5, "table": 64}, 1, 4, None),
        ("serve/decode_pages", 3.5, 3.5, None, {"tick": 3, "live": 9, "table": 64,
                                               "sparse_pages": 9, "sparse_live": 9}, 1, 5, None),
    ]
    assert _metric("sparse_pages_read_share")(_ctx([], spans=rows)) == \
        pytest.approx(100.0 * 384 / 1600)
    plain = [r for r in rows if "sparse_live" not in r[4]]
    assert _metric("sparse_pages_read_share")(_ctx([], spans=plain)) is None


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert CELL in [w["name"] for w in bench["workloads"]]
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    new = {"lightning_step_roofline", "lightning_chunk_roofline", "sparse_decode_roofline",
           "sparse_prefill_roofline", "sparse_pages_read_share", "sala_wave_share",
           "sala_chunk_share"}
    assert new | {"serve_step.mfu", "serve_busy.mfu", "device_idle.serve", "recompiles.serve",
                  "setup_compile_s", "dispatch_overlap_share", "decode_pages_live_share"} <= listed
    assert not {"moe_gmm_roofline", "paged_gqa_decode_roofline", "gdn_step_roofline",
                "window_decode_roofline", "kv_chunk_share"} & listed
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    entry = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert len(entry["why"]) <= 200 and "matmul-bound" in entry["why"]
    _, cell, config = run.load_cell(CELL)
    assert cell["engine"] == {"max_slots": 32, "block_len": 64, "prefill_chunk": 1024,
                              "decode_waves_per_dispatch": 1, "max_model_len": 65536}
    assert (cell["traffic"], cell["traffic_file"], cell["lead_in_s"],
            cell["trace_seconds"]) == ("longdoc", "longdoc", 30, 5)
    mix = run.load_json(run.HERE / "traffic" / "longdoc.json")
    assert (mix["prompt"], mix["answer"], mix["max_total"]) == (
        {"dist": "lognormal", "median": 24576, "sigma": 0.6, "min": 4096, "max": 61440},
        {"dist": "lognormal", "median": 512, "sigma": 0.7, "min": 64, "max": 2048}, 65536)
    assert set(cell["limits"]) == {"token_gap_max", "token_gap_mean",
                                   "selection_ambiguous_share", "state_gap"}
    assert cell["limits"]["selection_ambiguous_share"] == 0.2


# -- the cell end to end -------------------------------------------------------

def _model(config):
    from rocket_tpu.models.transformer import TransformerLM

    return TransformerLM(driver.transformer_config(config))


SEED = 2**31 + 21


def execute(seed=SEED):
    return run.execute("tiny-sala.longdoc", seed, 1.0, False,
                       devices=jax.devices()[:1], root=FIX)


@functools.cache
def sound():
    return execute()


def test_a_sound_serving_run_is_correct():
    line = sound()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] == 12 and line["failed"] == 0
    assert list(line["checks"]) == ["selection_ambiguous_share", "state_gap",
                                    "token_gap_max", "token_gap_mean"]


def test_the_three_controls_read_over_their_limits():
    """What ``tools/control_sala`` reads on the chip, at the tiny size: the
    reference in fp8, with sparse layers attending densely and with no
    decay, each in the program's place over a sound run's sample, come out
    NOT correct by the harness's own judgement."""
    from benchmark.tools import control_sala as control

    _, cell, config = run.load_cell("tiny-sala.longdoc", root=FIX)
    result = driver.run(cell, config, seed=SEED, seconds=1.0)
    assert control.judged(result["numbers"], cell["limits"]) == {"correct": True, "over": []}
    margin = float(cell["compare"]["margin"])
    for name, options in control.CONTROLS.items():
        got, state_gap = driver.reference_numbers(
            config, SEED, result["sample"], span=32, probe=result["state_probe"],
            control=True, **options)
        numbers = driver.summarise(got, margin, state_gap)
        verdict = control.judged(numbers, cell["limits"])
        assert verdict["correct"] is False and "token_gap_mean" in verdict["over"], (
            name, numbers)


@pytest.mark.parametrize("fault", ["no_selection", "no_decay", "no_mup"])
def test_a_part_of_the_mathematics_dropped_is_not_correct(monkeypatch, fault):
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.nn import lightning
    from rocket_tpu.ops import paged_attention as pa

    if fault == "no_selection":
        # Sparse layers that never switch: every query attends densely.
        select = pa.select_pages

        def dense_everywhere(q, kc_rows, pos, **kw):
            top, sparse = select(q, kc_rows, pos, **kw)
            return top, jnp.zeros_like(sparse)

        monkeypatch.setattr(pa, "select_pages", dense_everywhere)
    elif fault == "no_decay":
        monkeypatch.setattr(lightning.LightningConfig, "log_decay",
                            lambda self, layer: 0.0 * lightning.alibi_slopes(self.num_heads))
    else:
        init = TransformerLM.__init__

        def plain(self, config, *args, **kw):
            config.residual_scale = 1.0
            init(self, config, *args, **kw)

        monkeypatch.setattr(TransformerLM, "__init__", plain)
    line = execute()
    assert line["correct"] is False, (fault, line["checks"])
    worst = max(c["value"] / c["limit"] for c in line["checks"].values())
    assert worst > 2, (fault, line["checks"])
