"""The window / full attention, routed-expert configuration's share of the
benchmark: its counts by hand, its configuration against the published one, its
readers on hand-made events, its cell end to end at a tiny size on the CPU
— a sound run comes out correct, both controls and a part of the
mathematics dropped under the timed path come out NOT correct."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counts_latent_moe, counts_window, run
from benchmark.drivers import serve_window_moe as driver
from benchmark.readers import op_share, served_roofline
from benchmark.reference import laguna_lm as ref

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures_window"
NAME = "Laguna-XS.2-ep4"
CELL = f"{NAME}.codeagent"
REAL = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- counts --------------------------------------------------------------------

def test_window_decode_count_by_hand():
    # Two decoded tokens, one of a slot under the window (live context 300)
    # and one over it (9,000): 300 + 512 K rows and as many V rows of 8 K/V
    # heads of 128 (2,048 B a row), read once; q and out of 64 heads of 128
    # (bfloat16, 16 KB each) a token; 4 operations a row and query lane.
    assert counts_window.window_layers(REAL) == 6
    work = counts_window.window_decode(REAL, [300, 9000])
    assert work["bytes"] == 2 * (2 * 812 * 1024 + 2 * 2 * 8192) == 3_391_488
    assert work["flops"] == 4 * 812 * 8192 == 26_607_616


def test_moe_gmm_count_reads_the_same_keys():
    # The count the latent family brought reads hidden_size and
    # moe_intermediate_size, which this configuration gives: a wave's 3
    # pairs on 2 experts of 2048 x 512 (3.1 M parameters each).
    work = counts_latent_moe.moe_gmm(REAL, [("decode", [[1, 0, 2]])])
    assert work["flops"] == 6 * 3 * 2048 * 512
    assert work["bytes"] == 2 * (2 * 3 * 2048 * 512 + 3 * (2 * 2048 + 3 * 512))


def test_serve_flops_by_hand():
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    dense = 3 * 2048 * 8192
    routed = 2048 * 256 + 3 * 2048 * 512 + 2.0 * 3 * 2048 * 512
    assert (full, sliding) == (29_458_432, 37_879_808)
    per_token = 2 * full + 6 * sliding + dense + 7 * routed + 2048 * 25088
    assert per_token == 457_637_888 == ref.matmul_params_per_token(REAL, 2.0)
    # Positions 0 and 999: rows 1 and 1,000 in the 2 full layers (48 heads),
    # 1 and 512 in the 6 sliding ones (64 heads), 4 x heads x 128 a row.
    attention = 4 * 128 * (2 * 48 * 1001 + 6 * 64 * 513)
    assert ref.serve_flops(REAL, [0, 999]) == 2 * 2 * per_token + attention
    assert ref.attended_rows(REAL, 1, 999) == 512 and ref.attended_rows(REAL, 4, 999) == 1000
    # Pairs counted as they were routed: two a token more, two experts' worth.
    assert ref.serve_flops(REAL, [0], 4.0) - ref.serve_flops(REAL, [0], 2.0) == \
        2 * 7 * 2 * 3 * 2048 * 512


def test_the_chips_share_is_1_874_billion_parameters():
    shapes = jax.eval_shape(
        lambda k: ref.make_params(k, REAL, jnp.bfloat16), jax.random.key(0))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layers = shapes["layers"]
    assert size(layers["0"]) == 79_794_176
    assert size(layers["4"]) == 234_459_136
    assert all(size(layers[str(i)]) == 242_880_512 for i in (1, 2, 3, 5, 6, 7))
    assert size(shapes) == 1_874_298_880
    program = jax.eval_shape(lambda p: ref.program_params(p, REAL), shapes)
    model_shapes = jax.eval_shape(
        lambda k: _model(REAL).init(k)["params"], jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, program) == \
        jax.tree.map(lambda a: a.shape, model_shapes)


#: The published ``config.json`` of ``Laguna-XS.2``: its numbers and
#: words; the per-layer lists and ``rope_parameters`` are checked below.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "gating": True, "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
}


def test_the_configuration_holds_the_published_config_and_the_four_cuts():
    cut = {"num_hidden_layers": 8, "num_experts": 64, "vocab_size": 25088,
           "max_position_embeddings": 16384}
    published = {k: PUBLISHED[k] for k in cut}
    assert {k: REAL[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    # The per-layer lists stay whole, as published; layer i reads entry i.
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert REAL["layer_types"] == period * 10
    assert REAL["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert REAL["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert REAL["rope_parameters"] == {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1,
                           "beta_fast": 64, "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    assert REAL["reduced"] == list(cut) and REAL["published"] == published
    assert set(REAL["reduced_why"]) == set(cut)
    for key in ("router", "shared_expert", "norms", "window", "gate", "yarn", "weights"):
        assert key in REAL["assumed"], key
    assert (REAL["num_experts_published"], REAL["experts_held_offset"]) == (256, 0)
    # Every width is the published one.
    assert (REAL["hidden_size"], REAL["head_dim"], REAL["num_key_value_heads"],
            REAL["intermediate_size"], REAL["moe_intermediate_size"],
            REAL["shared_expert_intermediate_size"], REAL["num_experts_per_tok"],
            REAL["sliding_window"]) == (2048, 128, 8, 8192, 512, 512, 8, 512)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["source"] == REAL["source"] and entry["reduced"] == REAL["reduced"]
    tc = driver.transformer_config(REAL)
    assert (tc.num_layers, tc.cache_layers, tc.window_layers, tc.window) == (8, 2, 6, 512)
    assert tc.layer_types == ("full_attention", "sliding_attention", "sliding_attention",
                              "sliding_attention") * 2
    assert tc.kv_pool_lanes == (1024, 1024)
    assert tc.slot_state_shapes == ((6, (512, 1024), "bfloat16"),) * 2
    assert tc.first_dense_layers == 1 and tc.routed_experts.held == (0, 64)
    assert tc.routed_experts.num_experts == 256 and tc.routed_experts.top_k == 8
    kinds = tc.attention_kinds
    assert (kinds["full_attention"].num_heads, kinds["sliding_attention"].num_heads) == (48, 64)
    assert kinds["full_attention"].rope_fraction == 0.5
    assert kinds["full_attention"].rope_yarn.trig_scale == pytest.approx(1.4158883083359672)
    assert kinds["sliding_attention"].rope_yarn is None
    assert (kinds["full_attention"].window, kinds["sliding_attention"].window) == (0, 512)


# -- the readers ---------------------------------------------------------------

def _ctx(ops, modules=(), decode=()):
    trace = {"/device:TPU:0": {
        "XLA Ops": [[n, s, d] for n, s, d in ops],
        "XLA Modules": [[n, s, d] for n, s, d in modules],
    }}
    return {"trace": trace, "host": {"traced_decode": list(decode)},
            "config": REAL, "peaks": PEAKS, "device_kind": "TPU v5 lite",
            "spans": [], "cell_seconds": 4.0}


def _metric(name):
    spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
    reader = {"served_roofline": served_roofline, "op_share": op_share}[spec["reader"]]
    return lambda ctx: reader.read(ctx, **spec["args"])


def test_window_decode_roofline_on_hand_made_events():
    # Six kernel calls of 10 us for the two tokens above: 6 layers x
    # 3,391,488 bytes at 819 GB/s = 24.8 us of 60. The full layers'
    # ``paged_decode`` calls are not the window's.
    ops = [(f"window_decode.{i} custom-call", 20_000 * i, 10_000) for i in range(6)]
    ops.append(("paged_decode.3 custom-call", 0, 50_000))
    got = _metric("window_decode_roofline")(_ctx(ops, decode=[300, 9000]))
    assert got == pytest.approx(100 * 6 * 3_391_488 / 819e9 / 60e-6)
    assert _metric("window_decode_roofline")(_ctx(ops)) is None                # no token
    assert _metric("window_decode_roofline")(_ctx(ops[6:], decode=[7])) is None  # no event


def test_window_wave_share_divides_kernel_time_by_the_wave():
    ops = [("window_decode.3 custom-call", 0, 300), ("paged_decode.7 custom-call", 0, 100)]
    modules = [("jit_decode_wave(123)", 0, 1000), ("jit_prefill_chunk_fn(45)", 0, 400)]
    assert _metric("window_wave_share")(_ctx(ops, modules)) == pytest.approx(30.0)
    # A program without the kernel (the parent) has nothing to read.
    assert _metric("window_wave_share")(_ctx(ops[1:], modules)) is None
    # The full layers' pattern does not take the window's kernel.
    spec = run.load_json(run.HERE / "metrics" / "paged_gqa_decode_roofline.json")
    assert served_roofline.trace.op_durations_s(
        _ctx(ops[:1])["trace"], spec["args"]["pattern"]) == []


def test_the_new_metrics_and_the_cell_are_appended_to_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [c["name"] for c in bench["configs"]][-1] == NAME
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "window_decode_roofline", "window_wave_share"]
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert {"window_decode_roofline", "window_wave_share", "moe_gmm_roofline",
            "expert_pairs_per_token", "expert_load_max_over_mean.p95", "serve_step.mfu",
            "serve_busy.mfu", "device_idle.serve", "recompiles.serve", "setup_compile_s",
            "dispatch_overlap_share", "decode_pages_live_share"} <= listed
    assert not {"paged_gqa_decode_roofline", "paged_decode_roofline", "gdn_step_roofline",
                "mla_decode_roofline", "ssm_step_roofline"} & listed
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    entry = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert "quarter of their deployment load" in entry["why"] and len(entry["why"]) <= 200
    _, cell, config = run.load_cell(CELL)
    assert cell["engine"] == {"max_slots": 64, "block_len": 64, "prefill_chunk": 1024,
                              "decode_waves_per_dispatch": 1, "max_model_len": 16384}
    assert (cell["traffic"], cell["traffic_file"], cell["lead_in_s"],
            cell["trace_seconds"]) == ("codeagent", "codeagent", 25, 5)
    mix = run.load_json(run.HERE / "traffic" / "codeagent.json")
    assert (mix["prompt"], mix["answer"], mix["max_total"], mix["order_seed"]) == (
        {"dist": "lognormal", "median": 2048, "sigma": 1.2, "min": 128, "max": 15360},
        {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 16, "max": 1024},
        16384, 42)
    assert set(cell["limits"]) == {"token_gap_max", "token_gap_mean",
                                   "routing_ambiguous_share"}
    assert config["vocab_size"] == 25088


# -- the cell end to end -------------------------------------------------------

def _model(config):
    from rocket_tpu.models.transformer import TransformerLM

    return TransformerLM(driver.transformer_config(config))


SEED = 2**31 + 21


def execute(seed=SEED):
    return run.execute("tiny-window.codeagent", seed, 1.0, False,
                       devices=jax.devices()[:1], root=FIX)


@functools.cache
def sound():
    return execute()


def test_a_sound_serving_run_is_correct():
    line = sound()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] == 20 and line["failed"] == 0
    assert list(line["checks"]) == ["routing_ambiguous_share", "token_gap_max",
                                    "token_gap_mean"]


def test_both_controls_read_over_their_limits():
    """What ``tools/control_window_moe`` reads on the chip, at the tiny
    size: the reference in fp8, and the reference whose sliding layers see
    the whole context, each in the program's place over a sound run's
    sample, come out NOT correct by the harness's own judgement."""
    from benchmark.tools import control_window_moe as control

    _, cell, config = run.load_cell("tiny-window.codeagent", root=FIX)
    result = driver.run(cell, config, seed=SEED, seconds=1.0)
    assert control.judged(result["numbers"], cell["limits"]) == {"correct": True, "over": []}
    margin = float(cell["compare"]["margin"])
    for name, options in control.CONTROLS.items():
        got = driver.reference_numbers(config, SEED, result["sample"], span=32,
                                       control=True, **options)
        numbers = driver.summarise(got, margin)
        verdict = control.judged(numbers, cell["limits"])
        assert verdict["correct"] is False and "token_gap_mean" in verdict["over"], (
            name, numbers)
        assert numbers["token_gap_mean"] > 3 * cell["limits"]["token_gap_mean"], (name, numbers)


@pytest.mark.parametrize("fault", ["ring_unwrapped", "no_head_gate", "no_yarn"])
def test_a_part_of_the_mathematics_dropped_is_not_correct(monkeypatch, fault):
    from rocket_tpu.nn.attention import MultiHeadAttention
    from rocket_tpu.ops import paged_attention as pa

    if fault == "ring_unwrapped":
        # A decode wave that takes the ring for a line: once a slot's
        # position passes the window it sees only the rows up to
        # ``position mod window``, not the whole ring.
        attend = pa.window_attention

        def unwrapped(q, k_new, v_new, k_ring, v_ring, positions, valid, **kw):
            if q.shape[1] == 1:
                positions = positions % k_ring.shape[2]
            return attend(q, k_new, v_new, k_ring, v_ring, positions, valid, **kw)

        monkeypatch.setattr(pa, "window_attention", unwrapped)
    elif fault == "no_head_gate":
        gated = MultiHeadAttention._gated_out
        monkeypatch.setattr(MultiHeadAttention, "_gated_out",
                            lambda self, params, out, gate: gated(self, params, out, None))
    else:
        init = MultiHeadAttention.__init__

        def plain(self, *args, rope_yarn=None, **kw):
            init(self, *args, **kw)

        monkeypatch.setattr(MultiHeadAttention, "__init__", plain)
    line = execute()
    assert line["correct"] is False, (fault, line["checks"])
    worst = max(c["value"] / c["limit"] for c in line["checks"].values())
    assert worst > 2, (fault, line["checks"])
