"""The latent-attention / routed-expert configuration's share of the
benchmark: its counts by hand, its reference's shapes, its cell end to end
at a tiny size on the CPU — sound runs come out correct, and each part of
the mathematics dropped under the timed path comes out NOT correct."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counts_latent_moe as counts
from benchmark import run
from benchmark.drivers import serve_latent_moe as driver
from benchmark.readers import expert_pairs, kernel_roofline_served
from benchmark.reference import deepseek_v3_lm as ref

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures_latent"
REAL = json.loads((ROOT / "benchmark/configs/dots.vlm1.inst-ep16.json").read_text())
TINY = json.loads((FIX / "configs/tiny-latent.json").read_text())


def test_mla_decode_count_by_hand():
    # Two decoded tokens of live contexts 1000 and 3000, one layer: 4000
    # rows of 576 values read once; per row and each of 128 heads a dot
    # over 576 (score) and one over 512 (value).
    work = counts.mla_decode(REAL, [1000, 3000])
    assert work["flops"] == 2 * 4000 * 128 * (576 + 512) == 1_114_112_000
    assert work["bytes"] == 2 * (4000 * 576 + 2 * 128 * (576 + 512)) == 5_165_056


def test_moe_gmm_count_by_hand():
    # A wave (3 pairs on 2 experts of layer 0, none in layer 1) and a chunk
    # whose LAST layer is not required work (4 pairs on 2 experts count).
    calls = [("decode", [[1, 0, 2], [0, 0, 0]]), ("prefill", [[3, 1, 0], [5, 5, 5]])]
    work = counts.moe_gmm(REAL, calls)
    assert work["flops"] == 7 * 6 * 7168 * 2048 == 616_562_688
    assert work["bytes"] == 2 * (4 * 3 * 7168 * 2048 + 7 * (2 * 7168 + 3 * 2048)) == 352_608_256


def test_serve_flops_count_by_hand():
    # Attention 187.1M a layer, dense FFN 396.4M, a routed layer: shared
    # 44.0M + router 1.8M + half a held expert; an eighth of the head.
    attn = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 128 * 128 * 7168
    assert attn == 187_105_280
    per_token = 6 * attn + 3 * 7168 * 18432 + 5 * (
        3 * 7168 * 2048 + 7168 * 256 + 0.5 * 3 * 7168 * 2048) + 7168 * 16160
    assert per_token == 1_974_304_768 == ref.matmul_params_per_token(REAL, 0.5)
    # Positions 0 and 9 attend 1 + 10 rows: 128 heads x (192 + 128) a row a layer.
    assert ref.serve_flops(REAL, [0, 9]) == 2 * 2 * per_token + 11 * 2 * 6 * 128 * 320
    assert ref.serve_flops(REAL, [0, 9]) == 7_902_625_792


#: The catalog's ``config`` of ``dots.vlm1.inst`` (model-configs guide,
#: ``architectures.jsonl``), as published.
PUBLISHED = json.loads(
    '{"attention_bias": false, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu", '
    '"hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512, '
    '"max_position_embeddings": 163840, "model_type": "dots_vlm", "moe_intermediate_size": '
    '2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, '
    '"norm_topk_prob": true, "num_attention_heads": 128, "num_experts_per_tok": 8, '
    '"num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1, '
    '"q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": '
    '1e-06, "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, '
    '"mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}, '
    '"rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": '
    'true, "tie_word_embeddings": false, "topk_group": 4, "topk_method": "noaux_tc", '
    '"v_head_dim": 128, "vocab_size": 129280} '
)


def test_the_configuration_holds_the_published_widths_and_the_six_cuts():
    assert REAL["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                               "vocab_size", "num_nextn_predict_layers", "max_position_embeddings"]
    assert REAL["published"] == {k: PUBLISHED[k] for k in REAL["reduced"]} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
        "vocab_size": 129280, "num_nextn_predict_layers": 1, "max_position_embeddings": 163840}
    for key, value in PUBLISHED.items():
        assert key in REAL, key
        assert REAL[key] == value or key in REAL["reduced"], key
    z = ref.sizes(REAL)
    assert (z["E"], z["held"], z["offset"], z["k"]) == (256, 16, 0, 8)


def test_reference_shapes_and_the_program_layout():
    params = jax.eval_shape(
        lambda k: ref.make_params(k, REAL, jnp.bfloat16), jax.random.key(0))
    assert sum(l.size for l in jax.tree.leaves(params)) == 5_503_361_280   # 11.0 GB
    moe = params["layers"]["1"]["moe"]
    assert moe["w_g"].shape == (7168, 256) and moe["bias"].shape == (256,)
    assert moe["experts"]["w_gate_up"].shape == (16, 7168, 4096)
    assert moe["experts"]["w_down"].shape == (16, 2048, 7168)
    assert "mlp" in params["layers"]["0"] and "moe" not in params["layers"]["0"]
    assert params["head"].shape == (7168, 16160)
    prog = ref.program_params(params, REAL)
    from rocket_tpu.models.transformer import TransformerLM

    model = TransformerLM(driver.transformer_config(REAL))
    want = jax.eval_shape(model.init, jax.random.key(0))["params"]
    assert jax.tree.structure(prog) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(prog)] == [a.shape for a in jax.tree.leaves(want)]
    assert model.config.kv_pool_lanes == (640,)
    tiny = ref.make_params(jax.random.key(0), TINY)
    out, margin = ref.logits(tiny, jnp.arange(10) % 96, TINY)
    assert out.shape == (10, 96) and margin.shape == (10,) and bool(jnp.all(margin >= 0))


def test_bias_changes_the_top8_for_most_tokens_at_the_routers_real_width():
    """``assumed.router_bias``: with e_score_correction_bias ~ normal(0.1)
    the biased and the unbiased selection differ for 9 tokens in 10."""
    cfg = dict(REAL, hidden_size=256)
    p = {"w_g": jax.random.normal(jax.random.key(1), (256, 256)) / 16.0,
         "bias": 0.1 * jax.random.normal(jax.random.key(2), (256,))}
    x = jax.random.normal(jax.random.key(3), (2000, 256))
    _, with_bias, _ = ref.route(p, x, cfg)
    _, without, _ = ref.route(dict(p, bias=jnp.zeros(256)), x, cfg)
    differ = (np.sort(with_bias, 1) != np.sort(without, 1)).any(axis=1).mean()
    assert 0.85 < differ <= 1.0, differ


def test_expert_pair_readers_on_recorded_spans():
    rows = [
        ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None),
        ("moe/expert_pairs", 0.5, 0.5, 0, {"kind": "decode", "tick": 0, "tokens": 4,
                                           "pairs": "[[2,0],[1,1]]"}, 1, 1, None),
        ("moe/expert_pairs", 0.6, 0.6, 0, {"kind": "prefill", "tick": 0, "tokens": 8,
                                           "pairs": "[[3,1],[0,0]]"}, 1, 2, None),
    ]
    ctx = {"spans": rows, "host": {}, "cell_seconds": 1.0}
    assert expert_pairs.read(ctx, stat="per_token") == pytest.approx(8 / (4 * 2 + 8 * 2))
    # Ratios: 2/1, 1/1, 3/2 (the empty layer has none): p100 = 2.
    assert expert_pairs.read(ctx, stat="max_over_mean", q=100) == pytest.approx(2.0)
    assert expert_pairs.read({"spans": rows[:1], "host": {}, "cell_seconds": 1.0},
                             stat="per_token") is None
    trace = {"/device:TPU:0": {"XLA Ops": [["moe_gmm_down.1 custom-call", 0, 1_000_000]]}}
    ctx.update(trace=trace, config=REAL, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               device_kind="x")
    work = counts.moe_gmm(REAL, [("decode", [[2, 0], [1, 1]]), ("prefill", [[3, 1], [0, 0]])])
    share = kernel_roofline_served.read(
        ctx, pattern="^[^ ]*moe_gmm[^ ]* custom-call$", module="counts_latent_moe",
        count="moe_gmm", per="expert_calls")
    assert share == pytest.approx(100 * (work["bytes"] / 819e9) / 1e-3)
    assert kernel_roofline_served.read(
        ctx, pattern="^nothing$", module="counts_latent_moe", count="moe_gmm",
        per="expert_calls") is None


# -- the cell end to end -------------------------------------------------------

def execute(seed=2**31 + 21):
    return run.execute("tiny-latent.docqa", seed, 1.0, False,
                       devices=jax.devices()[:1], root=FIX)


def test_a_sound_serving_run_is_correct():
    line = execute()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] == 20 and line["failed"] == 0
    assert list(line["checks"]) == [
        "routing_ambiguous_share", "token_gap_max", "token_gap_mean"]


_BUILD = driver.transformer_config


def _with_experts(**changes):
    def faulty(config):
        tc = _BUILD(config)
        tc.routed_experts = dataclasses.replace(tc.routed_experts, **changes)
        return tc
    return faulty


def _without_m2(config):
    from rocket_tpu.nn.attention import YarnScaling

    tc = _with_experts()(config)
    yarn = dataclasses.replace(tc.latent_attention.yarn, mscale_all_dim=0.0, mscale=0.0)
    assert isinstance(yarn, YarnScaling)
    tc.latent_attention = dataclasses.replace(tc.latent_attention, yarn=yarn)
    return tc


FAULTS = {
    "group_limit": _with_experts(n_group=1, topk_group=1),
    "normalisation": _with_experts(norm_topk_prob=False),
    "scaling": _with_experts(routed_scaling_factor=1.0),
    "shared_expert": _with_experts(shared_hidden=0),
    "yarn_m2": _without_m2,
}


@pytest.mark.parametrize("fault", [*FAULTS, "bias", "fp8_activations"])
def test_a_part_of_the_mathematics_dropped_is_not_correct(monkeypatch, fault):
    if fault in FAULTS:
        monkeypatch.setattr(driver, "transformer_config", FAULTS[fault])
    elif fault == "bias":
        import rocket_tpu.nn.moe as moe

        route = moe.route_sigmoid_grouped
        monkeypatch.setattr(moe, "route_sigmoid_grouped",
                            lambda logits, bias, cfg: route(logits, bias * 0, cfg))
    else:
        # A lower precision than the file states: the residual stream
        # rounded to fp8 after every block.
        from rocket_tpu.models.transformer import Block

        paged = Block.apply_paged

        def low(self, *args, **kwargs):
            x, pages, counts = paged(self, *args, **kwargs)
            return ref.fp8(x).astype(x.dtype), pages, counts

        monkeypatch.setattr(Block, "apply_paged", low)
    line = execute()
    assert line["correct"] is False, (fault, line["checks"])
    gap = line["checks"]["token_gap_max"]
    assert gap["value"] > 3 * gap["limit"], (fault, line["checks"])
