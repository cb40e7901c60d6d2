"""The linear-attention / routed-expert configuration's share of the
benchmark: its counts by hand, its reference against a Python loop, its
readers on hand-made events and on nothing, its cell end to end at a tiny
size on the CPU — a sound run comes out correct, and a part of the
mathematics dropped under the timed path comes out NOT correct."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counts_gdn as counts
from benchmark import counts_paged_gqa as paged
from benchmark import counts_latent_moe, run
from benchmark.drivers import serve_linear_moe as driver
from benchmark.readers import op_share, served_roofline
from benchmark.reference import qwen3_next_lm as ref

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures_linear"
NAME = "Qwen3-Next-80B-A3B-Instruct-ep4"
CELL = f"{NAME}.longchat"
REAL = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
TINY = json.loads((FIX / "configs/tiny-linear.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- counts --------------------------------------------------------------------

def test_gdn_step_count_by_hand():
    # One DeltaNet layer, 3 decoded tokens: S (32 x 128 x 128 float32) read
    # and written, the convolution's tail (3 x 8192 bfloat16) read and
    # written, the rows q, k (2048 each), v, z (4096 each) in bfloat16, o
    # (4096) and g, beta (32 each) in float32.
    work = counts.gdn_step(REAL, 3)
    rows = (2 * 2048 + 2 * 4096) * 2 + 4096 * 4 + 2 * 32 * 4
    per_token = 2 * 2_097_152 + 2 * 49_152 + rows
    assert (rows, per_token) == (41_216, 4_333_824)
    assert work["bytes"] == 3 * per_token == 13_001_472
    assert work["flops"] == 7 * 3 * 32 * 128 * 128 == 11_010_048
    assert counts.state_layers(REAL) == 6


def test_gdn_chunk_count_by_hand():
    # Two chunks of 1024 and 100 real rows: each reads and writes S once
    # and moves its real rows.
    work = counts.gdn_chunk(REAL, [1024, 100])
    assert work["bytes"] == 1124 * 41_216 + 2 * 2 * 2_097_152 == 54_715_392
    assert work["flops"] == 7 * 1124 * 32 * 128 * 128 == 4_125_097_984


def test_moe_gmm_count_by_hand_at_2048_by_512():
    # The count the latent family brought reads hidden_size and
    # moe_intermediate_size, which this configuration gives under the same
    # keys: a wave (3 pairs on 2 experts of layer 0, none in layer 1) and a
    # chunk whose LAST layer is not required work (4 pairs on 2 experts).
    calls = [("decode", [[1, 0, 2], [0, 0, 0]]), ("prefill", [[3, 1, 0], [5, 5, 5]])]
    work = counts_latent_moe.moe_gmm(REAL, calls)
    assert work["flops"] == 7 * 6 * 2048 * 512 == 44_040_192
    assert work["bytes"] == 2 * (4 * 3 * 2048 * 512 + 7 * (2 * 2048 + 3 * 512)) == 25_244_672


def test_serve_flops_and_parameters_by_hand():
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2.5 * 3 * 2048 * 512
    assert (gdn, attn) == (33_685_504, 27_262_976)
    per_token = 6 * gdn + 2 * attn + 8 * ffn + 2048 * 37984
    assert per_token == 430_915_584 == ref.matmul_params_per_token(REAL, 2.5)
    rule = 6 * 7 * 32 * 128 * 128
    assert rule == 22_020_096 == ref.rule_flops_per_token(REAL)
    # Positions 0 and 9 attend 1 + 10 rows in each of the 2 attention layers.
    assert ref.serve_flops(REAL, [0, 9]) == \
        2 * (2 * per_token + rule) + 4 * 2 * 4096 * 11 == 1_768_062_976
    # Pairs counted as they were routed: two a token more, two experts' worth.
    assert ref.serve_flops(REAL, [0], 4.5) - ref.serve_flops(REAL, [0], 2.5) == \
        2 * 8 * 2 * 3 * 2048 * 512


def test_the_chips_share_is_3_67_billion_parameters():
    shapes = jax.eval_shape(
        lambda k: ref.make_params(k, REAL, jnp.bfloat16), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_667_251_328
    program = jax.eval_shape(lambda p: ref.program_params(p, REAL), shapes)
    assert sum(a.size for a in jax.tree.leaves(program)) == 3_667_251_328
    model_shapes = jax.eval_shape(
        lambda k: _model(REAL).init(k)["params"], jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, program) == \
        jax.tree.map(lambda a: a.shape, model_shapes)


#: The catalog's ``config`` of ``Qwen3-Next-80B-A3B-Instruct`` (model-configs
#: guide, ``architectures.jsonl``), as published.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def test_the_configuration_holds_the_published_widths_and_the_four_cuts():
    cut = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 37984,
           "max_position_embeddings": 16384}
    assert {k: REAL[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    assert REAL["reduced"] == list(cut)
    assert REAL["published"] == {k: PUBLISHED[k] for k in cut}
    assert (REAL["num_experts_published"], REAL["experts_held_offset"]) == (512, 0)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["source"] == REAL["source"] and entry["reduced"] == REAL["reduced"]
    tc = driver.transformer_config(REAL)
    assert (tc.num_layers, tc.cache_layers, tc.state_layers) == (8, 2, 6)
    assert [i for i in range(8) if not tc.is_state_layer(i)] == [3, 7]
    assert tc.kv_pool_lanes == (512, 512)
    assert tc.slot_state_shapes == ((6, (32, 128, 128), "float32"),
                                    (6, (24576,), "bfloat16"))
    assert tc.routed_experts.held == (0, 128) and tc.routed_experts.num_experts == 512


# -- the reference -------------------------------------------------------------

def test_the_reference_mixer_is_the_rule_written_out():
    """``ref.gated_delta_net`` against a Python loop over tokens and heads
    in float64: the convolution tap by tap, ``S`` updated in place."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64),
                     ref.layer_params(jax.random.key(3), TINY, False)["gdn"])
    x = np.asarray(jax.random.normal(jax.random.key(4), (9, 64)), np.float64)
    hk, hv, dk, dv = 2, 4, 16, 16
    silu = lambda a: a / (1 + np.exp(-a))
    unit = lambda a: a / np.sqrt(np.sum(a * a) + 1e-6)
    s = np.zeros((hv, dk, dv))
    past = [np.zeros(2 * hk * dk + hv * dv)] * 3
    want = []
    for t in range(9):
        window = past + [np.concatenate([x[t] @ p[w] for w in ("w_q", "w_k", "w_v")])]
        mixed = silu(sum(p["conv_w"][j] * window[j] for j in range(4)))
        past = window[1:]
        z = (x[t] @ p["w_z"]).reshape(hv, dv)
        beta = 1 / (1 + np.exp(-(x[t] @ p["w_b"])))
        g = -np.exp(p["a_log"]) * np.log1p(np.exp(x[t] @ p["w_a"] + p["dt_bias"]))
        out = []
        for h in range(hv):
            j = h // (hv // hk)
            q = unit(mixed[j * dk:(j + 1) * dk]) * dk ** -0.5
            k = unit(mixed[hk * dk + j * dk:hk * dk + (j + 1) * dk])
            v = mixed[2 * hk * dk + h * dv:2 * hk * dk + (h + 1) * dv]
            s[h] = np.exp(g[h]) * s[h]
            s[h] = s[h] + np.outer(k, beta[h] * (v - s[h].T @ k))
            o = s[h].T @ q
            o = o / np.sqrt(np.mean(o * o) + TINY["rms_norm_eps"]) * p["norm"]
            out.append(o * silu(z[h]))
        want.append(np.concatenate(out) @ p["w_out"])
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a.astype(np.float32)), p)
        got, state = ref.gated_delta_net(p32, jnp.asarray(x, jnp.float32), TINY)
        # The same 9 rows and 3 of padding: the state stops where told.
        padded = jnp.concatenate([jnp.asarray(x, jnp.float32), jnp.ones((3, 64))])
        _, stopped = ref.gated_delta_net(p32, padded, TINY, length=9)
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(state, s, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(stopped, state, atol=1e-6)


def test_an_expert_gathers_its_tokens_or_runs_on_all_of_them_to_the_same_sum():
    """``ref.expert_layer`` gathers a held expert's tokens up to a capacity
    of an eighth of the sequence and runs it on every token where more
    chose it: 64 tokens (capacity 8: the dense form for the popular
    experts) against every expert on every token, by hand."""
    p = ref.layer_params(jax.random.key(5), TINY, False)["moe"]
    x = jax.random.normal(jax.random.key(6), (64, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, margin = ref.expert_layer(p, x, TINY, shared=False)
        w, experts, _ = ref.route(p, x, TINY)
        want = jnp.zeros_like(x)
        for i in range(4):
            f = jax.tree.map(lambda a: a[i], p["experts"])
            weight = jnp.sum(jnp.where(experts == 8 + i, w, 0.0), axis=1)
            want = want + weight[:, None] * ref.swiglu(f, x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    counts_ = [(np.asarray(experts) == 8 + i).sum() for i in range(4)]
    assert max(counts_) > 8 >= 0 and margin.shape == (64,)


def test_the_state_carried_in_bfloat16_moves_the_reference():
    params = ref.make_params(jax.random.key(0), TINY, jnp.float32)
    tokens = jnp.arange(40) % 96
    full, _ = ref.logits(params, tokens, TINY)
    low, _ = ref.logits(params, tokens, TINY, state_dtype=jnp.bfloat16)
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
    reader = {"served_roofline": served_roofline, "op_share": op_share}[spec["reader"]]
    return lambda ctx: reader.read(ctx, **spec["args"])


def test_gdn_step_roofline_on_hand_made_events():
    # Two kernel calls of 100 us for 4 decoded tokens: 6 layers x 4 x
    # 4,333,824 bytes at 819 GB/s = 127 us of 200.
    ops = [("gdn_step.3 custom-call", 0, 100_000), ("gdn_step.3 custom-call", 190_000, 100_000),
           ("fusion.1 fusion", 120_000, 10_000)]
    got = _metric("gdn_step_roofline")(_ctx(ops, decode=[300, 400, 500, 600]))
    assert got == pytest.approx(100 * 6 * 4 * 4_333_824 / 819e9 / 200e-6)
    assert _metric("gdn_step_roofline")(_ctx(ops)) is None            # no token
    assert _metric("gdn_step_roofline")(_ctx(ops[2:], decode=[1])) is None  # no event


def test_gdn_chunk_roofline_reads_the_chunks_rows_from_the_programs_spans():
    tick = ("serve/tick", 0.0, 4.0, None, {}, 1, 0, None)
    chunks = [("serve/prefill_enqueue", 1.0 + i, 1.1 + i, 0,
               {"rid": 1, "start": 1024 * i, "valid": v}, 1, 1 + i, None)
              for i, v in enumerate((1024, 100))]
    ops = [("gdn_chunk.7 custom-call", 0, 2_000_000)]
    ctx = _ctx(ops, spans=[tick, *chunks])
    # The rows' bytes (54.7 MB, 67 us) over the rule's operations (4.1
    # GFLOP, 21 us): the larger, times 6 layers, of 2 ms.
    got = _metric("gdn_chunk_roofline")(ctx)
    assert got == pytest.approx(100 * 6 * 54_715_392 / 819e9 / 2e-3)
    assert _metric("gdn_chunk_roofline")(_ctx(ops, spans=[tick])) is None
    assert _metric("gdn_chunk_roofline")(_ctx([], spans=[tick, *chunks])) is None


@pytest.mark.parametrize("name, layers, q_lanes, kv_lanes", [
    ("Qwen3-Next-80B-A3B-Instruct-ep4", 2, 16 * 256, 2 * 256),
    ("AI21-Jamba2-3B", 2, 20 * 128, 1 * 128),
])
def test_the_paged_count_of_shared_kv_heads_by_hand(name, layers, q_lanes, kv_lanes):
    """Two decoded tokens of live contexts 1,000 and 3,000: 4,000 K rows
    and as many V rows of the K/V heads' lanes, two queries in and two
    outputs out (bfloat16); 4 operations a row and query lane. The caching
    layers: 3 and 7 of 8; 7 and 21 of 28."""
    config = run.load_json(run.HERE / "configs" / f"{name}.json")
    assert paged.cache_layers(config) == layers
    got = paged.paged_decode(config, [1000, 3000])
    assert got["bytes"] == 2 * (2 * 4000 * kv_lanes + 2 * 2 * q_lanes)
    assert got["flops"] == 4 * 4000 * q_lanes
    assert paged.cache_layers({"num_hidden_layers": 5}) == 5


def test_paged_gqa_decode_roofline_on_hand_made_events():
    # Four kernel calls of 50 us for two tokens of 1,000 and 3,000 live
    # rows: 2 layers x (4,000 x 512 x 2 x 2 + 2 x 2 x 4,096 x 2) bytes at
    # 819 GB/s = 20.1 us of 200.
    ops = [(f"paged_decode.{i} custom-call", 60_000 * i, 50_000) for i in range(4)]
    ops.append(("gdn_step.3 custom-call", 0, 100_000))
    got = _metric("paged_gqa_decode_roofline")(_ctx(ops, decode=[1000, 3000]))
    assert got == pytest.approx(100 * 2 * 8_224_768 / 819e9 / 200e-6)
    assert _metric("paged_gqa_decode_roofline")(_ctx(ops)) is None           # no token
    assert _metric("paged_gqa_decode_roofline")(_ctx(ops[4:], decode=[7])) is None  # no event
    with pytest.raises(ValueError, match="unknown per"):
        served_roofline.read(_ctx(ops, decode=[7]), pattern="paged", module="counts_paged_gqa",
                             count="paged_decode", layers="cache_layers", per="wave")


def test_the_shares_divide_kernel_time_by_program_time():
    ops = [("gdn_step.3 custom-call", 0, 300), ("gdn_chunk.7 custom-call", 0, 100),
           ("fusion.9 fusion", 0, 999)]
    modules = [("jit_decode_wave(123)", 0, 1000), ("jit_prefill_chunk_fn(45)", 0, 400)]
    ctx = _ctx(ops, modules)
    assert _metric("gdn_wave_share")(ctx) == pytest.approx(30.0)
    assert _metric("gdn_chunk_share")(ctx) == pytest.approx(25.0)
    # A program without the named kernels (the parent) has nothing to read.
    assert _metric("gdn_wave_share")(_ctx(ops[2:], modules)) is None
    assert _metric("gdn_chunk_share")(_ctx(ops, modules[:1])) is None


def test_the_new_metrics_and_the_cell_are_appended_to_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert {"gdn_step_roofline", "gdn_chunk_roofline", "gdn_wave_share",
            "gdn_chunk_share", "moe_gmm_roofline", "expert_pairs_per_token",
            "expert_load_max_over_mean.p95", "serve_step.mfu", "serve_busy.mfu",
            "device_idle.serve", "recompiles.serve", "setup_compile_s",
            "dispatch_overlap_share"} <= listed
    assert not {"paged_decode_roofline", "ssm_step_roofline", "mla_decode_roofline"} & listed
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    _, cell, config = run.load_cell(CELL)
    assert cell["engine"] == {"max_slots": 64, "block_len": 64, "prefill_chunk": 1024,
                              "decode_waves_per_dispatch": 1, "max_model_len": 16384}
    assert (cell["traffic"], cell["traffic_file"], cell["lead_in_s"]) == (
        "longchat", "longchat", 25)
    mix = run.load_json(run.HERE / "traffic" / "longchat.json")
    assert (mix["prompt"], mix["answer"], mix["max_total"], mix["order_seed"]) == (
        {"dist": "lognormal", "median": 4096, "sigma": 0.8, "min": 512, "max": 14336},
        {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 1024},
        16384, 37)
    assert set(cell["limits"]) == {"token_gap_max", "token_gap_mean",
                                   "routing_ambiguous_share", "state_gap"}
    assert config["vocab_size"] == 37984


# -- the cell end to end -------------------------------------------------------

def _model(config):
    from rocket_tpu.models.transformer import TransformerLM

    return TransformerLM(driver.transformer_config(config))


SEED = 2**31 + 21


def execute(seed=SEED):
    return run.execute("tiny-linear.longchat", seed, 1.0, False,
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
    assert list(line["checks"]) == ["routing_ambiguous_share", "state_gap",
                                    "token_gap_max", "token_gap_mean"]


def test_the_probe_alone_reads_the_state_gap_a_whole_run_reads():
    """The probe is served alone before any traffic, so its number does not
    depend on what a loaded machine finished: the control tool's
    ``--probe-only`` reads it without a window."""
    from benchmark.tools import control_linear_moe as control

    _, cell, config = run.load_cell("tiny-linear.longchat", root=FIX)
    alone = control.probe_only(cell, config, SEED, span=32)
    assert alone["numbers"]["state_gap"] == sound()["checks"]["state_gap"]["value"]
    assert alone["numbers"]["token_gap_max"] is None


def test_the_controls_read_over_their_limits():
    """What ``tools/control_linear_moe`` reads on the chip, at the tiny
    size: the reference with ``S`` carried in bfloat16 in the program's
    place moves the state's own number over its limit."""
    _, cell, config = run.load_cell("tiny-linear.longchat", root=FIX)
    engine = driver.build_engine(cell, config, SEED)
    driver.warm_up(engine, config, 16)
    probe = driver.state_probe(engine, config, cell["compare"]["state_probe"], SEED)
    sound_gap = driver.reference_numbers(config, SEED, [], span=32, probe=probe)[1]
    low_gap = driver.reference_numbers(config, SEED, [], span=32, probe=probe,
                                       state_dtype=jnp.bfloat16, control=True)[1]
    limit = cell["limits"]["state_gap"]
    assert sound_gap < 0.8 * limit and low_gap > 1.3 * limit, (sound_gap, low_gap)
    # Through the harness's own judgement, as the tool prints it.
    from benchmark.tools import control_linear_moe as control

    assert control.judged({"state_gap": sound_gap, "token_gap_max": None},
                          cell["limits"]) == {"correct": True, "over": []}
    assert control.judged({"state_gap": low_gap}, cell["limits"]) == {
        "correct": False, "over": ["state_gap"]}


@pytest.mark.parametrize("fault", ["attention_gate", "stale_state", "fp8_activations"])
def test_a_part_of_the_mathematics_dropped_is_not_correct(monkeypatch, fault):
    if fault == "attention_gate":
        # Plain attention: the output gate dropped.
        from rocket_tpu.nn.attention import MultiHeadAttention

        gated = MultiHeadAttention._gated_out
        monkeypatch.setattr(MultiHeadAttention, "_gated_out",
                            lambda self, params, out, gate: gated(self, params, out, None))
    elif fault == "stale_state":
        # A slot's state is never reset: a request inherits what the
        # slot's last one left.
        import rocket_tpu.nn.gdn as gdn

        apply_state = gdn.GatedDeltaNet.apply_state

        def stale(self, params, x, state, positions, valid, **kw):
            return apply_state(self, params, x, state, positions + 1, valid, **kw)

        monkeypatch.setattr(gdn.GatedDeltaNet, "apply_state", stale)
    else:
        # A lower precision than the file states: the residual stream
        # rounded to fp8 after every state layer, at a scale of each row's
        # own.
        from rocket_tpu.models.transformer import Block

        stateful = Block.apply_state

        def low(self, *args, **kwargs):
            x, state, counts_ = stateful(self, *args, **kwargs)
            return jax.vmap(jax.vmap(ref.fp8))(x).astype(x.dtype), state, counts_

        monkeypatch.setattr(Block, "apply_state", low)
    line = execute()
    assert line["correct"] is False, (fault, line["checks"])
    # By a margin, whichever requests a loaded machine finished in the window.
    worst = max(c["value"] / c["limit"] for c in line["checks"].values())
    assert worst > 2, (fault, line["checks"])
