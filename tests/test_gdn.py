"""Gated DeltaNet layers beside gated full attention with a routed
feed-forward in every layer (``nn/gdn.py``, the options of
``nn.attention.MultiHeadAttention``, ``nn.moe.route_softmax``) against the
plain reference (``benchmark/reference/qwen3_next_lm.py``), and a model of
eight tiny layers through the serve path — the matrix per-slot state beside
the paged pool, what resets it, what leaves it alone. Tiny widths, float32,
CPU."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_linear_moe as driver
from benchmark.reference import qwen3_next_lm as ref
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.nn import gdn
from rocket_tpu.nn.gdn import GatedDeltaNet, GatedDeltaNetConfig
from rocket_tpu.nn.moe import RoutedExperts, route_softmax
from rocket_tpu.serve import ServeConfig, ServeEngine
from rocket_tpu.serve.engine import abstract_wave_inputs

#: Eight layers, attention every fourth (3 and 7); 16 experts of which this
#: chip holds 4 (4..7), 3 chosen a token; heads of 32 over a width of 64.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "vocab_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "full_attention_interval": 4, "linear_conv_kernel_dim": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "num_experts": 4, "num_experts_published": 16, "experts_held_offset": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "max_position_embeddings": 128, "tie_word_embeddings": False,
}


def _highest(fn):
    @functools.wraps(fn)
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


@pytest.fixture(scope="module")
def hybrid():
    """``(model, program params, reference params)``, float32."""
    tc = driver.transformer_config(TINY)
    tc.activation_dtype = None
    weights = ref.make_params(jax.random.key(0), TINY, jnp.float32)
    return TransformerLM(tc), ref.program_params(weights, TINY), weights


def _engine(hybrid, **over):
    model, params, _ = hybrid
    sc = dict(max_slots=4, block_len=8, prefill_chunk=8)
    sc.update(over)
    return ServeEngine(model, params, ServeConfig(**sc))


#: A sequence length every whole-sequence oracle below is padded to: one
#: compile of the model and one of the reference for the whole file.
T = 40


@pytest.fixture(scope="module")
def four_slots(hybrid):
    """One engine of four slots for the file: every test that serves
    through it starts its requests at position 0, which is what resets a
    slot, whatever the test before left there."""
    return _engine(hybrid)


@pytest.fixture(scope="module")
def one_slot(hybrid):
    return _engine(hybrid, max_slots=1)


@pytest.fixture(scope="module")
def whole(hybrid):
    """``whole(tokens) -> (program logits, reference logits, margin)`` of
    one sequence, each the whole-sequence forward, padded to ``T`` (causal:
    the padding moves no row before it)."""
    model, params, weights = hybrid
    program = jax.jit(lambda tokens: model.apply(
        {"params": params, "state": {}}, {"tokens": tokens[None]}, mode="eval"
    )[0]["logits"][0])
    reference = jax.jit(lambda tokens: ref.logits(weights, tokens, TINY))

    def run(tokens):
        padded = np.zeros((T,), np.int32)
        padded[:len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            want, margin = reference(jnp.asarray(padded))
            got = program(jnp.asarray(padded))
        n = len(tokens)
        return np.asarray(got)[:n], np.asarray(want)[:n], np.asarray(margin)[:n]

    return run


def _assert_greedy(whole, prompt, tokens):
    """``tokens`` are the greedy continuation of ``prompt`` by the
    whole-sequence forward: each served token's logit is the largest but
    for 1e-3 of the logits' spread (two paths, float32: a near-tie may fall
    either way)."""
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits, _, _ = whole(seq[:-1])
    logits = logits[len(prompt) - 1:]
    gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
    assert gap.max() <= 1e-3 * logits.std(), gap


def _serve(engine, prompts, new=12):
    rids = [engine.submit(p, max_new_tokens=new, temperature=0.0) for p in prompts]
    engine.drain()
    return [engine.result(r).tokens for r in rids]


def _prompts(n, seed=3, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# -- (a) the rule, in each of its forms, against the literal recurrence ------

def _rule_inputs(cfg, s, c, seed=0):
    """Operands of the rule as the mixer hands them over, and a carried
    state: ``q``, ``k`` normalised a key head, ``g`` < 0, ``beta`` in
    (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (s, c, cfg.num_k_heads, cfg.head_k_dim)))
    k = unit(jax.random.normal(ks[1], (s, c, cfg.num_k_heads, cfg.head_k_dim)))
    return dict(
        q=(q * cfg.head_k_dim ** -0.5).reshape(s, c, -1), k=k.reshape(s, c, -1),
        v=jax.random.normal(ks[2], (s, c, cfg.value_dim)),
        g=-jax.random.uniform(ks[3], (s, c, cfg.num_v_heads), minval=1e-3, maxval=0.7),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (s, c, cfg.num_v_heads))),
        s_all=jax.random.normal(
            ks[5], (3, 4, cfg.num_v_heads, cfg.head_k_dim, cfg.head_v_dim)),
    )


def _literal(cfg, x, layer, slots, valid, fresh):
    """The rule written out in numpy, token by token, from each slot's
    carried state (one sequence at a time, its first ``valid`` rows):
    ``(o, s_all')``."""
    s_all = np.array(x["s_all"])
    out = np.zeros(x["v"].shape, np.float32)
    for i, slot in enumerate(np.asarray(slots)):
        n = int(valid[i])
        if not n:
            continue
        heads = lambda a, h: np.repeat(
            np.asarray(a[i, :n]).reshape(n, h, -1), cfg.num_v_heads // h, axis=1)
        state = np.zeros_like(s_all[layer, slot]) if fresh[i] else s_all[layer, slot]
        q, k = heads(x["q"], cfg.num_k_heads), heads(x["k"], cfg.num_k_heads)
        v = heads(x["v"], cfg.num_v_heads)
        for t in range(n):
            state = np.exp(np.asarray(x["g"][i, t]))[:, None, None] * state
            r = np.einsum("hkv,hk->hv", state, k[t])
            u = np.asarray(x["beta"][i, t])[:, None] * (v[t] - r)
            state = state + k[t][:, :, None] * u[:, None, :]
            out[i, t] = np.einsum("hkv,hk->hv", state, q[t]).reshape(-1)
        s_all[layer, slot] = state
    return out, s_all


RAGGED = dict(slots=jnp.asarray([2, 0]), valid=jnp.asarray([64, 37]),
              fresh=jnp.asarray([False, True]))


def _check_rule(got, want, valid):
    (o, s_all), (want_o, want_s) = got, want
    for i, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(np.asarray(o)[i, :n], want_o[i, :n], atol=1e-5)
    np.testing.assert_allclose(s_all, want_s, atol=1e-5)


@pytest.mark.parametrize("form", ["scan", "kernel16", "kernel32", "kernel64"])
def test_every_form_of_the_rule_is_the_literal_recurrence(form, monkeypatch):
    """The ``lax.scan`` and the chunked (WY) form — the chunk kernel,
    interpreted — at three block sizes: two slots of 64 rows, one from a
    carried state, one afresh with 37 real rows (so a block that is partly
    real, and at 16 rows one that is skipped). Float32, 1e-5: the order of
    the sums."""
    kernel = form.startswith("kernel")
    cfg = GatedDeltaNetConfig(1, 2, 128, 128)
    if kernel:
        monkeypatch.setattr(gdn, "_CHUNK_ROWS", int(form[-2:]))
        assert gdn._chunk_rows(64) == int(form[-2:])
    x = _rule_inputs(cfg, 2, 64)
    want = _literal(cfg, x, 1, **RAGGED)
    # Not through the jit: its cache would hand one block size's trace to
    # the next.
    got = gdn.gdn_chunk.__wrapped__(
        x["s_all"], x["q"], x["k"], x["v"], x["g"], x["beta"], 1,
        RAGGED["slots"], RAGGED["valid"], RAGGED["fresh"],
        cfg=cfg, kernel=kernel, interpret=kernel)
    _check_rule(got, want, RAGGED["valid"])


@pytest.mark.parametrize("rows, block", [(1024, 128), (512, 128), (192, 96),
                                         (64, 64), (16, 16), (12, 0), (4, 0)])
def test_a_chunks_block_is_the_largest_multiple_of_8_that_divides_its_rows(rows, block):
    """No option: a chunk the kernel takes (heads of 128 x 128) is folded
    in the largest blocks of at most 128 rows that divide it, and rows
    that no multiple of 8 divides go to the scan."""
    cfg = GatedDeltaNetConfig(1, 2, 128, 128)
    assert gdn._chunk_rows(rows) == block
    assert gdn.gdn_kernel_supported(cfg, rows) == (block > 0)
    assert gdn.gdn_kernel_supported(cfg, rows, wave=True)


@pytest.mark.parametrize("running", [(1, 0, 1, 1), (0, 0, 0, 0)],
                         ids=["three-of-four", "none"])
def test_the_wave_kernel_is_the_literal_recurrence(running):
    """One row a slot: the wave kernel interpreted against the literal
    rule; a slot that does not run keeps its ``S`` bitwise and gets 0, one
    at position 0 starts from zeros."""
    cfg = GatedDeltaNetConfig(1, 2, 128, 128)
    x = _rule_inputs(cfg, 4, 1, seed=2)
    valid = jnp.asarray(running)
    fresh = jnp.asarray([False, False, True, False]) & (valid > 0)
    rows = {n: x[n][:, 0] for n in ("q", "k", "v", "g", "beta")}
    o, s_all = gdn.gdn_step(x["s_all"], *rows.values(), 2, valid, fresh, cfg=cfg,
                            kernel=True, interpret=True)
    want_o, want_s = _literal(cfg, x, 2, jnp.arange(4), np.asarray(valid), fresh)
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-5)
    np.testing.assert_allclose(s_all, want_s, atol=1e-5)
    idle = np.asarray(valid) == 0
    np.testing.assert_array_equal(np.asarray(s_all)[2, idle], np.asarray(x["s_all"])[2, idle])
    np.testing.assert_array_equal(np.asarray(s_all)[:2], np.asarray(x["s_all"])[:2])
    assert not np.asarray(o)[idle].any()
    plain_o, plain_s = gdn.gdn_step(x["s_all"], *rows.values(), 2, valid, fresh,
                                    cfg=cfg, kernel=False)
    np.testing.assert_allclose(o, plain_o, atol=1e-5)
    np.testing.assert_allclose(s_all, plain_s, atol=1e-5)


@_highest
def test_mixer_whole_sequence_matches_the_reference(hybrid):
    """Float32 both, the same equations: projections, the convolution, the
    norms, the rule and the gated output norm; 1e-5 is summation order."""
    model, params, weights = hybrid
    x = jax.random.normal(jax.random.key(1), (2, 37, 64), jnp.float32)
    mixer = model.blocks[0].mixer
    assert isinstance(mixer, GatedDeltaNet) and model.blocks[3].mixer is None
    got, _ = mixer.apply(
        {"params": params["blocks"]["0"]["mixer"], "state": {}}, x, mode="eval")
    for b in range(2):
        want, _ = ref.gated_delta_net(weights["layers"]["0"]["gdn"], x[b], TINY)
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
def test_rows_past_valid_change_nothing(kernel):
    """A chunk of 16 with 11 real rows: whatever the padding holds, the
    state and the real rows' outputs are the same bits; an empty chunk
    after it leaves the state bitwise."""
    cfg = GatedDeltaNetConfig(1, 2, 128, 128)
    mixer = GatedDeltaNet(64, cfg)
    p = mixer.init_params(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1, 16, 64), jnp.float32)
    state0 = tuple(jax.random.normal(jax.random.key(9), (2, 8) + shape, dtype)
                   for shape, dtype in cfg.state_shapes(jnp.float32))
    args = dict(layer=1, slots=jnp.asarray([3]), kernel=kernel, interpret=kernel)
    pos, valid = jnp.asarray([8]), jnp.asarray([11])

    def run(pad):
        return mixer.apply_state(p, x.at[:, 11:].set(pad), state0, pos, valid, **args)

    (out_a, state_a), (out_b, state_b) = run(0.0), run(1e3)
    np.testing.assert_array_equal(out_a[:, :11], out_b[:, :11])
    for a, b, was in zip(state_a, state_b, state0):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(was)[0])
        np.testing.assert_array_equal(np.asarray(a)[1, :3], np.asarray(was)[1, :3])
    _, state_c = mixer.apply_state(p, x, state_a, jnp.asarray([19]),
                                   jnp.asarray([0]), **args)
    for a, c in zip(state_a, state_c):
        np.testing.assert_array_equal(a, c)


def test_a_shape_the_kernels_do_not_take_is_said_on_a_tpu(hybrid, monkeypatch):
    """Off the CPU the kernels are the default; heads of 16 x 16 run the
    scan, many times the cost, and say so. On the CPU the scan is the
    path, silently."""
    import warnings

    model, params, _ = hybrid
    mixer, p = model.blocks[0].mixer, params["blocks"]["0"]["mixer"]
    x = jax.random.normal(jax.random.key(4), (1, 8, 64), jnp.float32)
    state = tuple(jnp.zeros((1, 1) + shape, dtype)
                  for shape, dtype in mixer.config.state_shapes(jnp.float32))
    call = lambda: mixer.apply_state(p, x, state, jnp.asarray([0]), jnp.asarray([8]))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        quiet, _ = call()
    monkeypatch.setattr(gdn, "_on_cpu", lambda: False)
    with pytest.warns(UserWarning, match="do not take heads of 16 x 16"):
        said, _ = call()
    np.testing.assert_array_equal(quiet, said)


# -- (b) the gated attention --------------------------------------------------

@_highest
def test_gated_attention_matches_the_reference(hybrid):
    """A head width that is not ``dim / heads`` (4 x 32 over 64), the
    output gate, per-head q/k norms and rotary over a quarter of the head,
    whole sequence."""
    model, params, weights = hybrid
    attn = model.blocks[3].attn
    assert attn.extended and (attn.head_dim, attn.rope_dim) == (32, 8)
    x = jax.random.normal(jax.random.key(1), (2, 24, 64), jnp.float32)
    got, _ = attn.apply(
        {"params": params["blocks"]["3"]["attn"], "state": {}}, x, mode="eval")
    for b in range(2):
        want = ref.attention(weights["layers"]["3"]["attn"], x[b], TINY,
                             query_block=8)
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


@_highest
@pytest.mark.parametrize("chunk", [8, 16])
def test_gated_attention_through_pages_matches_the_reference(hybrid, chunk):
    """The same layer through the paged pool: a sequence of 32 in chunks
    of 8 or 16 at its slot's positions, against the whole-sequence
    reference."""
    model, params, weights = hybrid
    attn, p = model.blocks[3].attn, params["blocks"]["3"]["attn"]
    x = jax.random.normal(jax.random.key(2), (32, 64), jnp.float32)
    pages = (jnp.zeros((2, 9, 8, 64)),) * 2
    table = jnp.asarray([[3, 1, 6, 2]], jnp.int32)
    got = []
    for start in range(0, 32, chunk):
        out, *pages = attn.apply_paged(
            p, x[None, start:start + chunk], *pages, table,
            jnp.asarray([start], jnp.int32), jnp.asarray([chunk], jnp.int32), layer=1)
        got.append(out[0])
    want = ref.attention(weights["layers"]["3"]["attn"], x, TINY, query_block=8)
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-5, rtol=1e-5)


def test_the_attention_options_are_inert_by_default():
    """No option given: the parameters and the layer are what they were."""
    from rocket_tpu.nn.attention import MultiHeadAttention

    plain = MultiHeadAttention(64, 4, num_kv_heads=2, rope=True)
    assert not plain.extended and plain.head_dim == 16 and plain.rope_dim == 16
    assert set(plain.init_params(jax.random.key(0))) == {"qkv", "proj"}
    assert plain.init_params(jax.random.key(0))["qkv"]["w"].shape == (64, 128)
    gated = MultiHeadAttention(64, 4, num_kv_heads=2, rope=True, head_dim=32,
                               gate=True, qk_norm=True, rope_fraction=0.25)
    p = gated.init_params(jax.random.key(0))
    assert set(p) == {"qkv", "proj", "q_norm", "k_norm"}
    assert p["qkv"]["w"].shape == (64, (2 * 4 + 2 * 2) * 32)
    assert p["proj"]["w"].shape == (128, 64)
    with pytest.raises(NotImplementedError, match="no dense-cache path"):
        gated.apply_cached(p, jnp.zeros((1, 1, 64)), {}, 0)


def test_a_long_table_chunk_walks_its_live_context(monkeypatch):
    """A prefill chunk whose table would make more scores than
    ``_CHUNK_SCORES_MAX`` folds its live context in tiles: the same
    numbers as the one-shot form."""
    from rocket_tpu.ops import paged_attention as pa

    ks = jax.random.split(jax.random.key(1), 5)
    q = jax.random.normal(ks[0], (2, 16, 4, 32))
    k_new, v_new = (jax.random.normal(k, (2, 16, 2, 32)) for k in ks[1:3])
    pool = [jax.random.normal(k, (2, 25, 8, 64)) for k in ks[3:5]]
    table = jnp.arange(1, 25, dtype=jnp.int32).reshape(2, 12)
    where = (table, jnp.asarray([40, 8], jnp.int32), jnp.asarray([16, 9], jnp.int32))
    want = pa.paged_attention(q, k_new, v_new, *pool, *where, layer=1)
    monkeypatch.setattr(pa, "_CHUNK_SCORES_MAX", 1)
    monkeypatch.setattr(pa, "_CHUNK_TILE_ROWS", 16)
    got = pa.paged_attention(q, k_new, v_new, *pool, *where, layer=1)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])


# -- (c) the softmax router and the shares ----------------------------------

@_highest
def test_softmax_route_matches_the_reference(hybrid):
    model, params, weights = hybrid
    x = jax.random.normal(jax.random.key(5), (40, 64), jnp.float32)
    p = weights["layers"]["1"]["moe"]
    want_w, want_e, margin = ref.route(p, x, TINY)
    got_w, got_e = route_softmax(x @ p["w_r"], model.blocks[1].routed.config)
    np.testing.assert_array_equal(got_e, want_e)
    np.testing.assert_allclose(got_w, want_w, atol=1e-6)
    np.testing.assert_allclose(got_w.sum(axis=1), 1.0, atol=1e-6)
    assert margin.shape == (40,) and float(margin.min()) >= 0


@_highest
def test_a_routed_block_matches_the_reference_share(hybrid):
    model, params, weights = hybrid
    x = jax.random.normal(jax.random.key(6), (2, 20, 64), jnp.float32)
    got, counts = model.blocks[1].routed.apply(
        {"params": params["blocks"]["1"]["moe"], "state": {}}, x)
    want, _ = ref.expert_layer(weights["layers"]["1"]["moe"], x.reshape(40, 64), TINY)
    np.testing.assert_allclose(got.reshape(40, 64), want, atol=1e-5)
    _, experts, _ = ref.route(weights["layers"]["1"]["moe"], x.reshape(40, 64), TINY)
    want_counts = [(np.asarray(experts) == e).sum() for e in range(4, 8)]
    np.testing.assert_array_equal(counts, want_counts)


@_highest
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The share test of the guide: the routed parts that the four chips'
    shares of 4 experts give, plus the gated shared expert counted once,
    equal the uncut reference's whole layer of 16 experts."""
    whole_cfg = {**TINY, "num_experts": 16, "experts_held_offset": 0}
    p = ref.layer_params(jax.random.key(7), TINY, False, all_experts=True)["moe"]
    x = jax.random.normal(jax.random.key(8), (24, 64), jnp.float32)
    whole, _ = ref.expert_layer(p, x, whole_cfg)
    shared, _ = ref.expert_layer(
        {**p, "experts": jax.tree.map(lambda a: a[:1], p["experts"])}, x, TINY,
        experts_held=(99, 1))      # an expert nobody holds: the shared part alone
    base = driver.transformer_config(TINY).routed_experts
    parts = 0.0
    for chip in range(4):
        cfg = dataclasses.replace(base, experts_held=(4 * chip, 4))
        params = {
            "router": {"w": p["w_r"]}, "shared": p["shared"],
            "experts": jax.tree.map(lambda a: a[4 * chip:4 * chip + 4], p["experts"]),
        }
        y, counts = RoutedExperts(64, cfg).apply({"params": params, "state": {}}, x)
        parts = parts + (y - shared)
        if chip == 1:       # TINY's own share: the reference's, too
            np.testing.assert_allclose(
                y, ref.expert_layer(params_ref(p, 4), x, TINY)[0], atol=1e-5)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


def params_ref(p, offset):
    return {**p, "experts": jax.tree.map(lambda a: a[offset:offset + 4], p["experts"])}


def test_the_sigmoid_router_keeps_its_parameters_and_the_softmax_one_has_no_bias():
    from rocket_tpu.nn.moe import RoutedExpertsConfig

    old = RoutedExperts(16, RoutedExpertsConfig(8, 2, 8, shared_hidden=8))
    p = old.init_params(jax.random.key(0))
    assert set(p["router"]) == {"w", "bias"} and set(p["shared"]) == {
        "w_gate", "w_up", "w_down"}
    new = RoutedExperts(16, RoutedExpertsConfig(
        8, 2, 8, shared_hidden=8, shared_gate=True, scoring="softmax"))
    p = new.init_params(jax.random.key(0))
    assert set(p["router"]) == {"w"} and "w_sg" in p["shared"]
    with pytest.raises(ValueError, match="no groups"):
        RoutedExperts(16, RoutedExpertsConfig(8, 2, 8, n_group=2, scoring="softmax"))
    with pytest.raises(ValueError, match="unknown scoring"):
        RoutedExperts(16, RoutedExpertsConfig(8, 2, 8, scoring="tanh"))


# -- (d) eight layers: two chunks with padding, then decode ------------------

def test_whole_model_apply_matches_the_reference_logits(whole):
    for seed in (1, 2):
        tokens = np.random.default_rng(seed).integers(0, 96, size=T).astype(np.int32)
        got, want, _ = whole(tokens)
        np.testing.assert_allclose(got, want, atol=2e-4 * float(want.std()) + 1e-5)


@_highest
def test_chunked_prefill_then_decode_matches_the_reference(hybrid, whole):
    """A prompt of 13 through chunks of 8 (the second holds 4 real rows and
    4 of padding), then 12 decode waves through pool and state: the logits
    of every wave against the reference's full forward of the whole
    sequence. Float32 both; 2e-4 of the logits' spread is summation order
    through eight layers (the rule itself is the same arithmetic), as for
    the Mamba and the latent fixtures — unless the reference's own router
    was within 1e-4 of another choice at a position, which none here is."""
    model, params, weights = hybrid
    sc = ServeConfig(max_slots=4, block_len=8, prefill_chunk=8)
    spec, mb, _, _ = sc.resolve(model.config)
    pages = spec.init_pages()
    # Stale state in every slot: position 0 must start from zeros.
    pages = pages[:2] + tuple(jnp.full_like(a, 3.0) for a in pages[2:])
    slot = 2
    table = np.zeros((4, mb), np.int32)
    table[slot, :4] = [5, 2, 7, 3]
    prompt = np.random.default_rng(2).integers(0, 96, size=13).astype(np.int32)
    step = jax.jit(model.paged_step)
    for start, valid in ((0, 8), (8, 4)):
        chunk = np.full((1, 8), 95, np.int32)
        chunk[0, :valid] = prompt[start:start + valid]
        _, pages, pairs = step(params, chunk, pages, table[slot:slot + 1],
                               np.asarray([start], np.int32), np.asarray([valid], np.int32),
                               np.asarray([slot], np.int32))
        assert pairs.shape == (8, 4) and int(pairs.sum()) <= 8 * valid * 3
    seq, last, got = list(prompt), int(prompt[-1]), []
    lengths = np.zeros((4,), np.int32)
    run = np.zeros((4,), np.int32)
    lengths[slot], run[slot] = 12, 1
    for _ in range(12):
        toks = np.zeros((4, 1), np.int32)
        toks[slot] = last
        logits, pages, _ = step(params, toks, pages, table, lengths.copy(), run)
        got.append(np.asarray(logits[slot]))
        last = int(np.argmax(got[-1]))
        seq.append(last)
        lengths[slot] += 1
    _, want, margin = whole(np.asarray(seq[:-1], np.int32))
    assert float(margin.min()) > 1e-4
    np.testing.assert_allclose(
        np.stack(got), want[12:], atol=2e-4 * float(want.std()) + 1e-5)


def test_engine_tokens_match_the_whole_sequence_forward(four_slots, whole):
    """The same through ``ServeEngine``: the tokens of three requests
    served together are the greedy continuation of the whole-sequence
    forward."""
    prompts = _prompts(3)
    for prompt, tokens in zip(prompts, _serve(four_slots, prompts)):
        _assert_greedy(whole, prompt, tokens)


# -- (e) what resets a slot's state, what leaves it alone ----------------------

def test_a_reused_slot_inherits_nothing(one_slot, four_slots, whole):
    """One slot: it serves A, then B, then A again. Each time the tokens
    are those of a slot of the four-slot engine, and the whole-sequence
    forward's."""
    a, b = _prompts(2, seed=5)
    first, second, third = _serve(one_slot, [a, b, a])
    assert first == third
    assert [first, second] == _serve(four_slots, [a, b])
    _assert_greedy(whole, a, first)
    _assert_greedy(whole, b, second)


def test_a_slot_frozen_in_flight_and_refilled_starts_from_zeros(one_slot):
    """One slot. A ends on an EOS the host learns only after it enqueued
    the next dispatch with A's slot in its run mask (two dispatches in
    flight): the device's carried mask freezes the slot there — its matrix
    state stays as A left it — then B is admitted into it. B's tokens are
    those of B served alone, and A's end at the EOS."""
    a, b = _prompts(2, seed=5)
    alone_a, alone_b = _serve(one_slot, [a]), _serve(one_slot, [b])
    eos = alone_a[0][4]
    rids = [one_slot.submit(a, max_new_tokens=12, temperature=0.0, eos_token_id=eos),
            one_slot.submit(b, max_new_tokens=12, temperature=0.0)]
    one_slot.drain()
    got_a, got_b = (one_slot.result(r).tokens for r in rids)
    assert got_a == alone_a[0][:alone_a[0].index(eos) + 1] and len(got_a) < 12
    assert got_b == alone_b[0]


def test_evict_and_reprefill_gives_the_undisturbed_tokens(hybrid, whole):
    """A pool too small for the load preempts and re-prefills (its blocks
    go, its slot's state stays behind as garbage): every request still ends
    with the whole-sequence forward's greedy tokens."""
    prompts = _prompts(8, seed=3, lo=4, hi=12)
    starved = _engine(hybrid, block_len=4, prefill_chunk=4, max_model_len=32,
                      num_blocks=9)
    got = _serve(starved, prompts, new=14)
    assert starved.report()["requests"]["preemptions"] > 0
    for prompt, tokens in zip(prompts, got):
        _assert_greedy(whole, prompt, tokens)


def test_a_slot_that_does_not_run_keeps_its_state_bitwise(four_slots):
    engine = four_slots.engine
    prompt = np.arange(1, 17, dtype=np.int32)
    table = np.zeros((4, 16), np.int32)
    table[0, :3], table[1, :3] = [1, 2, 3], [4, 5, 6]
    for slot in (0, 1):
        for start in (0, 8):
            engine.prefill(table[slot:slot + 1], prompt[None, start:start + 8] + slot,
                           np.asarray([start], np.int32), np.asarray([8], np.int32), slot)
    before = [np.asarray(a) for a in engine.pages]
    assert all(np.abs(a[:, :2]).max() > 0 for a in before[2:])
    s = engine.max_slots
    run = np.zeros((s,), bool)
    run[0] = True
    lengths = np.asarray([16, 16, 0, 0], np.int32)
    engine.decode(table, lengths, np.asarray([7, 9, 0, 0], np.int32), run,
                  np.full((s,), 40, np.int32), np.zeros((s,), np.float32),
                  np.zeros((s,), np.int32), np.ones((s,), np.float32),
                  np.full((s,), -1, np.int32), np.zeros((s,), np.int32))
    for was, now in zip(before[2:], engine.pages[2:]):
        now = np.asarray(now)
        assert not np.array_equal(was[:, 0], now[:, 0])       # slot 0 ran
        np.testing.assert_array_equal(was[:, 1:], now[:, 1:])  # the rest: bitwise


def test_two_waves_a_dispatch_give_the_tokens_of_one(hybrid, four_slots):
    prompts = _prompts(5, seed=11)
    assert _serve(_engine(hybrid, decode_waves_per_dispatch=2), prompts) == \
        _serve(four_slots, prompts)


def test_the_engine_records_the_pairs_its_held_experts_received(four_slots):
    """``moe/expert_pairs`` as the latent fixture records it: every routed
    layer (all eight), the 4 held experts, some 3 * 4 / 16 pairs a token."""
    import json

    from rocket_tpu.obs import spans

    engine = four_slots
    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        _serve(engine, _prompts(3))
    finally:
        spans.uninstall(recorder)
    records = [e for e in recorder.events() if e.name == "moe/expert_pairs"]
    assert {e.ids["kind"] for e in records} == {"decode", "prefill"}
    pairs = [np.asarray(json.loads(e.ids["pairs"])) for e in records]
    assert all(p.shape == (8, 4) for p in pairs)
    tokens = sum(int(e.ids["tokens"]) for e in records)
    assert 0.3 < sum(p.sum() for p in pairs) / (8 * tokens) < 1.5


# -- (f) the pool, and the fixtures that were there ----------------------------

def test_the_pool_holds_a_matrix_state_by_slot(hybrid):
    model, _, _ = hybrid
    cfg = model.config
    assert (cfg.num_layers, cfg.cache_layers, cfg.state_layers) == (8, 2, 6)
    assert [cfg.is_state_layer(i) for i in range(8)] == [True] * 3 + [False] + \
        [True] * 3 + [False]
    assert cfg.kv_pool_lanes == (64, 64)            # 2 kv heads of 32, not of 16
    sc = ServeConfig(max_slots=4, block_len=8, prefill_chunk=8)
    spec, mb, num_blocks, _ = sc.resolve(cfg)
    assert spec.pages_shapes == ((2, num_blocks, 8, 64),) * 2
    assert spec.state_shapes == (((6, 4, 4, 16, 16), "float32"),
                                 ((6, 4, 3 * 128), "float32"))
    assert spec.state_bytes == 6 * 4 * (4 * 16 * 16 + 3 * 128) * 4
    assert spec.pool_bytes == num_blocks * spec.block_bytes + spec.state_bytes
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=4, max_blocks_per_seq=mb, prefill_chunk=8)
    assert len(decode_args) == 15 and len(prefill_args) == 7   # + the slot
    assert [a.shape for a in decode_args[1]] == [s for s, _ in spec.arrays]
    # The static audit's floor of a wave counts every array of the tuple:
    # the rank-5 state read and written, like a vector state.
    from rocket_tpu.analysis.serve_audit import decode_floor_bytes

    floor = decode_floor_bytes(spec, 1000, max_slots=4, max_blocks_per_seq=mb)
    pages = 2 * 4 * (mb * 8 + 1) * 2 * 64 * 4
    assert floor == 1000 + pages + 2 * spec.state_bytes


def test_one_kind_of_state_layer_a_model():
    from rocket_tpu.nn.ssm import SSMConfig

    base = dict(vocab_size=8, max_seq_len=8, dim=16, num_layers=4, num_heads=2,
                pos_embedding="rope", gdn=GatedDeltaNetConfig(1, 2, 8, 8),
                attn_layer_period=4, attn_layer_offset=3)
    TransformerConfig(**base).validate()
    with pytest.raises(ValueError, match="two kinds of state layer"):
        TransformerConfig(**base, ssm=SSMConfig(d_inner=128, dt_rank=4)).validate()
    with pytest.raises(ValueError, match="scan_layers"):
        TransformerConfig(**base, scan_layers=True).validate()
    with pytest.raises(ValueError, match="without ssm or gdn"):
        TransformerConfig(**{**base, "gdn": None}).validate()
    with pytest.raises(ValueError, match="RMSNorm's"):
        TransformerConfig(**base, norm_zero_centered=True).validate()


#: Greedy tokens of the three fixtures that were there, recorded at the
#: commit before this module (61ac9fb): three requests each, prompts from
#: ``default_rng(7)``, 10 new tokens. (``tests/test_ssm.py`` holds the K/V
#: and the latent fixtures' own frozen tokens; they are asked for again
#: here because the attention, the router and the pool they run changed.)
FROZEN_MAMBA = [[0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                [32, 54, 54, 54, 54, 54, 54, 54, 54, 54],
                [48, 62, 58, 33, 18, 50, 50, 50, 50, 50]]


def _mamba_fixture():
    import test_ssm

    tc = test_ssm.driver.transformer_config(test_ssm.TINY)
    tc.activation_dtype = None
    weights = test_ssm.ref.make_params(jax.random.key(0), test_ssm.TINY, jnp.float32)
    return (TransformerLM(tc), test_ssm.ref.program_params(weights, test_ssm.TINY),
            ServeConfig(max_slots=4, block_len=8, prefill_chunk=8),
            [(2, 33, 8, 16)] * 2 + [(2, 4, 16, 128), (2, 4, 384)], 145408, 7)


def _stateless(name):
    import test_ssm

    fixture = {"gpt2": test_ssm._gpt2_fixture, "latent": test_ssm._latent_fixture}[name]
    return fixture() + (6,)


@pytest.mark.parametrize("name", ["gpt2", "latent", "mamba"])
def test_the_fixtures_that_were_there_are_what_they_were(name):
    """The K/V pool, the latent pool and the Mamba state beside pages: pool
    shapes, both programs' signatures and the greedy tokens, bit for bit as
    before this module."""
    import test_ssm

    model, params, sc, pages, pool_bytes, prefill_arity = (
        _mamba_fixture() if name == "mamba" else _stateless(name))
    assert model.config.gdn is None and model.config.head_dim is None
    spec, mb, _, _ = sc.resolve(model.config)
    assert spec.pool_bytes == pool_bytes
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=sc.prefill_chunk)
    assert len(decode_args) == 15 and len(prefill_args) == prefill_arity
    engine = ServeEngine(model, params, sc)
    assert [list(a.shape) for a in engine.engine.pages] == [list(s) for s in pages]
    rng = np.random.default_rng(7)
    rids = [engine.submit(
        rng.integers(0, model.config.vocab_size, size=int(rng.integers(3, 14))).astype(np.int32),
        max_new_tokens=10) for _ in range(3)]
    engine.drain()
    frozen = FROZEN_MAMBA if name == "mamba" else test_ssm.FROZEN[name]
    assert [engine.result(r).tokens for r in rids] == frozen
