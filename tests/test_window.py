"""Sliding-window attention layers beside full ones (``nn/attention.py``
``MultiHeadAttention(window=, head_gate=, rope_yarn=)``, ``models/
transformer.py`` ``layer_types`` / ``AttentionKind``, ``ops/
paged_attention.py`` ``window_attention``) against the plain reference
(``benchmark/reference/laguna_lm.py``), and a model of six tiny layers
through the serve path — the ring of ``window`` rows a slot beside the
paged pool: prompts shorter than the window, as long, longer; a chunk
longer than the window; decoding across ``window - 1 -> window`` and round
the ring more than twice; eviction and re-prefill. Tiny widths, float32,
CPU."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_window_moe as driver
from benchmark.reference import laguna_lm as ref
from rocket_tpu.models.transformer import AttentionKind, TransformerConfig, TransformerLM
from rocket_tpu.nn.attention import MultiHeadAttention, YarnScaling, yarn_inv_freq
from rocket_tpu.nn.moe import RoutedExperts
from rocket_tpu.ops import paged_attention as pa
from rocket_tpu.serve import ServeConfig, ServeEngine
from rocket_tpu.serve.engine import abstract_wave_inputs

#: Six layers (full, sliding x 3, full, sliding), a window of 8; 6 query
#: heads in a full layer and 8 in a sliding one over 2 K/V heads of 64
#: (128 lanes: the width the decode kernel takes); 8 experts of which this
#: chip holds 4 (4..7), 2 chosen a token; layer 0 dense.
TINY = json.loads((Path(__file__).resolve().parents[1]
                   / "benchmark/tests/fixtures_window/configs/tiny-window.json").read_text())
W = TINY["sliding_window"]


@pytest.fixture(scope="module")
def tiny():
    """``(model, program params, reference params)``, float32."""
    tc = driver.transformer_config(TINY)
    tc.activation_dtype = None
    weights = ref.make_params(jax.random.key(0), TINY, jnp.float32)
    return TransformerLM(tc), ref.program_params(weights, TINY), weights


def _engine(tiny, **over):
    model, params, _ = tiny
    sc = dict(max_slots=4, block_len=8, prefill_chunk=12)
    sc.update(over)
    return ServeEngine(model, params, ServeConfig(**sc))


@pytest.fixture(scope="module")
def four_slots(tiny):
    return _engine(tiny)


#: The length every whole-sequence oracle is padded to: one compile each.
T = 64


@pytest.fixture(scope="module")
def reference(tiny):
    """``reference(tokens) -> (logits, margin)`` of one sequence, padded to
    ``T`` (causal: the padding moves no row before it)."""
    _, _, weights = tiny
    fn = jax.jit(lambda tokens: ref.logits(weights, tokens, TINY))

    def run(tokens):
        padded = np.zeros((T,), np.int32)
        padded[:len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            logits, margin = fn(jnp.asarray(padded))
        return np.asarray(logits)[:len(tokens)], np.asarray(margin)[:len(tokens)]

    return run


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, size=n).astype(np.int32)


def _serve(engine, prompts, new=20):
    rids = [engine.submit(p, max_new_tokens=new, temperature=0.0) for p in prompts]
    engine.drain()
    return [engine.result(r).tokens for r in rids]


def _assert_greedy(reference, prompt, tokens):
    """``tokens`` are the reference's greedy continuation of ``prompt``:
    each served token's logit is the largest but for 1e-3 of the logits'
    spread (two paths, float32: a near-tie may fall either way)."""
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits, _ = reference(seq[:-1])
    logits = logits[len(prompt) - 1:]
    gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
    assert gap.max() <= 1e-3 * logits.std(), gap


# -- (a) the options, by hand ---------------------------------------------------

def test_the_head_gate_scales_each_head_by_one_sigmoid():
    """``head_gate``: the fused projection is ``[q | k | v | g]`` with ONE
    column a head, and head ``h``'s output is multiplied by ``sigmoid(x .
    g_h)`` before the output projection — by hand, from the same layer
    without the gate and with the identity for its output projection."""
    kw = dict(num_kv_heads=2, head_dim=16, use_bias=False, rope=True)
    attn = MultiHeadAttention(32, 4, head_gate=True, **kw)
    p = attn.init_params(jax.random.key(0))
    assert p["qkv"]["w"].shape == (32, 4 * 16 + 2 * 2 * 16 + 4)
    x = jax.random.normal(jax.random.key(1), (1, 5, 32))
    plain = MultiHeadAttention(32, 4, **kw)
    heads = plain.apply({"params": {"qkv": {"w": p["qkv"]["w"][:, :-4]},
                                    "proj": {"w": jnp.eye(64)}}, "state": {}}, x)[0]
    g = jax.nn.sigmoid(x @ p["qkv"]["w"][:, -4:])                      # (1, 5, 4)
    want = (heads.reshape(1, 5, 4, 16) * g[..., None]).reshape(1, 5, 64) @ p["proj"]["w"]
    got = attn.apply({"params": p, "state": {}}, x)[0]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_and_the_trig_scale_by_hand():
    """The full layers' rotary at Laguna's published constants: 64 rotated
    lanes of 128, theta 500,000, factor 64 over 4,096 positions, beta_fast
    64, beta_slow 1. The correction range by hand: lane pair ``i`` turns
    ``4096 / (2 pi theta^(2i/64))`` times; it is kept below pair 5 (floor
    of 5.66) and divided by 64 from pair 16 (ceil of 15.80) on, a straight
    ramp between. cos and sin are multiplied by ``0.1 ln 64 + 1``."""
    yarn = YarnScaling(factor=64, original_max_position_embeddings=4096,
                       beta_fast=64, beta_slow=1)
    dim = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(500000))
    assert (math.floor(dim(64)), math.ceil(dim(1))) == (5, 16)
    plain = 500000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
    want = plain * (1 - ramp) + plain / 64 * ramp
    np.testing.assert_allclose(yarn_inv_freq(64, 500000.0, yarn), want, rtol=1e-6)
    np.testing.assert_allclose(ref.rope_of(json.loads(
        (Path(__file__).resolve().parents[1] / "benchmark/configs/Laguna-XS.2-ep4.json"
         ).read_text()), 0)["inv_freq"], want, rtol=1e-6)
    assert yarn.trig_scale == pytest.approx(1.4158883083359672, abs=1e-12)
    # The layer rotates lanes 0..63 of a head at these frequencies, scaled,
    # and leaves lanes 64..127 as they were.
    attn = MultiHeadAttention(128, 1, head_dim=128, use_bias=False, rope=True,
                              rope_base=500000.0, rope_fraction=0.5, rope_yarn=yarn)
    p = {"qkv": {"w": jnp.eye(128, 3 * 128)}, "proj": {"w": jnp.eye(128)}}
    x = jax.random.normal(jax.random.key(2), (1, 3, 128))
    q, k, _, _ = attn._project(p, x, jnp.asarray([1000], jnp.int32))
    pos = 1000 + np.arange(3)[:, None]
    cos, sin = np.cos(pos * want) * 1.4158883083359672, np.sin(pos * want) * 1.4158883083359672
    a, b = np.asarray(x[0, :, :32]), np.asarray(x[0, :, 32:64])
    np.testing.assert_allclose(q[0, :, 0, :32], a * cos - b * sin, atol=2e-4)
    np.testing.assert_allclose(q[0, :, 0, 32:64], a * sin + b * cos, atol=2e-4)
    np.testing.assert_array_equal(q[0, :, 0, 64:], x[0, :, 64:])


def test_the_options_are_inert_by_default():
    """No option given: the parameters are what they were, and the kinds
    read from ``layer_types`` fall back to the configuration's own."""
    plain = MultiHeadAttention(64, 4, num_kv_heads=2, rope=True)
    assert not plain.extended and plain.window == 0 and not plain.head_gate
    assert plain.inv_freq is None and plain.trig_scale == 1.0
    assert plain.init_params(jax.random.key(0))["qkv"]["w"].shape == (64, 128)
    cfg = TransformerConfig(vocab_size=8, max_seq_len=8, dim=16, num_layers=2, num_heads=2)
    assert cfg.attention_kind(1) == AttentionKind() and cfg.window == 0
    assert cfg.window_layers == 0 and cfg.cache_layers == 2 and cfg.slot_state_shapes == ()
    with pytest.raises(ValueError, match="two kinds of output gate"):
        MultiHeadAttention(64, 4, gate=True, head_gate=True)


def test_layer_types_name_a_kind_for_every_layer():
    base = dict(vocab_size=8, max_seq_len=8, dim=16, num_layers=2, num_heads=2,
                pos_embedding="rope")
    kinds = {"f": AttentionKind(), "s": AttentionKind(num_heads=4, window=4)}
    cfg = TransformerConfig(**base, layer_types=("f", "s", "f"), attention_kinds=kinds)
    cfg.validate()
    assert (cfg.window_layers, cfg.cache_layers, cfg.window) == (1, 1, 4)
    assert TransformerLM(cfg).blocks[1].attn.num_heads == 4
    with pytest.raises(ValueError, match="attention_kinds entry"):
        TransformerConfig(**base, layer_types=("f", "x"), attention_kinds=kinds).validate()
    with pytest.raises(ValueError, match="attention_kinds entry"):
        TransformerConfig(**base, layer_types=("f",), attention_kinds=kinds).validate()
    with pytest.raises(ValueError, match="one window a model"):
        TransformerConfig(**base, layer_types=("s", "t"), attention_kinds={
            **kinds, "t": AttentionKind(window=8)}).validate()
    with pytest.raises(ValueError, match="without layer_types"):
        TransformerConfig(**base, attention_kinds=kinds).validate()
    with pytest.raises(ValueError, match="no ssm, gdn"):
        TransformerConfig(**base, layer_types=("f", "s"), attention_kinds=kinds,
                          scan_layers=True).validate()


# -- (b) the layers against the reference ----------------------------------------

def test_whole_model_apply_matches_the_reference_logits(tiny, reference):
    model, params, _ = tiny
    tokens = _prompt(T, seed=1)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params, "state": {}},
                          {"tokens": jnp.asarray(tokens)[None]}, mode="eval")[0]["logits"][0]
    want, _ = reference(tokens)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(want.std()) + 1e-5)
    # The window matters at this length: the reference without it differs.
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.logits(ref.make_params(jax.random.key(0), TINY), jnp.asarray(tokens),
                              TINY, window=False)
    assert float(jnp.abs(whole - want).max()) > 0.1


def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The share test: the routed parts of the two chips' shares of 4
    experts each, plus the ungated shared expert counted once, equal the
    uncut reference's whole layer of 8 experts."""
    whole_cfg = {**TINY, "num_experts": 8, "experts_held_offset": 0}
    p = ref.layer_params(jax.random.key(7), TINY, 8, False, all_experts=True)["moe"]
    x = jax.random.normal(jax.random.key(8), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.expert_layer(p, x, whole_cfg)
        shared = ref.swiglu(p["shared"], x)
        base = driver.transformer_config(TINY).routed_experts
        parts = 0.0
        for chip in range(2):
            cfg = dataclasses.replace(base, experts_held=(4 * chip, 4))
            params = {"router": {"w": p["w_r"]}, "shared": p["shared"],
                      "experts": jax.tree.map(lambda a: a[4 * chip:4 * chip + 4], p["experts"])}
            y, _ = RoutedExperts(64, cfg).apply({"params": params, "state": {}}, x)
            parts = parts + (y - shared)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


# -- (c) the serve path: pool, rings, programs -----------------------------------

@pytest.mark.parametrize("length", [5, W, W + 1, 30],
                         ids=["under", "the_window", "over", "chunks_over"])
def test_chunked_prefill_then_decode_matches_the_reference(tiny, reference, length):
    """A prompt shorter than the window, exactly the window, one longer and
    one of three chunks of 12 (a chunk longer than the window: its early
    rows need ring rows its later rows overwrite), then 20 decode waves —
    across ``W - 1 -> W`` and round the ring of 8 more than twice — through
    ``paged_step`` on a pool and rings left full of garbage: every wave's
    logits against the reference's whole-sequence forward."""
    model, params, _ = tiny
    sc = ServeConfig(max_slots=4, block_len=8, prefill_chunk=12)
    spec, mb, _, _ = sc.resolve(model.config)
    pages = tuple(jnp.full(shape, 3.0, dtype) for shape, dtype in spec.arrays)
    slot = 2
    table = np.zeros((4, mb), np.int32)
    table[slot, :7] = [5, 2, 7, 3, 9, 11, 1]
    prompt = _prompt(length, seed=length)
    step = jax.jit(model.paged_step)
    with jax.default_matmul_precision("highest"):
        for start in range(0, length - 1, 12):
            valid = min(12, length - 1 - start)
            chunk = np.full((1, 12), 95, np.int32)
            chunk[0, :valid] = prompt[start:start + valid]
            _, pages, _ = step(params, chunk, pages, table[slot:slot + 1],
                               np.asarray([start], np.int32), np.asarray([valid], np.int32),
                               np.asarray([slot], np.int32))
        seq, last, got = list(prompt[:-1]), int(prompt[-1]), []
        lengths = np.zeros((4,), np.int32)
        run = np.zeros((4,), np.int32)
        lengths[slot], run[slot] = length - 1, 1
        for _ in range(20):
            toks = np.zeros((4, 1), np.int32)
            toks[slot] = last
            logits, pages, _ = step(params, toks, pages, table, lengths.copy(), run)
            got.append(np.asarray(logits[slot]))
            seq.append(last)
            last = int(np.argmax(got[-1]))
            lengths[slot] += 1
    want, margin = reference(np.asarray(seq, np.int32))
    assert float(margin[length - 1:].min()) > 1e-4
    np.testing.assert_allclose(np.stack(got), want[length - 1:],
                               atol=2e-4 * float(want.std()) + 1e-5)


def test_engine_tokens_match_the_reference(four_slots, reference):
    """Four requests served together — under, at and over the window, and
    over several chunks — are the reference's greedy continuations."""
    prompts = [_prompt(n, seed=n) for n in (5, W, W + 1, 30)]
    for prompt, tokens in zip(prompts, _serve(four_slots, prompts)):
        _assert_greedy(reference, prompt, tokens)


def test_a_reused_slot_reads_nothing_stale(tiny, four_slots):
    """One slot serves a long request (its ring full), then a short one,
    then the long one again: each time the tokens are those the four-slot
    engine gives."""
    one = _engine(tiny, max_slots=1)
    a, b = _prompt(30, seed=4), _prompt(5, seed=5)
    first, second, third = _serve(one, [a, b, a])
    assert first == third
    assert [first, second] == _serve(four_slots, [a, b])


def test_evict_and_reprefill_gives_the_undisturbed_tokens(tiny, reference):
    """A pool too small for the load preempts and re-prefills (the ring of
    the evicted slot stays behind as garbage): every request still ends
    with the reference's greedy tokens."""
    prompts = [_prompt(n, seed=20 + n) for n in (6, 9, 11, 7, 10, 12)]
    starved = _engine(tiny, block_len=4, prefill_chunk=4, max_model_len=32, num_blocks=9)
    got = _serve(starved, prompts, new=18)
    assert starved.report()["requests"]["preemptions"] > 0
    for prompt, tokens in zip(prompts, got):
        _assert_greedy(reference, prompt, tokens)


def test_a_slot_that_does_not_run_keeps_its_ring_bitwise(four_slots):
    engine = four_slots.engine
    table = np.zeros((4, engine.max_blocks_per_seq), np.int32)
    table[0, :2], table[1, :2] = [1, 2], [3, 4]
    prompt = np.arange(1, 13, dtype=np.int32)
    for slot in (0, 1):
        engine.prefill(table[slot:slot + 1], prompt[None] + slot, np.asarray([0], np.int32),
                       np.asarray([12], np.int32), slot)
    before = [np.asarray(a) for a in engine.pages]
    s = engine.max_slots
    run = np.zeros((s,), bool)
    run[0] = True
    engine.decode(table, np.asarray([12, 12, 0, 0], np.int32), np.asarray([7, 9, 0, 0], np.int32),
                  run, np.full((s,), 40, np.int32), np.zeros((s,), np.float32),
                  np.zeros((s,), np.int32), np.ones((s,), np.float32),
                  np.full((s,), -1, np.int32), np.zeros((s,), np.int32))
    for was, now in zip(before[2:], engine.pages[2:]):
        now = np.asarray(now)
        # Slot 0 wrote position 12 at ring row 12 mod 8 = 4, nothing else.
        changed = np.argwhere((was != now).any(axis=-1))
        assert {(int(r[1]), int(r[2])) for r in changed} == {(0, 4)}


def test_the_decode_kernel_on_the_ring_is_the_xla_path():
    """``window_decode``, interpreted, against the XLA band path that a
    decode row takes off the kernel: slots before the window fills, at its
    edge and past it, and one that does not run (its ring unchanged, its
    row ignored)."""
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (4, 1, 8, 64))
    k_new, v_new = (jax.random.normal(k, (4, 1, 2, 64)) for k in ks[1:3])
    rings = [jax.random.normal(k, (3, 4, 16, 128)) for k in ks[3:5]]
    positions = jnp.asarray([0, 15, 16, 40], jnp.int32)
    valid = jnp.asarray([1, 1, 0, 1], jnp.int32)
    want = pa.window_attention(q, k_new, v_new, *rings, positions, valid, layer=1)
    got = pa.window_attention(q, k_new, v_new, *rings, positions, valid, layer=1,
                              interpret=True)
    live = np.asarray(valid) > 0
    np.testing.assert_allclose(got[0][live], want[0][live], atol=1e-5)
    for g, w, r in zip(got[1:], want[1:], rings):
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(g[:, 2], r[:, 2])


def test_the_pool_holds_two_full_layers_by_block_and_four_rings_by_slot(tiny):
    model, _, _ = tiny
    cfg = model.config
    assert (cfg.num_layers, cfg.cache_layers, cfg.window_layers, cfg.window) == (6, 2, 4, W)
    assert [b.window for b in model.blocks] == [0, W, W, W, 0, W]
    assert [b.attn.num_heads for b in model.blocks] == [6, 8, 8, 8, 6, 8]
    sc = ServeConfig(max_slots=4, block_len=8, prefill_chunk=12)
    spec, mb, num_blocks, _ = sc.resolve(cfg)
    assert spec.pages_shapes == ((2, num_blocks, 8, 128),) * 2
    assert spec.state_shapes == (((4, 4, W, 128), "float32"),) * 2 and spec.window == W
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=4, max_blocks_per_seq=mb, prefill_chunk=12)
    assert len(decode_args) == 15 and len(prefill_args) == 7      # + the slot


def test_the_decode_pages_record_counts_the_rings_rows(four_slots):
    """``serve/decode_pages`` carries ``window_rows``: over the running
    slots, ``min(length + 1, window)`` each."""
    from rocket_tpu.obs import spans

    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        _serve(four_slots, [_prompt(3, seed=1), _prompt(20, seed=2)], new=6)
    finally:
        spans.uninstall(recorder)
    records = [e.ids for e in recorder.events() if e.name == "serve/decode_pages"]
    assert records and all(0 < r["window_rows"] <= 2 * W for r in records)
    # The short one alone first (3 rows), then beside the long one's full ring.
    assert min(r["window_rows"] for r in records) == 3
    assert max(r["window_rows"] for r in records) >= W + 3


# -- (d) the configurations that were there --------------------------------------

def _linear_fixture():
    import test_gdn

    tc = test_gdn.driver.transformer_config(test_gdn.TINY)
    tc.activation_dtype = None
    weights = test_gdn.ref.make_params(jax.random.key(0), test_gdn.TINY, jnp.float32)
    return (TransformerLM(tc), test_gdn.ref.program_params(weights, test_gdn.TINY),
            ServeConfig(max_slots=4, block_len=8, prefill_chunk=8),
            [(2, 65, 8, 64)] * 2 + [(6, 4, 4, 16, 16), (6, 4, 384)], 7)


#: Greedy tokens of the Gated DeltaNet fixture, recorded at the commit
#: before window layers were added (b39cdce): three requests, prompts from
#: ``default_rng(7)``, 10 new tokens.
FROZEN_LINEAR = [[81, 20, 16, 77, 72, 49, 72, 49, 63, 71],
                 [59, 4, 30, 22, 38, 49, 72, 84, 40, 71],
                 [90, 12, 30, 63, 71, 56, 46, 16, 59, 84]]


@pytest.mark.parametrize("name", ["gpt2", "latent", "mamba", "linear"])
def test_the_configurations_that_were_there_build_no_ring(name):
    """The K/V pool, the latent pool, the Mamba state and the DeltaNet
    state beside pages: no window layer, no ring among the donated arrays,
    both programs' signatures as they were — and the DeltaNet fixture's
    greedy tokens bit for bit (``tests/test_gdn.py`` holds the other three's)."""
    import test_gdn

    if name == "linear":
        model, params, sc, pages, prefill_arity = _linear_fixture()
    elif name == "mamba":
        model, params, sc, pages, _, prefill_arity = test_gdn._mamba_fixture()
    else:
        model, params, sc, pages, _, prefill_arity = test_gdn._stateless(name)
    cfg = model.config
    assert (cfg.window, cfg.window_layers, cfg.layer_types) == (0, 0, ())
    assert all(b.window == 0 for b in model.blocks)
    spec, mb, _, _ = sc.resolve(cfg)
    assert spec.window == 0
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=sc.prefill_chunk)
    assert len(decode_args) == 15 and len(prefill_args) == prefill_arity
    assert [list(a.shape) for a in decode_args[1]] == [list(s) for s in pages]
    if name == "linear":
        engine = ServeEngine(model, params, sc)
        rng = np.random.default_rng(7)
        rids = [engine.submit(
            rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 14))).astype(np.int32),
            max_new_tokens=10) for _ in range(3)]
        engine.drain()
        assert [engine.result(r).tokens for r in rids] == FROZEN_LINEAR
