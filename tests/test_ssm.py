"""State-space layers beside attention: the Mamba-1 mixer (``nn/ssm.py``)
against the plain reference (``benchmark/reference/jamba_lm.py``), and a
hybrid model through the serve path — the per-slot state beside the paged
pool, what resets it, what leaves it alone. Tiny widths, float32, CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.reference import jamba_lm as ref
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.nn.ssm import MambaMixer
from rocket_tpu.serve import KVPoolSpec, ServeConfig, ServeEngine
from rocket_tpu.serve.engine import abstract_wave_inputs

#: Four layers, period 2, offset 1: mixer, attention, mixer, attention.
TINY = {
    "hidden_size": 64, "num_hidden_layers": 4, "vocab_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 1, "intermediate_size": 96,
    "mamba_expand": 2, "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attn_layer_period": 2, "attn_layer_offset": 1, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 64, "tie_word_embeddings": True,
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


def _serve(engine, prompts, new=12):
    rids = [engine.submit(p, max_new_tokens=new, temperature=0.0) for p in prompts]
    engine.drain()
    return [engine.result(r).tokens for r in rids]


def _prompts(n, seed=3, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# -- (a) the mixer's whole sequence against the literal recurrence -----------

@_highest
def test_mixer_whole_sequence_matches_the_reference_recurrence(hybrid):
    """Float32 both, the same equations: the program scans ``h`` as
    (d_state, d_inner), the reference as (d_inner, d_state) token by token;
    1e-5 is summation order in four small matmuls."""
    model, params, weights = hybrid
    x = jax.random.normal(jax.random.key(1), (2, 37, 64), jnp.float32)
    mixer = model.blocks[0].mixer
    assert isinstance(mixer, MambaMixer) and model.blocks[1].mixer is None
    got, _ = mixer.apply(
        {"params": params["blocks"]["0"]["mixer"], "state": {}}, x, mode="eval")
    for b in range(2):
        want, _ = ref.mamba(weights["layers"]["0"]["mamba"], x[b], TINY)
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


@_highest
def test_whole_model_apply_matches_the_reference_logits(hybrid):
    model, params, weights = hybrid
    tokens = np.random.default_rng(1).integers(0, 96, size=(2, 32)).astype(np.int32)
    out, _ = model.apply({"params": params, "state": {}},
                         {"tokens": jnp.asarray(tokens)}, mode="eval")
    for b in range(2):
        want = ref.logits(weights, jnp.asarray(tokens[b]), TINY)
        np.testing.assert_allclose(
            out["logits"][b], want, atol=2e-4 * float(jnp.std(want)) + 1e-5)


# -- (b) two chunks with padding, then decode, against the full forward ------

@_highest
def test_chunked_prefill_then_decode_matches_the_reference(hybrid):
    """A prompt of 13 through chunks of 8 (the second holds 4 real rows and
    4 of padding), then 12 decode waves through pool and state: the logits
    of every wave against the reference's full forward of the whole
    sequence. Float32 both; 2e-4 of the logits' spread is summation order
    through four layers (the recurrence itself is the same arithmetic), as
    for the latent fixture."""
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
        _, pages, _ = step(params, chunk, pages, table[slot:slot + 1],
                           np.asarray([start], np.int32), np.asarray([valid], np.int32),
                           np.asarray([slot], np.int32))
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
    want = ref.logits(weights, jnp.asarray(np.asarray(seq[:-1], np.int32)), TINY)[12:]
    np.testing.assert_allclose(
        np.stack(got), want, atol=2e-4 * float(jnp.std(want)) + 1e-5)


def test_engine_tokens_match_the_whole_sequence_forward(hybrid):
    """The same through ``ServeEngine``: greedy tokens of three requests
    equal ``generate()``'s, which runs the whole-sequence forward."""
    from rocket_tpu.models.transformer import generate

    model, params, _ = hybrid
    prompts = _prompts(3)
    for prompt, tokens in zip(prompts, _serve(_engine(hybrid), prompts)):
        want = generate(model, {"params": params, "state": {}}, prompt, 12,
                        temperature=0.0)[0, len(prompt):]
        assert tokens == np.asarray(want).tolist()


# -- (c), (d) what resets a slot's state -------------------------------------

def test_a_reused_slot_inherits_nothing(hybrid):
    """One slot: it serves A, then B. B's tokens are a fresh engine's."""
    a, b = _prompts(2, seed=5)
    both = _serve(_engine(hybrid, max_slots=1), [a, b])
    assert both[1] == _serve(_engine(hybrid, max_slots=1), [b])[0]
    assert both[0] == _serve(_engine(hybrid, max_slots=1), [a])[0]


def test_a_slot_frozen_in_flight_and_refilled_starts_from_zeros(hybrid):
    """One slot. A ends on an EOS the host learns only after it enqueued
    the next dispatch with A's slot in its run mask: the device's carried
    mask freezes the slot there (its state stays as A left it), then B is
    admitted into it. B's tokens are a fresh engine's, and A's end at the
    EOS."""
    a, b = _prompts(2, seed=5)
    alone = _serve(_engine(hybrid, max_slots=1), [a])[0]
    eos = alone[4]
    engine = _engine(hybrid, max_slots=1)
    asked = []
    dispatch = engine.engine.decode_dispatch

    def logged(*args):
        asked.append((bool(args[3][0]), bool(args[-1][0])))   # run, fresh
        return dispatch(*args)

    engine.engine.decode_dispatch = logged
    rids = [engine.submit(a, max_new_tokens=12, temperature=0.0, eos_token_id=eos),
            engine.submit(b, max_new_tokens=12, temperature=0.0)]
    engine.drain()
    got_a, got_b = (engine.result(r).tokens for r in rids)
    assert got_a == alone[:alone.index(eos) + 1] and len(got_a) < 12
    # A ran fresh once, then from the carry: one of those dispatches came
    # after its EOS (asked for, frozen); B joined fresh.
    assert asked[:len(got_a) + 1] == [(True, True)] + [(True, False)] * len(got_a)
    assert asked[len(got_a) + 1] == (True, True)
    assert got_b == _serve(_engine(hybrid, max_slots=1), [b])[0]


def test_evict_and_reprefill_gives_the_undisturbed_tokens(hybrid):
    """A pool too small for the load preempts and re-prefills (its blocks
    go, its slot's state stays behind as garbage): every request ends with
    the tokens of an engine that never evicts."""
    prompts = _prompts(8, seed=3, lo=4, hi=12)
    starved = _engine(hybrid, block_len=4, prefill_chunk=4, max_model_len=32,
                      num_blocks=9)
    got = _serve(starved, prompts, new=14)
    assert starved.report()["requests"]["preemptions"] > 0
    roomy = _engine(hybrid, block_len=4, prefill_chunk=4, max_model_len=32)
    assert got == _serve(roomy, prompts, new=14)
    assert roomy.report()["requests"]["preemptions"] == 0


# -- (e) what leaves a slot's state alone ------------------------------------

def test_a_slot_that_does_not_run_keeps_its_state_bitwise(hybrid):
    model, params, _ = hybrid
    engine = _engine(hybrid).engine
    prompt = np.arange(1, 17, dtype=np.int32)
    table = np.zeros((4, 8), np.int32)
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


def test_a_chunk_of_a_stateful_model_must_name_its_slot(hybrid):
    engine = _engine(hybrid).engine
    chunk = (np.zeros((1, 8), np.int32), np.zeros((1, 8), np.int32),
             np.asarray([0], np.int32), np.asarray([8], np.int32))
    with pytest.raises(ValueError, match="needs the slot"):
        engine.prefill(*chunk)
    assert engine.prefill_chunks == 0
    engine.prefill(*chunk, 2)
    assert engine.prefill_chunks == 1


def test_a_shape_the_kernel_does_not_take_is_said_on_a_tpu(hybrid, monkeypatch):
    """Off the CPU the kernel is the default; a shape outside
    ``ssm_kernel_supported`` (5 rows here) runs the scan, several times the
    cost, and says so once. On the CPU the scan is the path, silently."""
    import warnings

    from rocket_tpu.nn import ssm

    model, params, _ = hybrid
    mixer, p = model.blocks[0].mixer, params["blocks"]["0"]["mixer"]
    x = jax.random.normal(jax.random.key(4), (1, 5, 64), jnp.float32)
    state = tuple(jnp.zeros((1, 1) + shape, dtype)
                  for shape, dtype in mixer.config.state_shapes(jnp.float32))
    call = lambda: mixer.apply_state(p, x, state, jnp.asarray([0]), jnp.asarray([5]))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        quiet, _ = call()
    monkeypatch.setattr(ssm, "_on_cpu", lambda: False)
    with pytest.warns(UserWarning, match="does not take .* 5 rows"):
        said, _ = call()
    np.testing.assert_array_equal(quiet, said)


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
def test_rows_past_valid_change_nothing(hybrid, kernel):
    """A chunk of 8 with 5 real rows: whatever the 3 rows of padding hold,
    the state and the real rows' outputs are the same bits; and they are
    those of the 5 rows alone followed by an empty chunk."""
    model, params, _ = hybrid
    mixer, p = model.blocks[0].mixer, params["blocks"]["0"]["mixer"]
    c = mixer.config
    x = jax.random.normal(jax.random.key(4), (1, 8, 64), jnp.float32)
    state0 = tuple(jax.random.normal(jax.random.key(9), (2, 8) + shape, dtype)
                   for shape, dtype in c.state_shapes(jnp.float32))
    args = dict(layer=1, slots=jnp.asarray([3]), kernel=kernel, interpret=kernel)
    pos, valid = jnp.asarray([8]), jnp.asarray([5])

    def run(pad):
        xp = x.at[:, 5:].set(pad)
        return mixer.apply_state(p, xp, state0, pos, valid, **args)

    (out_a, state_a), (out_b, state_b) = run(0.0), run(1e3)
    np.testing.assert_array_equal(out_a[:, :5], out_b[:, :5])
    for a, b, was in zip(state_a, state_b, state0):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(was)[0])
        np.testing.assert_array_equal(np.asarray(a)[1, :3], np.asarray(was)[1, :3])
    _, state_c = mixer.apply_state(p, x, state_a, jnp.asarray([13]),
                                   jnp.asarray([0]), **args)
    for a, c_ in zip(state_a, state_c):
        np.testing.assert_array_equal(a, c_)


@_highest
def test_the_kernel_computes_what_the_scan_computes(hybrid):
    """The Pallas kernel, interpreted, against the ``lax.scan``: a chunk
    from a carried state with padding, and a wave of 8 slots of which two
    do not run and one starts afresh."""
    model, params, _ = hybrid
    mixer, p = model.blocks[2].mixer, params["blocks"]["2"]["mixer"]
    state0 = tuple(jax.random.normal(jax.random.key(8), (2, 8) + shape, dtype)
                   for shape, dtype in mixer.config.state_shapes(jnp.float32))
    chunk = jax.random.normal(jax.random.key(5), (2, 16, 64), jnp.float32)
    wave = jax.random.normal(jax.random.key(6), (8, 1, 64), jnp.float32)
    cases = [
        (chunk, dict(positions=jnp.asarray([16, 0]), valid=jnp.asarray([16, 11]),
                     slots=jnp.asarray([6, 1]))),
        (wave, dict(positions=jnp.asarray([5, 9, 0, 0, 3, 1, 7, 2]),
                    valid=jnp.asarray([1, 0, 1, 0, 1, 1, 1, 1]))),
    ]
    for x, kw in cases:
        pos, valid = kw.pop("positions"), kw.pop("valid")
        want, state_w = mixer.apply_state(p, x, state0, pos, valid, layer=1,
                                          kernel=False, **kw)
        got, state_g = mixer.apply_state(p, x, state0, pos, valid, layer=1,
                                         kernel=True, interpret=True, **kw)
        rows = np.arange(x.shape[1])[None, :] < np.asarray(valid)[:, None]
        np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                                   atol=1e-5, rtol=1e-5)
        for g, w in zip(state_g, state_w):
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


# -- (f) k waves a dispatch ---------------------------------------------------

def test_two_waves_a_dispatch_give_the_tokens_of_one(hybrid):
    prompts = _prompts(5, seed=11)
    assert _serve(_engine(hybrid, decode_waves_per_dispatch=2), prompts) == \
        _serve(_engine(hybrid), prompts)


# -- (g) the pool, and the models without state layers -----------------------

def test_the_pool_counts_caching_layers_and_holds_state_by_slot(hybrid):
    model, _, _ = hybrid
    cfg = model.config
    assert (cfg.num_layers, cfg.cache_layers, cfg.state_layers) == (4, 2, 2)
    sc = ServeConfig(max_slots=4, block_len=8, prefill_chunk=8)
    spec, mb, num_blocks, _ = sc.resolve(cfg)
    assert spec.pages_shapes == ((2, num_blocks, 8, 16),) * 2
    assert spec.state_shapes == (((2, 4, 16, 128), "float32"),
                                 ((2, 4, 3 * 128), "float32"))
    assert spec.pool_bytes == num_blocks * spec.block_bytes + spec.state_bytes
    assert spec.state_bytes == 2 * 4 * (16 * 128 + 3 * 128) * 4
    assert [a.shape for a in spec.init_pages()] == [s for s, _ in spec.arrays]
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=4, max_blocks_per_seq=mb, prefill_chunk=8)
    assert len(decode_args) == 15 and len(prefill_args) == 7   # + the slot
    assert [a.shape for a in decode_args[1]] == [s for s, _ in spec.arrays]
    with pytest.raises(ValueError, match="max_slots"):
        KVPoolSpec(num_layers=1, num_blocks=2, block_len=4, lanes=(8, 8),
                   slot_state=((1, (4,), "float32"),))


def test_a_mixed_stack_cannot_be_scanned_or_mispatterned():
    from rocket_tpu.nn.ssm import SSMConfig

    base = dict(vocab_size=8, max_seq_len=8, dim=16, num_layers=2, num_heads=2,
                pos_embedding="none", ssm=SSMConfig(d_inner=128, dt_rank=4))
    TransformerConfig(**base, attn_layer_period=2, attn_layer_offset=1).validate()
    with pytest.raises(ValueError, match="scan_layers"):
        TransformerConfig(**base, scan_layers=True).validate()
    with pytest.raises(ValueError, match="attn_layer_offset"):
        TransformerConfig(**base, attn_layer_period=2, attn_layer_offset=2).validate()
    with pytest.raises(ValueError, match="without ssm"):
        TransformerConfig(**{**base, "ssm": None}, attn_layer_period=2).validate()
    with pytest.raises(ValueError, match="pos_embedding"):
        TransformerConfig(**{**base, "pos_embedding": "sinusoid"}).validate()


#: Greedy tokens of the two stateless fixtures below, recorded at the commit
#: before state layers came (1aceb4d): three requests each, prompts from
#: ``default_rng(7)``, 10 new tokens.
FROZEN = {
    "gpt2": [[26, 36, 14, 30, 47, 36, 47, 38, 30, 36],
             [48, 47, 40, 34, 40, 40, 35, 40, 40, 40],
             [34, 36, 34, 34, 34, 34, 40, 40, 34, 40]],
    "latent": [[65, 17, 35, 56, 4, 79, 50, 33, 27, 89],
               [81, 20, 16, 77, 81, 20, 16, 77, 22, 38],
               [1, 58, 67, 4, 7, 67, 4, 46, 37, 9]],
}


def _gpt2_fixture():
    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0))
    params = jax.jit(model.init)(jax.random.key(0))["params"]
    return model, params, ServeConfig(max_slots=4, block_len=4, prefill_chunk=4), \
        [(2, 65, 4, 32)] * 2, 133120


def _latent_fixture():
    import test_latent_moe as latent

    params = latent.ref.program_params(latent._weights(), latent.TINY)
    return latent._model(), params, \
        ServeConfig(max_slots=2, block_len=8, prefill_chunk=8), [(3, 17, 8, 128)], 208896


@pytest.mark.parametrize("name,fixture", [("gpt2", _gpt2_fixture),
                                          ("latent", _latent_fixture)])
def test_models_without_state_layers_are_what_they_were(name, fixture):
    """The K/V pool and the latent pool, both programs' signatures and the
    greedy tokens, bit for bit as before this module."""
    model, params, sc, pages, pool_bytes = fixture()
    assert model.config.slot_state_shapes == ()
    assert model.config.cache_layers == model.config.num_layers
    spec, mb, _, _ = sc.resolve(model.config)
    assert spec.state_shapes == () and spec.state_bytes == 0
    assert spec.pool_bytes == pool_bytes
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=sc.prefill_chunk)
    assert len(decode_args) == 15 and len(prefill_args) == 6
    engine = ServeEngine(model, params, sc)
    assert [list(a.shape) for a in engine.engine.pages] == [list(s) for s in pages]
    rng = np.random.default_rng(7)
    rids = [engine.submit(
        rng.integers(0, model.config.vocab_size, size=int(rng.integers(3, 14))).astype(np.int32),
        max_new_tokens=10) for _ in range(3)]
    engine.drain()
    assert [engine.result(r).tokens for r in rids] == FROZEN[name]
