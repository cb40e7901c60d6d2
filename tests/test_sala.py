"""Lightning attention (``nn/lightning.py``) and block-sparse attention over
the paged pool (``ops/paged_attention.py`` ``sparse_attention``, the
kernels ``lightning_step`` / ``lightning_chunk`` / ``sparse_decode`` /
``sparse_prefill``) against the plain reference
(``benchmark/reference/minicpm_sala_lm.py``), and a model of four tiny
layers (lightning, sparse, lightning, sparse) through the serve path: the
selection's exact page sets, prefill then decode across the switch from
dense to sparse attention, eviction and re-prefill, a slot that does not
run. A scaled ``sparse_config``: ``dense_len`` 128, blocks of 16, units of
8 every 4, ``topk`` 4, a window of 32. Tiny widths, float32, CPU."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_sala as driver
from benchmark.reference import minicpm_sala_lm as ref
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.nn.lightning import (
    LightningConfig,
    alibi_slopes,
    lightning_chunk,
    lightning_step,
)
from rocket_tpu.ops import paged_attention as pa
from rocket_tpu.ops.select_scores import select_scores, select_scores_supported
from rocket_tpu.ops.sparse_prefill import sparse_prefill, sparse_prefill_supported
from rocket_tpu.serve import ServeConfig, ServeEngine

TINY = json.loads((Path(__file__).resolve().parents[1]
                   / "benchmark/tests/fixtures_sala/configs/tiny-sala.json").read_text())
SC = pa.SparseAttentionConfig(**TINY["sparse_config"])
DENSE = SC.dense_len


@pytest.fixture(scope="module")
def tiny():
    """``(model, program params, reference params)``, float32."""
    tc = driver.transformer_config(TINY)
    tc.activation_dtype = None
    weights = ref.make_params(jax.random.key(0), TINY, jnp.float32)
    return TransformerLM(tc), ref.program_params(weights, TINY), weights


@pytest.fixture(scope="module")
def reference(tiny):
    """``tokens -> (logits (T, V), margin (T,))`` of the reference, the
    sequence padded to whole query blocks."""
    _, _, weights = tiny
    fn = jax.jit(lambda tokens: ref.logits(weights, tokens, TINY))

    def run(tokens):
        n = len(tokens)
        padded = np.zeros((-(-n // 64) * 64,), np.int32)
        padded[:n] = tokens
        with jax.default_matmul_precision("highest"):
            logits, margin = jax.device_get(fn(jnp.asarray(padded)))
        return logits[:n], margin[:n]

    return run


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], size=n).astype(np.int32)


def _engine(tiny, **over):
    model, params, _ = tiny
    sc = dict(max_slots=4, block_len=16, prefill_chunk=64)
    sc.update(over)
    return ServeEngine(model, params, ServeConfig(**sc))


def _serve(engine, prompts, new=12):
    rids = [engine.submit(p, max_new_tokens=new, temperature=0.0) for p in prompts]
    engine.drain()
    return [engine.result(r).tokens for r in rids]


def _assert_greedy(reference, prompt, tokens):
    """Each served token is the reference's best at its position, or
    within a rounding's reach of it where the reference's own selection
    was near a tie."""
    logits, margin = reference(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))
    p = len(prompt)
    for j, tok in enumerate(tokens):
        row = logits[p - 1 + j]
        gap = float(row.max() - row[tok])
        assert gap < 1e-3 or margin[p - 1 + j] < 1e-3, (j, gap)


# -- (a) the lightning rule ------------------------------------------------------

LCFG = LightningConfig(num_heads=2, head_dim=128, published_layers=32, first_layer=13)


def _literal(s0, q, k, v, log_decay, valid, fresh):
    """The recurrence token by token in numpy float64: ``s0`` (S, H, d,
    d); ``q``, ``k``, ``v`` (S, C, H, d)."""
    lam = np.exp(np.asarray(log_decay, np.float64))
    s = np.where(np.asarray(fresh)[:, None, None, None], 0.0, np.asarray(s0, np.float64))
    out = np.zeros(q.shape, np.float64)
    for i in range(q.shape[0]):
        for t in range(int(valid[i])):
            s[i] = lam[:, None, None] * s[i] + np.einsum(
                "hk,hv->hkv", k[i, t].astype(np.float64), v[i, t].astype(np.float64))
            out[i, t] = np.einsum("hk,hkv->hv", q[i, t].astype(np.float64), s[i])
    return out, s


@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_the_chunk_rule_is_the_literal_recurrence(form):
    """Two slots' chunks (one from a carried state, one afresh with rows
    past ``valid``) through ``lightning_chunk``: outputs of the real rows
    and the states left in the slots against the recurrence; every other
    slot's state untouched."""
    h, d, c = LCFG.num_heads, LCFG.head_dim, 32
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (2, c, h * d)) * 0.1
    k = jax.random.normal(ks[1], (2, c, h * d)) * 0.1
    v = jax.random.normal(ks[2], (2, c, h * d))
    s_all = jax.random.normal(ks[3], (2, 3, h, d, d))
    slots, valid = np.array([2, 0], np.int32), np.array([32, 20], np.int32)
    fresh = np.array([False, True])
    log_decay = jnp.asarray(LCFG.log_decay(15), jnp.float32)
    o, s_new = lightning_chunk(s_all, q, k, v, log_decay, 1, jnp.asarray(slots),
                               jnp.asarray(valid), jnp.asarray(fresh), cfg=LCFG,
                               kernel=form == "kernel", interpret=True)
    heads = lambda a: np.asarray(a).reshape(2, c, h, d)
    want, s_want = _literal(np.asarray(s_all)[1, slots], heads(q), heads(k), heads(v),
                            log_decay, valid, fresh)
    o = heads(o)
    for i in range(2):
        np.testing.assert_allclose(o[i, :valid[i]], want[i, :valid[i]], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_new)[1, slots], s_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(s_new)[1, 1], np.asarray(s_all)[1, 1])
    np.testing.assert_array_equal(np.asarray(s_new)[0], np.asarray(s_all)[0])


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_wave_is_one_token_of_the_chunk(kernel):
    """A wave over four slots — two running (one afresh), two not —
    against one row of the literal recurrence; a slot that does not run
    keeps its state bitwise and gets zeros."""
    h, d = LCFG.num_heads, LCFG.head_dim
    ks = jax.random.split(jax.random.key(4), 4)
    q, k = (jax.random.normal(ks[i], (4, h * d)) * 0.1 for i in range(2))
    v = jax.random.normal(ks[2], (4, h * d))
    s_all = jax.random.normal(ks[3], (1, 4, h, d, d))
    valid = np.array([1, 0, 1, 0], np.int32)
    fresh = np.array([False, False, True, True])
    log_decay = jnp.asarray(LCFG.log_decay(20), jnp.float32)
    o, s_new = lightning_step(s_all, q, k, v, log_decay, 0, jnp.asarray(valid),
                              jnp.asarray(fresh), cfg=LCFG, kernel=kernel, interpret=True)
    rows = lambda a: np.asarray(a).reshape(4, 1, h, d)
    want, s_want = _literal(np.asarray(s_all)[0], rows(q), rows(k), rows(v), log_decay,
                            valid, fresh)
    np.testing.assert_allclose(np.asarray(o).reshape(4, h, d), want[:, 0], rtol=1e-4, atol=1e-4)
    for i in (0, 2):
        np.testing.assert_allclose(np.asarray(s_new)[0, i], s_want[i], rtol=1e-4, atol=1e-4)
    for i in (1, 3):
        np.testing.assert_array_equal(np.asarray(s_new)[0, i], np.asarray(s_all)[0, i])
        assert not np.asarray(o)[i].any()


def test_the_decay_reads_the_published_index():
    """``lambda = exp(-2^(-h/4) (1 - l / 31 + 1e-5))`` at 32 heads, ``l``
    the published index: the mixer of the stack's layer ``i`` is the
    published ``first_layer + i``, and the reference's decay is the
    program's."""
    slopes = alibi_slopes(32)
    np.testing.assert_allclose(slopes, 2.0 ** (-np.arange(1, 33) / 4))
    cfg = LightningConfig(num_heads=32, head_dim=128, published_layers=32, first_layer=13)
    for layer in (13, 20):
        want = -slopes * (1 - layer / 31 + 1e-5)
        np.testing.assert_allclose(cfg.log_decay(layer), want)
    mixer = cfg.make_mixer(4096, layer=3)
    assert mixer.layer == 16
    np.testing.assert_allclose(mixer.log_decay, cfg.log_decay(16).astype(np.float32))
    assert float(np.exp(cfg.log_decay(13)[0])) == pytest.approx(math.exp(-0.8408964 * 0.5806552), rel=1e-5)
    tc = driver.transformer_config(TINY)
    model = TransformerLM(tc)
    lightning = [b.mixer for b in model.blocks if b.mixer is not None]
    assert [m.layer for m in lightning] == [5, 7]
    for i, m in zip((0, 2), lightning):
        np.testing.assert_array_equal(m.log_decay, ref.log_decay(TINY, i))


# -- (b) the selection and the sparse kernels -----------------------------------

def _pool_with_keys(keys, bl=16, seed=0):
    """One slot's ``keys`` (T, lanes) written into a one-layer pool at a
    shuffled table: ``(k_pages, table)``."""
    t, lanes = keys.shape
    mb = t // bl
    table = np.random.default_rng(seed).permutation(np.arange(1, mb + 1)).astype(np.int32)
    pages = np.zeros((1, mb + 1, bl, lanes), np.float32)
    pages[0, table] = keys.reshape(mb, bl, lanes)
    return jnp.asarray(pages), jnp.asarray(table[None])


def test_compressed_keys_are_each_units_mean_across_chunk_edges():
    """Units written chunk by chunk (chunks of 24 rows: every edge cuts a
    unit) are the mean of their 8 keys, each written once its last key is
    in, none before."""
    t, lanes = 96, 64
    keys = np.random.default_rng(1).standard_normal((t, lanes)).astype(np.float32)
    pages, table = _pool_with_keys(keys)
    kc = jnp.full((1, 2, t // SC.kernel_stride, lanes), 7.0, jnp.float32)
    for start in range(0, 72, 24):
        kc = pa.write_compressed_keys(
            kc, pages, table, jnp.asarray([start], jnp.int32), jnp.asarray([24], jnp.int32),
            rows=24, layer=0, slots=jnp.asarray([1], jnp.int32), cfg=SC)
    got = np.asarray(kc)[0, 1]
    for j in range(t // SC.kernel_stride):
        end = j * SC.kernel_stride + SC.kernel_size - 1
        if end < 72:
            np.testing.assert_allclose(got[j], keys[4 * j:4 * j + 8].mean(0), rtol=1e-6)
        else:
            assert (got[j] == 7.0).all(), j
    assert (np.asarray(kc)[0, 0] == 7.0).all()


def test_the_selection_is_the_references_page_sets():
    """Queries at positions under ``dense_len``, at it and past it, with
    the compressed keys of a random sequence: each row's ``topk`` blocks
    (the first, the window's, the best scored) are exactly the reference's,
    and a row under ``dense_len`` is marked dense — through the XLA loop
    over heads (heads of 32) and through the kernel ``select_scores``,
    interpreted (heads of 128 against a cache of 128 units, the last 49
    never written), every row reading slot 0 of the cache."""
    t, h_kv, g = 320, 2, 2
    positions = np.array([5, 100, 127, 128, 129, 200, 255, 319], np.int32)
    for d, units, interpret in ((32, t // SC.kernel_stride, None), (128, 128, True)):
        assert select_scores_supported(1, h_kv * g, h_kv, d, units) == bool(interpret)
        rng = np.random.default_rng(2)
        keys = rng.standard_normal((t, h_kv * d)).astype(np.float32)
        pages, table = _pool_with_keys(keys)
        kc = jnp.zeros((1, 1, units, h_kv * d), jnp.float32)
        kc = pa.write_compressed_keys(kc, pages, table, jnp.asarray([0], jnp.int32),
                                      jnp.asarray([t], jnp.int32), rows=t, layer=0,
                                      slots=jnp.asarray([0], jnp.int32), cfg=SC)
        q = rng.standard_normal((len(positions), h_kv * g, d)).astype(np.float32) * 2
        with jax.default_matmul_precision("highest"):
            top, sparse = pa.select_pages(
                jnp.asarray(q)[:, None], kc, jnp.asarray(positions)[:, None], h_kv=h_kv,
                cfg=SC, slots=jnp.zeros(len(positions), jnp.int32), interpret=interpret)
        cfg = dict(TINY, num_key_value_heads=h_kv, num_attention_heads=h_kv * g, head_dim=d)
        pick, margin = ref.selection(jnp.asarray(q), ref.compressed_keys(
            jnp.asarray(keys).reshape(t, h_kv, d), cfg), jnp.asarray(positions), cfg)
        pick = np.asarray(pick)
        np.testing.assert_array_equal(np.asarray(sparse)[:, 0], positions >= DENSE)
        for i, p in enumerate(positions):
            for head in range(h_kv):
                want = set(np.flatnonzero(pick[i, head]))
                if p < DENSE:
                    assert want == set(range(p // SC.block_size + 1))
                    continue
                got = set(np.asarray(top)[i, 0, head].tolist())
                assert got == want, (d, p, head, got, want)
                assert 0 in got and p // SC.block_size in got
                assert max(got) * SC.block_size <= p
        # No margin where a row attends densely; past it, the 4th pick is
        # forced (block 0 and the window's 3) unless the window holds fewer
        # blocks.
        margin = np.asarray(margin)
        assert np.isinf(margin[positions < DENSE]).all()
        assert np.isfinite(margin[positions == 319]).all()


_SCORE_CASES = {
    # slots past dense_len, under it, one that does not run, one at 3 (no
    # complete unit)
    "wave": dict(c=1, positions=[300, 90, 200, 3], valid=[1, 1, 0, 1],
                 slots=None, tile=256, rows=None),
    # a chunk of 32 rows from 112 in blocks of 8: rows under dense_len and
    # past it; the second slot row's last 12 rows are padding
    "chunk_across_dense_len": dict(c=32, positions=[112, 400], valid=[32, 20],
                                   slots=[2, 0], tile=128, rows=8),
    # the first chunk of a prompt: its first 7 rows complete no unit
    "no_complete_unit": dict(c=16, positions=[0], valid=[16], slots=[1], tile=128,
                             rows=None),
    # 200 live units: a whole tile of 128 and 72 of the next
    "ragged_live": dict(c=1, positions=[806, 806], valid=[1, 1], slots=[3, 1],
                        tile=128, rows=None),
    # a block of 8 rows whose first row completes 126 units and whose last
    # 128: the first tile is whole for some of its rows only
    "tile_edge_in_a_block": dict(c=16, positions=[508], valid=[16], slots=[2],
                                 tile=128, rows=8),
}


@pytest.mark.parametrize("case", sorted(_SCORE_CASES))
def test_the_select_scores_kernel_is_the_loop_over_heads(case):
    """The unit scores ``c_sum`` of the kernel ``select_scores``
    (interpreted: 4 heads over 2 K/V heads of 128, 256 units of a
    4-slot, 2-layer cache read at layer 1) against ``_unit_scores``' loop
    over heads, float32; a slot row that does not run reads -1."""
    spec = _SCORE_CASES[case]
    c, h_kv, g, d, units = spec["c"], 2, 2, 128, 256
    positions = jnp.asarray(spec["positions"], jnp.int32)
    valid = jnp.asarray(spec["valid"], jnp.int32)
    s = len(spec["positions"])
    slots = jnp.arange(s, dtype=jnp.int32) if spec["slots"] is None \
        else jnp.asarray(spec["slots"], jnp.int32)
    assert select_scores_supported(c, h_kv * g, h_kv, d, units)
    rng = np.random.default_rng(7)
    kc = jnp.asarray(rng.standard_normal((2, 4, units, h_kv * d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((s, c, h_kv * g, d)), jnp.float32)
    pos = positions[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    got = np.asarray(select_scores(
        q, kc, 1, slots, positions, valid, stride=SC.kernel_stride,
        size=SC.kernel_size, tile=spec["tile"], rows=spec["rows"], interpret=True))
    want = np.asarray(pa._unit_scores(q, kc[1, slots], pos, h_kv=h_kv, cfg=SC))
    assert got.shape == want.shape == (s, c, h_kv, units)
    runs = np.asarray(valid) > 0
    np.testing.assert_allclose(got[runs], want[runs], rtol=1e-5, atol=1e-5)
    assert (got[~runs] == -1).all()
    assert np.isfinite(got).all()
    complete = (np.arange(units) * SC.kernel_stride + SC.kernel_size - 1)[None, None] \
        <= np.asarray(pos)[..., None]                                  # (S, C, U)
    complete = np.broadcast_to(complete[:, :, None], got.shape)
    assert (got[runs][~complete[runs]] == -1).all()
    # Each row's complete units hold a softmax a head, summed over g heads.
    sums = np.where(complete, got, 0).sum(-1)[runs]
    has = complete.any(-1)[runs]
    assert has.any() and not has.all() if case == "no_complete_unit" else has.any()
    np.testing.assert_allclose(sums[has], g, rtol=1e-5)


def test_sparse_decode_is_dense_attention_over_the_listed_pages():
    """The fused walk (interpreted) over three slots' page lists — a slot
    past ``dense_len``, one under it, one that does not run — against
    softmax attention over exactly the keys those pages hold up to each
    slot's position; the XLA gather gives the same."""
    s, h_kv, g, d, bl, mb = 3, 2, 4, 128, 16, 24
    rng = np.random.default_rng(5)
    kp = jnp.asarray(rng.standard_normal((1, 1 + s * mb, bl, h_kv * d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1, 1 + s * mb, bl, h_kv * d)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(s * mb).reshape(s, mb), jnp.int32)
    q = jnp.asarray(rng.standard_normal((s, h_kv * g, d)), jnp.float32)
    positions = jnp.asarray([300, 90, 200], jnp.int32)
    nl = 8
    ids = np.zeros((s, h_kv, nl), np.int32)
    ids[0, 0, :4], ids[0, 1, :4] = [0, 3, 17, 18], [0, 9, 11, 18]
    ids[1, :, :6] = np.arange(6)
    count = np.array([[4, 4], [6, 6], [0, 0]], np.int32)
    got = pa._sparse_decode_pallas(q, kp, vp, table, jnp.asarray(ids), jnp.asarray(count),
                                   positions, 0, interpret=True)
    xla = pa._sparse_decode_xla(q, kp, vp, table, jnp.asarray(ids), jnp.asarray(count),
                                positions, 0)
    for i in range(2):
        for head in range(h_kv):
            rows = [b * bl + r for b in ids[i, head, :count[i, head]] for r in range(bl)
                    if b * bl + r <= int(positions[i])]
            page = np.asarray(table)[i, np.asarray(rows) // bl]
            k = np.asarray(kp)[0, page, np.asarray(rows) % bl, head * d:(head + 1) * d]
            v = np.asarray(vp)[0, page, np.asarray(rows) % bl, head * d:(head + 1) * d]
            qh = np.asarray(q)[i, head * g:(head + 1) * g]
            w = np.exp(qh @ k.T / math.sqrt(d) - (qh @ k.T / math.sqrt(d)).max(-1, keepdims=True))
            want = (w / w.sum(-1, keepdims=True)) @ v
            np.testing.assert_allclose(np.asarray(got)[i, head * g:(head + 1) * g], want,
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(xla)[i, head * g:(head + 1) * g], want,
                                       rtol=1e-4, atol=1e-4)
    assert not np.asarray(got)[2].any()


def test_the_sparse_prefill_kernel_is_the_xla_walk_and_copies_no_unpicked_page():
    """A chunk of 128 rows at position 256 with random picks (block 0
    always): the kernel (interpreted) against the XLA walk; the kernel
    again with every page no row picked full of NaN gives the same."""
    s, c, hq, h_kv, d, bl, mb = 1, 128, 4, 2, 128, 16, 32
    assert sparse_prefill_supported(c, hq, h_kv, d, bl, mb * bl, 2)
    ks = jax.random.split(jax.random.key(6), 4)
    kp = jax.random.normal(ks[0], (1, mb + 1, bl, h_kv * d)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[1], (1, mb + 1, bl, h_kv * d)).astype(jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, mb + 1))[None], jnp.int32)
    q = jax.random.normal(ks[2], (s, c, hq, d)).astype(jnp.bfloat16)
    positions, valid = jnp.asarray([256], jnp.int32), jnp.asarray([100], jnp.int32)
    pick = jax.random.bernoulli(ks[3], 0.2, (s, h_kv, c, mb)) | (jnp.arange(mb) == 0)
    pick = pick & ((jnp.arange(mb) * bl)[None, None, None] <= (256 + jnp.arange(c))[None, None, :, None])
    pick = pick.at[:, 0, :, 5].set(False)                 # no row of head 0 picks page 5
    want = pa._sparse_chunk_xla(q, kp, vp, table, positions, valid, 0, pick)
    got = sparse_prefill(q, kp, vp, table, positions, valid, pick, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[:, :100], np.asarray(want)[:, :100])
    unpicked = ~np.asarray(pick).any(axis=2)[0]             # (Hkv, MB)
    poisoned = np.array(kp, np.float32)
    for head in range(h_kv):
        for b in np.flatnonzero(unpicked[head]):
            poisoned[0, int(table[0, b]), :, head * d:(head + 1) * d] = np.nan
    again = sparse_prefill(q, jnp.asarray(poisoned, jnp.bfloat16), vp, table, positions,
                           valid, pick, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(again)[:, :100], np.asarray(want)[:, :100])


# -- (c) the model: muP scalings, the serve path ----------------------------------

def test_the_mup_scalings_are_off_by_default_and_scale_where_they_say(tiny):
    """Defaults 1: a configuration without them builds no factor. With
    them: the embedding times ``scale_emb``, each branch times ``alpha``,
    the last hidden state over the width ratio — the tiny model's logits
    move, and those of a model whose logit divisor alone is 16 are the
    plain ones over 16 (every norm is an RMSNorm, the head is linear)."""
    model, params, _ = tiny
    base = TransformerConfig(vocab_size=8, max_seq_len=8, dim=8, num_layers=1, num_heads=2)
    assert (base.embed_scale, base.residual_scale, base.logit_divisor) == (1.0, 1.0, 1.0)
    tc = model.config
    z = ref.sizes(TINY)
    assert tc.embed_scale == 12.0 and tc.logit_divisor == 128 / 32
    assert tc.residual_scale == pytest.approx(1.4 / math.sqrt(16)) == z["alpha"]
    assert all(b.branch_scale == tc.residual_scale for b in model.blocks)

    def last_logits(cfg):
        m = TransformerLM(cfg)
        spec, mb, _, _ = ServeConfig(max_slots=1, block_len=16, prefill_chunk=32).resolve(cfg)
        pages = spec.init_pages()
        table = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]
        tokens = jnp.asarray(_prompt(32, seed=9))[None]
        logits, _, _ = m.paged_step(params, tokens, pages, table, jnp.zeros((1,), jnp.int32),
                                    jnp.full((1,), 32, jnp.int32), jnp.zeros((1,), jnp.int32))
        return np.asarray(logits)

    plain = dataclasses.replace(tc, embed_scale=1.0, residual_scale=1.0, logit_divisor=1.0)
    with jax.default_matmul_precision("highest"):
        full, none = last_logits(tc), last_logits(plain)
        divided = last_logits(dataclasses.replace(plain, logit_divisor=16.0))
    np.testing.assert_allclose(divided, none / 16, rtol=1e-5, atol=1e-6)
    assert np.abs(full - none).max() > 0.1 * np.abs(none).std()


def test_the_layer_kinds_say_which_layers_hold_state_pages_and_keys(tiny):
    """``layer_types`` is the one description: lightning kinds are state
    layers (the matrix state a slot), sparse kinds cache pages (the
    layer rows of the pool) and compressed keys (the last per-slot array,
    a row every ``kernel_stride`` positions); the sparse kind has no
    rotary."""
    model, _, _ = tiny
    tc = model.config
    assert [tc.is_state_layer(i) for i in range(4)] == [True, False, True, False]
    assert (tc.state_layers, tc.cache_layers, tc.sparse_layers, tc.window_layers) == (2, 2, 2, 0)
    assert tc.slot_state_shapes == ((2, (4, 32, 32), "float32"), (2, (128, 64), "float32"))
    sparse = [b for b in model.blocks if b.sparse]
    assert len(sparse) == 2 and all(not b.attn.rope for b in sparse)
    assert all(b.attn.gate and b.attn.qk_norm is not None for b in sparse)
    spec, *_ = ServeConfig(max_slots=4, block_len=16, prefill_chunk=64).resolve(tc)
    assert spec.sparse == SC and spec.num_layers == 2


@pytest.mark.parametrize("length", [60, 125, 200], ids=["dense", "switch", "sparse"])
def test_chunked_prefill_then_decode_matches_the_reference(tiny, reference, length):
    """A prompt through chunks of 64, then 16 decode waves — under
    ``dense_len`` all along, across it in decode, past it in prefill —
    through ``paged_step`` on a pool and state arrays left full of garbage
    (compressed keys included): every wave's logits against the
    reference's whole-sequence forward."""
    model, params, _ = tiny
    sc = ServeConfig(max_slots=4, block_len=16, prefill_chunk=64)
    spec, mb, _, _ = sc.resolve(model.config)
    pages = tuple(jnp.full(shape, 3.0, dtype) for shape, dtype in spec.arrays)
    slot = 1
    table = np.zeros((4, mb), np.int32)
    table[slot, :16] = 1 + np.random.default_rng(length).permutation(16)
    prompt = _prompt(length, seed=length)
    step = jax.jit(model.paged_step)
    with jax.default_matmul_precision("highest"):
        for start in range(0, length - 1, 64):
            valid = min(64, length - 1 - start)
            chunk = np.full((1, 64), 7, np.int32)
            chunk[0, :valid] = prompt[start:start + valid]
            _, pages, _ = step(params, chunk, pages, table[slot:slot + 1],
                               np.asarray([start], np.int32), np.asarray([valid], np.int32),
                               np.asarray([slot], np.int32))
        seq, last, got = list(prompt[:-1]), int(prompt[-1]), []
        lengths = np.zeros((4,), np.int32)
        run = np.zeros((4,), np.int32)
        lengths[slot], run[slot] = length - 1, 1
        for _ in range(16):
            toks = np.zeros((4, 1), np.int32)
            toks[slot] = last
            logits, pages, _ = step(params, toks, pages, table, lengths.copy(), run)
            got.append(np.asarray(logits[slot]))
            seq.append(last)
            last = int(np.argmax(got[-1]))
            lengths[slot] += 1
    want, margin = reference(np.asarray(seq, np.int32))
    assert float(margin[length - 1:].min()) > 1e-3
    np.testing.assert_allclose(np.stack(got), want[length - 1:],
                               atol=2e-4 * float(want.std()) + 1e-5)


@pytest.fixture(scope="module")
def four_slots(tiny):
    return _engine(tiny)


def test_engine_tokens_match_the_reference(four_slots, reference):
    """Four requests served together, under, across and past ``dense_len``
    are the reference's greedy continuations."""
    prompts = [_prompt(n, seed=n) for n in (20, 120, 130, 250)]
    for prompt, tokens in zip(prompts, _serve(four_slots, prompts)):
        _assert_greedy(reference, prompt, tokens)


def test_evict_and_reprefill_gives_the_undisturbed_tokens(tiny, reference):
    """A pool too small for the load preempts and re-prefills (the evicted
    slot's state and compressed keys stay behind as garbage): every request
    still ends with the reference's greedy tokens."""
    prompts = [_prompt(n, seed=40 + n) for n in (140, 60, 150, 90)]
    starved = _engine(tiny, max_model_len=256, num_blocks=24)
    got = _serve(starved, prompts, new=16)
    assert starved.report()["requests"]["preemptions"] > 0
    for prompt, tokens in zip(prompts, got):
        _assert_greedy(reference, prompt, tokens)


def test_a_slot_that_does_not_run_keeps_its_state_and_keys_bitwise(four_slots):
    """A decode wave with slot 2 held back: its lightning state and its
    compressed keys come back bit for bit."""
    engine = four_slots.engine
    before = [np.asarray(a) for a in engine.pages[2:]]
    s = engine.max_slots
    run = np.array([True, True, False, True])
    engine.decode(np.zeros((s, engine.max_blocks_per_seq), np.int32),
                  np.full((s,), 150, np.int32), np.zeros((s,), np.int32), run,
                  np.full((s,), 10_000, np.int32), np.zeros((s,), np.float32),
                  np.zeros((s,), np.int32), np.ones((s,), np.float32),
                  np.full((s,), -1, np.int32), np.zeros((s,), np.int32))
    after = [np.asarray(a) for a in engine.pages[2:]]
    for old, new in zip(before, after):
        np.testing.assert_array_equal(old[:, 2], new[:, 2])


def test_the_decode_pages_record_counts_the_sparse_pages(four_slots):
    """``serve/decode_pages`` carries ``sparse_pages`` and ``sparse_live``:
    per running slot and K/V head, ``topk`` of the live pages past
    ``dense_len`` and all of them under it; the prefill enqueue carries the
    same sums over its chunk's rows."""
    from rocket_tpu.obs import spans

    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        _serve(four_slots, [_prompt(200, seed=1)], new=4)
    finally:
        spans.uninstall(recorder)
    waves = [e.ids for e in recorder.events() if e.name == "serve/decode_pages"]
    live = 2 * (199 // 16 + 1)
    assert waves and waves[0]["sparse_live"] == live and waves[0]["sparse_pages"] == 2 * SC.topk
    chunks = [e.ids for e in recorder.events() if e.name == "serve/prefill_enqueue"]
    rows = np.arange(64, 128)
    assert chunks[1]["sparse_live"] == 2 * int((rows // 16 + 1).sum())
    assert chunks[1]["sparse_pages"] == chunks[1]["sparse_live"]          # all dense
    rows = np.arange(128, 192)
    want = 2 * int(np.minimum(rows // 16 + 1, SC.topk).sum())
    assert chunks[2]["sparse_pages"] == want < chunks[2]["sparse_live"]


# -- (d) the configurations that were there ---------------------------------------

@pytest.mark.parametrize("name,state,slot_state", [
    ("AI21-Jamba2-3B.reason",
     [0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 22, 23, 24, 25, 26, 27],
     ((26, (16, 5120), "float32"), (26, (15360,), "bfloat16"))),
    ("Qwen3-Next-80B-A3B-Instruct-ep4.longchat", [0, 1, 2, 4, 5, 6],
     ((6, (32, 128, 128), "float32"), (6, (24576,), "bfloat16"))),
    ("Laguna-XS.2-ep4.codeagent", [],
     ((6, (512, 1024), "bfloat16"), (6, (512, 1024), "bfloat16"))),
])
def test_the_configurations_that_were_there_describe_their_layers_as_before(
        name, state, slot_state):
    """The period-and-offset rule (Jamba, Qwen3-Next) and the window kinds
    (Laguna) read through the one description of a layer's kind: the same
    state layers, page layers and per-slot arrays as before, no sparse
    layer, no compressed keys, no muP factor."""
    import importlib

    from benchmark import run

    _, cell, config = run.load_cell(name)
    tc = importlib.import_module(f"benchmark.drivers.{cell['driver']}").transformer_config(config)
    assert [i for i in range(tc.num_layers) if tc.is_state_layer(i)] == state
    assert tc.cache_layers == 2 and tc.sparse_layers == 0
    assert tc.slot_state_shapes == slot_state
    assert (tc.embed_scale, tc.residual_scale, tc.logit_divisor) == (1.0, 1.0, 1.0)
    spec, *_ = ServeConfig(max_slots=2, block_len=64, prefill_chunk=64,
                           max_model_len=1024).resolve(tc)
    assert spec.sparse is None
