"""Latent attention (MLA), the sigmoid group-limited router, the held share
of a routed expert layer and the latent serving pool, against the plain
float32 reference ``benchmark/reference/deepseek_v3_lm.py`` — tiny widths,
seeded weights, CPU."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_latent_moe as driver
from benchmark.reference import deepseek_v3_lm as ref

TINY = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "vocab_size": 96, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_held_offset": 4, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 64, "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    "assumed": {"router_bias_std": 0.1},
}


def _model(config=TINY, dtype="float32", **over):
    from rocket_tpu.models.transformer import TransformerLM

    tc = driver.transformer_config(config)
    tc.activation_dtype = None if dtype == "float32" else dtype
    for k, v in over.items():
        setattr(tc, k, v)
    return TransformerLM(tc)


def _weights(config=TINY, seed=0, **kw):
    return ref.make_params(jax.random.key(seed), config, jnp.float32, **kw)


def _highest(fn):
    @functools.wraps(fn)
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


# -- (a) whole-sequence apply ------------------------------------------------

@_highest
def test_whole_sequence_apply_matches_the_reference_logits():
    """Float32 end to end, the same equations twice: 2e-4 of the logits'
    spread is summation order over six matmuls a layer."""
    params = _weights()
    tokens = np.random.default_rng(1).integers(0, 96, size=(2, 40)).astype(np.int32)
    model = _model()
    out, _ = model.apply(
        {"params": ref.program_params(params, TINY), "state": {}},
        {"tokens": jnp.asarray(tokens)}, mode="eval",
    )
    for b in range(2):
        want, _ = ref.logits(params, jnp.asarray(tokens[b]), TINY)
        np.testing.assert_allclose(out["logits"][b], want, atol=2e-4 * float(jnp.std(want)) + 1e-5)


# -- (b) chunked prefill then decode through the latent pool ------------------

def _serve_through_pool(model, params, tokens, prompt, *, chunk, block_len):
    """Prefill ``tokens[:prompt-1]`` in chunks, then decode one position at
    a time: logits that predict every position from ``prompt`` on."""
    from rocket_tpu.serve.kv_pool import KVPoolSpec

    total = len(tokens)
    mb = -(-64 // block_len)
    spec = KVPoolSpec(num_layers=model.config.num_layers, num_blocks=1 + mb,
                      block_len=block_len, lanes=model.config.kv_pool_lanes)
    pages = spec.init_pages()
    assert [p.shape[-1] for p in pages] == [128]      # ONE array: 32 + 8 lanes, tiled
    table = jnp.arange(1, 1 + mb, dtype=jnp.int32)[None]
    step = jax.jit(model.paged_step)
    at = lambda n: jnp.asarray([n], jnp.int32)
    pos = 0
    while pos < prompt - 1:
        n = min(chunk, prompt - 1 - pos)
        piece = np.zeros((1, chunk), np.int32)
        piece[0, :n] = tokens[pos:pos + n]
        _, pages, pairs = step(params, jnp.asarray(piece), pages, table, at(pos), at(n))
        assert pairs.shape == (2, 4) and int(pairs.sum()) <= n * 4 * 2
        pos += n
    got = []
    for pos in range(prompt - 1, total - 1):
        logits, pages, pairs = step(
            params, jnp.asarray(tokens[None, pos:pos + 1]), pages, table, at(pos), at(1))
        got.append(logits[0])
    return jnp.stack(got)


@_highest
@pytest.mark.parametrize("chunk,block_len", [(8, 4), (16, 8)])
def test_prefill_in_chunks_then_decode_matches_the_reference_full_forward(chunk, block_len):
    """Logits at every answer position, served through the pool (chunks
    non-absorbed over gathered pages, decode absorbed) against the
    reference's one full forward. Float32: 5e-4 of the spread covers the
    absorbed form's other order of the same products."""
    params = _weights(seed=3)
    tokens = np.random.default_rng(2).integers(0, 96, size=(50,)).astype(np.int32)
    prompt = 29
    got = _serve_through_pool(
        _model(), ref.program_params(params, TINY), tokens, prompt,
        chunk=chunk, block_len=block_len,
    )
    want, _ = ref.logits(params, jnp.asarray(tokens), TINY)
    want = want[prompt - 1:len(tokens) - 1]
    np.testing.assert_allclose(got, want, atol=5e-4 * float(jnp.std(want)) + 1e-5)


# -- (c) the shares add up -----------------------------------------------------

@_highest
def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """Routed parts of all four shares (four experts each) plus the shared
    expert ONCE = the layer with all 16 experts held, in the reference and
    in the program's layer alike."""
    from rocket_tpu.nn.moe import RoutedExperts

    whole_cfg = dict(TINY, n_routed_experts=16, experts_held_offset=0)
    lp = ref.layer_params(jax.random.key(5), TINY, False, all_experts=True)["moe"]
    x = jax.random.normal(jax.random.key(6), (37, 64))
    whole, _ = ref.expert_layer(lp, x, whole_cfg)
    shared = ref.swiglu(lp["shared"], x)
    parts_ref, parts_prog, pairs = [], [], 0
    for offset in range(0, 16, 4):
        share = dict(lp, experts=jax.tree.map(lambda a: a[offset:offset + 4], lp["experts"]))
        # The held share made alone holds the same numbers.
        alone = ref.layer_params(
            jax.random.key(5), dict(TINY, experts_held_offset=offset), False)["moe"]
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(share)):
            np.testing.assert_array_equal(a, b)
        y, _ = ref.expert_layer(share, x, TINY, experts_held=(offset, 4), shared=False)
        parts_ref.append(y)
        cfg = dict(TINY, experts_held_offset=offset)
        layer = RoutedExperts(64, driver.transformer_config(cfg).routed_experts)
        block = ref.program_params(
            {"embed": 0, "norm": 0, "head": 0,
             "layers": {"0": {"ln1": 0, "ln2": 0, "attn": {k: 0 for k in (
                 "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_ukv", "w_o")},
                 "moe": share}}}, cfg)["blocks"]["0"]["moe"]
        y_prog, counts = layer.apply({"params": block, "state": {}}, x)
        parts_prog.append(y_prog - shared)
        pairs += int(counts.sum())
    assert pairs == 37 * 4          # every pair is held by exactly one share
    scale = float(jnp.std(whole))
    np.testing.assert_allclose(sum(parts_ref) + shared, whole, atol=1e-5 * scale)
    np.testing.assert_allclose(sum(parts_prog) + shared, whole, atol=1e-4 * scale)


# -- (d) the router alone -------------------------------------------------------

def _route_literal(logits, bias, *, groups, kept, k, scaling):
    """The equations, token by token, in numpy float64."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    out_w, out_e = [], []
    for row in s:
        biased = row + bias
        per = len(row) // groups
        score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum() for g in range(groups)]
        keep = np.argsort(score)[::-1][:kept]
        allowed = np.full(len(row), -np.inf)
        for g in keep:
            allowed[g * per:(g + 1) * per] = biased[g * per:(g + 1) * per]
        chosen = np.argsort(allowed)[::-1][:k]
        w = row[chosen] / (row[chosen].sum() + 1e-20) * scaling
        out_w.append(w)
        out_e.append(chosen)
    return np.asarray(out_w), np.asarray(out_e)


def test_router_matches_a_literal_transcription_and_bias_and_groups_matter():
    from rocket_tpu.nn.moe import RoutedExpertsConfig, route_sigmoid_grouped

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 32)).astype(np.float32)
    bias = rng.normal(scale=0.5, size=(32,)).astype(np.float32)
    cfg = RoutedExpertsConfig(num_experts=32, top_k=4, hidden=8, n_group=4,
                              topk_group=2, routed_scaling_factor=2.5)
    w, e = route_sigmoid_grouped(jnp.asarray(logits), jnp.asarray(bias), cfg)
    want_w, want_e = _route_literal(logits, bias, groups=4, kept=2, k=4, scaling=2.5)
    np.testing.assert_array_equal(np.sort(e, 1), np.sort(want_e, 1))
    order = np.argsort(np.asarray(e), 1), np.argsort(want_e, 1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order[0], 1),
                               np.take_along_axis(want_w, order[1], 1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)
    # The bias changes the selection (and never the weights' source)...
    _, unbiased = route_sigmoid_grouped(jnp.asarray(logits), jnp.zeros(32), cfg)
    assert (np.sort(unbiased, 1) != np.sort(e, 1)).any(axis=1).mean() > 0.3
    # ... and so does the group limit.
    free = RoutedExpertsConfig(num_experts=32, top_k=4, hidden=8, n_group=1,
                               topk_group=1, routed_scaling_factor=2.5)
    _, ungrouped = route_sigmoid_grouped(jnp.asarray(logits), jnp.asarray(bias), free)
    assert (np.sort(ungrouped, 1) != np.sort(e, 1)).any(axis=1).mean() > 0.3
    # The reference's router is the same function.
    p = {"w_g": jnp.eye(32), "bias": jnp.asarray(bias)}
    rcfg = dict(TINY, n_routed_experts_published=32, n_group=4, topk_group=2)
    rw, re_, margin = ref.route(p, jnp.asarray(logits), rcfg)
    np.testing.assert_array_equal(re_, e)
    np.testing.assert_allclose(rw, w, rtol=1e-6)
    assert margin.shape == (64,) and float(margin.min()) >= 0.0


# -- (e) absorbed = non-absorbed; YaRN by hand -------------------------------

@_highest
def test_absorbed_decode_equals_non_absorbed_attention():
    """One query row at position 20 over 21 cached rows: the absorbed form
    (the XLA path of the decode) and the chunk form (C = 2, row 0) give the
    same output to float32 rounding."""
    from rocket_tpu.ops.paged_attention import write_pages

    model = _model()
    attn = model.blocks[1].attn
    p = ref.program_params(_weights(seed=7), TINY)["blocks"]["1"]["attn"]
    x = jax.random.normal(jax.random.key(8), (1, 22, 64))
    pages = jnp.zeros((1, 9, 4, 128))
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    zero, n = jnp.zeros((1,), jnp.int32), jnp.asarray([20], jnp.int32)
    _, pages = attn.apply_paged(p, x[:, :20], pages, table, zero, n, layer=0)
    dec, pages_d = attn.apply_paged(p, x[:, 20:21], pages, table, n, n * 0 + 1, layer=0)
    chunk, pages_c = attn.apply_paged(p, x[:, 20:22], pages, table, n, n * 0 + 1, layer=0)
    np.testing.assert_allclose(dec[0, 0], chunk[0, 0], atol=2e-5 * float(jnp.std(chunk)))
    np.testing.assert_array_equal(pages_d[0, 1:6], pages_c[0, 1:6])
    whole, _ = attn.apply({"params": p, "state": {}}, x[:, :21], mode="eval")
    np.testing.assert_allclose(dec[0, 0], whole[0, 20], atol=2e-5 * float(jnp.std(whole)))


def test_yarn_frequencies_and_scale_against_hand_computed_values():
    from rocket_tpu.nn.attention import YarnScaling, yarn_inv_freq, yarn_mscale

    yarn = YarnScaling(factor=40, original_max_position_embeddings=4096,
                       beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
    inv = yarn_inv_freq(64, 10000.0, yarn)
    # Correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> 10;
    # 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.5 -> 23. Below 10 the plain
    # frequency, from 23 on the plain one over 40, a linear ramp between.
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[0], 1.0)
    np.testing.assert_allclose(inv[10], 10000.0 ** (-10 / 32), rtol=1e-6)
    np.testing.assert_allclose(inv[23], 10000.0 ** (-23 / 32) / 40, rtol=1e-6)
    np.testing.assert_allclose(inv[31], 10000.0 ** (-31 / 32) / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    plain = 10000.0 ** (-16 / 32)
    np.testing.assert_allclose(inv[16], plain / 40 * ramp + plain * (1 - ramp), rtol=1e-6)
    m = yarn_mscale(40, 1)
    np.testing.assert_allclose(m, 0.1 * math.log(40) + 1)          # 1.36889
    np.testing.assert_allclose(ref.yarn_inv_freq(dict(TINY, qk_rope_head_dim=64, rope_scaling=dict(
        TINY["rope_scaling"], original_max_position_embeddings=4096))), inv)
    full = dict(TINY, qk_nope_head_dim=128, qk_rope_head_dim=64)
    np.testing.assert_allclose(ref.softmax_scale(full), 192 ** -0.5 * m * m, rtol=1e-6)
    attn = _model(full | {"hidden_size": 64}).blocks[0].attn
    np.testing.assert_allclose(attn.scale, 192 ** -0.5 * m * m, rtol=1e-6)
    assert attn.trig_scale == 1.0


# -- (f) the generalised kernel, interpreted -------------------------------------

#: Waves of ``(position, valid)`` a slot against 4 pages of 16 rows (None:
#: a free slot, its table zeros; ``valid`` 0: it does not run this wave).
_WAVES = {
    "all_run": [(5, 1), (37, 1), (63, 1)],
    "free_slot": [None, (37, 1), (63, 1)],
    "valid0_long_context": [(62, 0), (5, 1), (63, 1)],
    "context_of_1": [(0, 1), (0, 1), (0, 1)],
    "exactly_one_page": [(15, 1), (15, 1), (0, 1)],
    "one_page_plus_1_row": [(16, 1), (16, 1), (15, 1)],
    "full_table": [(63, 1), (63, 1), (63, 1)],
    "ragged": [None, (62, 0), (0, 1), (15, 1), (16, 1), (63, 1)],
}


@pytest.mark.parametrize("wave", list(_WAVES))
@pytest.mark.parametrize("g,dk,dv", [(128, 640, 512), (8, 128, 32)])
def test_latent_decode_kernel_interpreted_matches_xla(g, dk, dv, wave):
    """``h_kv`` 1, ``g`` query heads, K = the whole ``dk``-lane row, V = its
    first ``dv`` lanes: the kernel body interpreted against the gathered
    XLA attention over ragged waves, at the tile nobody pinned and at one
    of two pages (a context's last tile half dead). bfloat16 operands at
    the real widths (128 / 576 in 640 / 512, few pages), float32 in
    miniature. A slot that does not run gives a finite row."""
    from rocket_tpu.ops.paged_attention import paged_latent_decode

    dtype, tol = (jnp.bfloat16, 2e-2) if dk == 640 else (jnp.float32, 2e-5)
    slots = _WAVES[wave]
    s, mb, bl, nb = len(slots), 4, 16, 1 + 4 * len(slots)
    ks = jax.random.split(jax.random.key(0), 2)
    pages = (jax.random.normal(ks[0], (2, nb, bl, dk)) * 0.5).astype(dtype)
    q = (jax.random.normal(ks[1], (s, g, dk)) * 0.5).astype(dtype)
    table = np.random.default_rng(0).permutation(np.arange(1, nb))[:s * mb].reshape(s, mb)
    table[[slot is None for slot in slots]] = 0
    positions, valid = (jnp.asarray([0 if slot is None else slot[i] for slot in slots], jnp.int32)
                        for i in (0, 1))
    table = jnp.asarray(table, jnp.int32)
    kw = dict(layer=1, d_v=dv, scale=0.11)
    want = np.asarray(paged_latent_decode(q, pages, table, positions, valid, **kw), np.float32)
    run = np.asarray(valid) > 0
    for block_kv in (None, 32):
        got = paged_latent_decode(q, pages, table, positions, valid, interpret=True,
                                  block_kv=block_kv, **kw)
        assert got.shape == (s, g, dv)
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got[run], want[run], atol=tol, rtol=tol)
        assert np.isfinite(got).all()


# -- (g) the prefill chunk kernel, interpreted ---------------------------------

def _chunk_layer(heads=8, wide=True):
    """A latent-attention layer alone: the docqa cell's head shape (128 + 64
    / 128 over a 512 + 64 latent in 640 lanes) at a cut head count, or a
    tiny one the kernel cannot take."""
    from rocket_tpu.nn.attention import LatentAttention, LatentAttentionConfig

    sizes = (512, 128, 64, 128) if wide else (32, 16, 8, 16)
    cfg = LatentAttentionConfig(
        q_lora_rank=48, kv_lora_rank=sizes[0], qk_nope_head_dim=sizes[1],
        qk_rope_head_dim=sizes[2], v_head_dim=sizes[3])
    attn = LatentAttention(96, heads, cfg)
    return attn, attn.init_params(jax.random.key(3))


def _chunk_operands(attn, p, positions, dtype, *, c=32, bl=16, mb=32, poison_from=None):
    """``x`` (S, C, D) and a pool whose first ``positions[s]`` rows a slot
    are a real prefix (written through the layer itself), the rest noise —
    or NaN from row ``poison_from`` on."""
    from rocket_tpu.ops.paged_attention import write_pages

    s = len(positions)
    nb = 1 + s * mb
    lanes = attn.config.pool_lanes
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    ks = jax.random.split(jax.random.key(5), 3)
    pages = (jax.random.normal(ks[0], (2, nb, bl, lanes)) * 0.3).astype(dtype)
    table = jnp.asarray(
        np.random.default_rng(1).permutation(np.arange(1, nb)).reshape(s, mb), jnp.int32)
    t = mb * bl
    prefix = jax.random.normal(ks[1], (s, t, attn.features)).astype(dtype)
    _, _, latent = attn._down(p, prefix, jnp.zeros((s,), jnp.int32))
    pos = jnp.asarray(positions, jnp.int32)
    pages = write_pages(pages, table, jnp.zeros((s,), jnp.int32), pos, latent, layer=1)
    if poison_from is not None:
        dead = jnp.arange(t)[None, :, None] >= poison_from
        rows = jnp.where(dead, jnp.nan, pages[1, table].reshape(s, t, lanes))
        pages = pages.at[1, table].set(rows.reshape(s, mb, bl, lanes))
    x = jax.random.normal(ks[2], (s, c, attn.features)).astype(dtype)
    return p, x, pages, table, pos, prefix


#: ``positions`` of two slots against a table of 512 rows, a chunk of 32
#: rows, key tiles of 128 (and the tile nobody pinned, 512).
_CHUNKS = {
    "first_chunk": [0, 0],
    "deep_ends_inside_a_tile": [200, 71],        # live 232 and 103 rows
    "exactly_one_tile": [96, 96],                # 96 + 32 = 128
    "one_tile_plus_a_row": [97, 0],
    "whole_table": [480, 480],
    "ragged": [480, 0],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_CHUNKS))
def test_latent_prefill_kernel_interpreted_matches_the_loop(case, dtype):
    """``mla_prefill`` interpreted against the XLA loop it stands in for
    (the portable path, so the oracle) through ``apply_paged``, pool
    updates included; float32 to rounding, bfloat16 no further from the
    float32 loop than the bfloat16 loop is."""
    from rocket_tpu.ops.latent_prefill import mla_prefill

    dtype = jnp.dtype(dtype)
    attn, p32 = _chunk_layer()
    p, x, pages, table, pos, _ = _chunk_operands(attn, p32, _CHUNKS[case], dtype)
    valid = jnp.full(pos.shape, x.shape[1], jnp.int32)
    want, want_pages = attn.apply_paged(p, x, pages, table, pos, valid, layer=1)
    got, got_pages = attn.apply_paged(p, x, pages, table, pos, valid, layer=1, interpret=True)
    np.testing.assert_array_equal(got_pages, want_pages)
    # The same call at tiles of 128 rows and two heads a step: several key
    # tiles, several head blocks.
    q_nope, q_rope, _ = attn._down(p, x, pos)
    rows = got_pages[1, table].reshape(len(pos), -1, pages.shape[-1])
    tiled = attn._out(p, mla_prefill(
        q_nope, q_rope, rows, attn._up_weights(p, dtype), pos, scale=attn.scale,
        block_kv=128, heads=2, interpret=True))
    scale = float(jnp.std(want.astype(jnp.float32)))
    if dtype == jnp.float32:
        for out in (got, tiled):
            np.testing.assert_allclose(out, want, atol=2e-5 * scale)
        return
    p32, x32, pages32 = (jax.tree.map(lambda a: a.astype(jnp.float32), t) for t in (p, x, pages))
    exact, _ = attn.apply_paged(p32, x32, pages32, table, pos, valid, layer=1)
    loop_err = float(jnp.abs(want.astype(jnp.float32) - exact).max())
    for out in (got, tiled):
        err = float(jnp.abs(out.astype(jnp.float32) - exact).max())
        assert err <= 1.5 * loop_err + 1e-3 * scale, (err, loop_err, scale)


@_highest
@pytest.mark.parametrize("positions", [[0], [96], [180]])
def test_latent_prefill_kernel_matches_the_whole_sequence_apply(positions):
    """A chunk through the kernel (interpreted) after a prefix written to
    the pool = the same rows of ``LatentAttention.apply`` over the whole
    sequence, float32."""
    attn, p32 = _chunk_layer()
    p, x, pages, table, pos, prefix = _chunk_operands(attn, p32, positions, jnp.float32)
    n, c = positions[0], x.shape[1]
    valid = jnp.full(pos.shape, c, jnp.int32)
    got, _ = attn.apply_paged(p, x, pages, table, pos, valid, layer=1, interpret=True)
    whole, _ = attn.apply(
        {"params": p, "state": {}}, jnp.concatenate([prefix[:, :n], x], axis=1), mode="eval")
    np.testing.assert_allclose(got, whole[:, n:], atol=2e-5 * float(jnp.std(whole)))


@_highest
def test_latent_prefill_kernel_ignores_padded_query_rows():
    """A short last chunk: the rows past ``valid`` are padding, their
    latents land in the trash block, and the real rows' outputs are what
    they are with any other padding."""
    attn, p32 = _chunk_layer()
    p, x, pages, table, pos, _ = _chunk_operands(attn, p32, [200, 40], jnp.float32)
    valid = jnp.asarray([7, 19], jnp.int32)
    other = x.at[0, 7:].set(9.0).at[1, 19:].set(-9.0)
    want, want_pages = attn.apply_paged(p, x, pages, table, pos, valid, layer=1)
    for chunk in (x, other):
        got, got_pages = attn.apply_paged(p, chunk, pages, table, pos, valid, layer=1,
                                          interpret=True)
        np.testing.assert_array_equal(got_pages[:, 1:], want_pages[:, 1:])
        for s, n in enumerate([7, 19]):
            np.testing.assert_allclose(got[s, :n], want[s, :n], atol=2e-5 * float(jnp.std(want)))


def test_latent_prefill_kernel_never_reads_a_dead_key_tile():
    """NaN in every row of the table past the live context's last tile:
    the kernel's output is finite and the loop's (whose steps end at the
    same tile), at the tile nobody pinned and at tiles of 128."""
    from rocket_tpu.ops.latent_prefill import mla_prefill
    from rocket_tpu.ops.paged_attention import write_pages

    attn, p32 = _chunk_layer()
    # Live: 71 + 32 = 103 rows and 200 + 32 = 232: tiles of 128 end at 256.
    p, x, pages, table, pos, _ = _chunk_operands(
        attn, p32, [71, 200], jnp.float32, poison_from=256)
    clean = _chunk_operands(attn, p32, [71, 200], jnp.float32)[2]
    q_nope, q_rope, latent = attn._down(p, x, pos)
    valid = jnp.full(pos.shape, x.shape[1], jnp.int32)
    gather = lambda pg: write_pages(pg, table, pos, valid, latent, layer=1)[1, table] \
        .reshape(2, -1, pages.shape[-1])
    assert bool(jnp.isnan(gather(pages)).any())
    kw = dict(scale=attn.scale, block_kv=128, heads=4, interpret=True)
    w = attn._up_weights(p, jnp.float32)
    got = mla_prefill(q_nope, q_rope, gather(pages), w, pos, **kw)
    want = mla_prefill(q_nope, q_rope, gather(clean), w, pos, **kw)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


def test_latent_prefill_falls_back_to_the_loop_at_a_tiny_width():
    """Heads of 16 + 8 / 16 over a 32-lane latent are no whole lane tiles:
    ``interpret=True`` or not, the chunk takes the loop (bitwise equal
    outputs), and the gate says so."""
    from rocket_tpu.ops.latent_prefill import mla_prefill_supported

    assert mla_prefill_supported(512, 128, 512, 128, 64, 128, 8192, 2)
    assert mla_prefill_supported(32, 8, 512, 128, 64, 128, 512, 4)
    assert not mla_prefill_supported(32, 4, 32, 16, 8, 16, 512, 4)
    assert not mla_prefill_supported(1024, 128, 512, 128, 64, 128, 8192, 2)   # over a query tile
    assert not mla_prefill_supported(24, 8, 512, 128, 64, 128, 512, 2)       # no whole sublane tile
    assert not mla_prefill_supported(32, 8, 512, 128, 64, 128, 192 * 3, 4)   # table of odd tiles
    attn, p32 = _chunk_layer(heads=4, wide=False)
    p, x, pages, table, pos, _ = _chunk_operands(attn, p32, [40, 0], jnp.float32)
    valid = jnp.full(pos.shape, x.shape[1], jnp.int32)
    want, _ = attn.apply_paged(p, x, pages, table, pos, valid, layer=1)
    got, _ = attn.apply_paged(p, x, pages, table, pos, valid, layer=1, interpret=True)
    np.testing.assert_array_equal(got, want)


# -- the engine end to end -------------------------------------------------------

def test_engine_serves_the_model_and_records_expert_pairs():
    """``ServeEngine`` / ``submit`` / ``step`` with the latent pool: greedy
    tokens equal the whole-sequence ``apply``'s argmax, both programs trace
    once, and an open span sink receives ``moe/expert_pairs``."""
    from rocket_tpu.obs import spans
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = _model()
    params = ref.program_params(_weights(seed=11), TINY)
    engine = ServeEngine(model, params, ServeConfig(
        max_slots=3, block_len=4, prefill_chunk=8, max_model_len=64))
    assert len(engine.engine.pages) == 1
    assert engine.engine.pages[0].shape == (3, 1 + 3 * 16, 4, 128)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32) for n in (5, 19, 11, 26)]
    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        rids = [engine.submit(p, max_new_tokens=6, temperature=0.0) for p in prompts]
        engine.drain()
    finally:
        spans.uninstall(recorder)
    assert engine.engine.decode_traces == 1 and engine.engine.prefill_traces == 1
    for rid, prompt in zip(rids, prompts):
        served = np.asarray(engine.result(rid).tokens)
        seq = np.concatenate([prompt, served])
        out, _ = model.apply({"params": params, "state": {}},
                             {"tokens": jnp.asarray(seq[None])}, mode="eval")
        logits = np.asarray(out["logits"][0, len(prompt) - 1:len(seq) - 1])
        gap = logits.max(-1) - logits[np.arange(6), served]
        assert gap.max() <= 1e-3 * logits.std(), gap
    records = [e for e in recorder.events() if e.name == "moe/expert_pairs"]
    kinds = {e.ids["kind"] for e in records}
    assert kinds == {"decode", "prefill"}
    prefill_tokens = sum(e.ids["tokens"] for e in records if e.ids["kind"] == "prefill")
    assert prefill_tokens == sum(len(p) - 1 for p in prompts)
    decode_tokens = sum(e.ids["tokens"] for e in records if e.ids["kind"] == "decode")
    assert decode_tokens == 6 * len(prompts)
    for e in records:
        pairs = np.asarray(json.loads(e.ids["pairs"]))
        assert pairs.shape == (2, 4) and (pairs.sum(1) <= e.ids["tokens"] * 4).all()
