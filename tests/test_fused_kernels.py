"""Structural kernel families (ISSUE 14): interpret-mode fwd+bwd parity
of each fused pallas variant against its reference path, and the three
call-site seams' contracts:

* with tables absent (or ``ROCKET_TPU_TUNE=0``) every seam is BITWISE
  the pre-existing composition — the acceptance criterion;
* the force-override envs engage each fused variant on CPU (interpret
  mode) and the results hold the tuner's parity tolerance;
* the padded group layout behind gather-gmm is exact under ragged and
  degenerate (empty-expert) routings.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.tune.space import TUNE_SPACES
from rocket_tpu.tune.tuner import check_parity

RNG = np.random.default_rng(0)


def _space_parity(kernel, ref, got, dtype):
    """check_parity under the kernel's OWN sweep contract — the
    TuneSpace parity_tol override when one is declared (fused_conv and
    block_attn scope a wider f32 bound for their legitimately
    reassociated reductions)."""
    return check_parity(
        ref, got, dtype, tol=TUNE_SPACES[kernel].parity_tol.get(dtype)
    )


def _value_and_grads(fn, *args, argnums=None):
    argnums = tuple(range(len(args))) if argnums is None else argnums

    def loss(*a):
        out = fn(*a)
        leaves = jax.tree.leaves(out)
        return sum((leaf.astype(jnp.float32) ** 2).sum()
                   for leaf in leaves), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=argnums, has_aux=True
    )(*args)
    return tuple(jax.tree.leaves(out)) + tuple(jax.tree.leaves(grads))


def _pallas_calls(fn, *args) -> int:
    # Fresh wrapper per call: make_jaxpr shares jit's trace cache keyed
    # on function identity, and the ROCKET_TPU_* force-overrides are
    # read at TRACE time — a cached trace would ignore an env flip.
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args)).count(
        "pallas_call"
    )


# -- fused conv epilogue (fused_conv) ----------------------------------------


def _bn_operands(b=8, hw=8, c=16, dtype=jnp.float32):
    x = jnp.asarray(
        RNG.normal(size=(b, hw, hw, c)).astype(np.float32) + 0.3
    ).astype(dtype)
    scale = jnp.asarray(
        1.0 + 0.1 * RNG.normal(size=(c,)).astype(np.float32)
    )
    bias = jnp.asarray(0.1 * RNG.normal(size=(c,)).astype(np.float32))
    return x, scale, bias


@pytest.mark.parametrize("schedule", ["twopass", "stats_xla"])
@pytest.mark.parametrize("act", [True, False])
def test_fused_bn_act_parity(schedule, act):
    """Both schedules of the fused BN(+relu) kernel match the
    `_bn_train` + relu reference — outputs, stats AND grads."""
    from rocket_tpu.ops.fused_conv import fused_bn_act, reference_bn_act

    x, scale, bias = _bn_operands()
    ref = _value_and_grads(
        lambda *a: reference_bn_act(*a, 1e-5, act), x, scale, bias
    )
    got = _value_and_grads(
        lambda *a: fused_bn_act(
            *a, eps=1e-5, act=act, schedule=schedule, block_rows=128,
            interpret=True,
        ),
        x, scale, bias,
    )
    ok, err = _space_parity("fused_conv", ref, got, "float32")
    assert ok, (schedule, act, err)


def test_fused_bn_act_bf16_parity():
    from rocket_tpu.ops.fused_conv import fused_bn_act, reference_bn_act

    x, scale, bias = _bn_operands(b=16, hw=8, c=32, dtype=jnp.bfloat16)
    ref = _value_and_grads(
        lambda *a: reference_bn_act(*a, 1e-5, True), x, scale, bias
    )
    got = _value_and_grads(
        lambda *a: fused_bn_act(*a, eps=1e-5, act=True, block_rows=256,
                                interpret=True),
        x, scale, bias,
    )
    ok, err = _space_parity("fused_conv", ref, got, "bfloat16")
    assert ok, err


def test_fused_bn_act_rejects_bad_config():
    from rocket_tpu.ops.fused_conv import fused_bn_act

    x, scale, bias = _bn_operands()
    with pytest.raises(ValueError, match="tile block_rows"):
        fused_bn_act(x, scale, bias, block_rows=384, interpret=True)
    with pytest.raises(ValueError, match="unknown schedule"):
        fused_bn_act(x, scale, bias, schedule="retired", block_rows=128,
                     interpret=True)


def test_bn_act_seam_default_is_bitwise_reference():
    """With no table entry the seam IS `_bn_train` + relu — bitwise,
    fwd and grads (the acceptance criterion)."""
    from rocket_tpu.nn.layers import _bn_train, bn_act_train

    x, scale, bias = _bn_operands()

    def seam(x, scale, bias):
        return bn_act_train(x, scale, bias, 1e-5, act=True)

    def manual(x, scale, bias):
        y, stats = _bn_train(x, scale, bias, 1e-5)
        return jax.nn.relu(y), stats

    a = _value_and_grads(seam, x, scale, bias)
    b = _value_and_grads(manual, x, scale, bias)
    for left, right in zip(a, b):
        np.testing.assert_array_equal(np.asarray(left), np.asarray(right))
    assert _pallas_calls(seam, x, scale, bias) == 0


def test_bn_act_seam_engages_under_force(monkeypatch):
    from rocket_tpu.nn.layers import bn_act_train

    x, scale, bias = _bn_operands()

    def seam(x, scale, bias):
        return bn_act_train(x, scale, bias, 1e-5, act=True)

    ref = _value_and_grads(seam, x, scale, bias)
    monkeypatch.setenv("ROCKET_TPU_FUSED_CONV", "pallas")
    assert _pallas_calls(seam, x, scale, bias) == 1
    got = _value_and_grads(seam, x, scale, bias)
    ok, err = _space_parity("fused_conv", ref, got, "float32")
    assert ok, err


def test_batchnorm_apply_unchanged_and_act_folds():
    """`BatchNorm.apply` stays op-identical to the pre-seam composition
    and `apply_act(act=True)` == relu(apply(...)) bitwise on the
    default path, train AND eval."""
    from rocket_tpu.nn.layers import BatchNorm, _bn_train

    bn = BatchNorm(16)
    x, scale, bias = _bn_operands(c=16)
    variables = {
        "params": {"scale": scale, "bias": bias},
        "state": {"mean": jnp.zeros(16), "var": jnp.ones(16)},
    }
    for mode in ("train", "eval"):
        y_plain, _ = bn.apply(variables, x, mode=mode)
        y_act, _ = bn.apply_act(variables, x, mode=mode, act=True)
        np.testing.assert_array_equal(
            np.asarray(jax.nn.relu(y_plain)), np.asarray(y_act)
        )
    y_train, state = bn.apply(variables, x, mode="train")
    y_ref, stats = _bn_train(x, scale, bias, bn.eps)
    np.testing.assert_array_equal(np.asarray(y_train), np.asarray(y_ref))
    mean = jax.lax.stop_gradient(stats)[..., 0]
    np.testing.assert_array_equal(
        np.asarray(state["mean"]),
        np.asarray(bn.momentum * variables["state"]["mean"]
                   + (1 - bn.momentum) * mean),
    )


def test_resnet_block_default_has_no_pallas_and_act_matches():
    """The resnet wiring keeps the default program pallas-free, and the
    folded-act _ConvBN equals relu(unfused _ConvBN) bitwise."""
    from rocket_tpu.models.resnet import _BasicBlock, _ConvBN

    x = jnp.asarray(RNG.normal(size=(4, 8, 8, 16)).astype(np.float32))
    cb_act = _ConvBN(16, 16, 3, act=True)
    cb_plain = _ConvBN(16, 16, 3)
    v = cb_act.init(jax.random.key(0))
    y_act, _ = cb_act.apply(v, x, mode="train")
    y_plain, _ = cb_plain.apply(v, x, mode="train")
    np.testing.assert_array_equal(
        np.asarray(y_act), np.asarray(jax.nn.relu(y_plain))
    )
    blk = _BasicBlock(16, 16, 1)
    vb = blk.init(jax.random.key(1))
    assert _pallas_calls(
        lambda x: blk.apply(vb, x, mode="train")[0], x
    ) == 0


# -- whole-block attention half (block_attn) ---------------------------------


def _block_operands(b=4, t=64, d=128, dtype=jnp.float32):
    x = jnp.asarray(
        RNG.normal(size=(b, t, d)).astype(np.float32) * 0.5
    ).astype(dtype)
    ln_s = jnp.asarray(1.0 + 0.1 * RNG.normal(size=(d,)).astype(np.float32))
    ln_b = jnp.asarray(0.1 * RNG.normal(size=(d,)).astype(np.float32))
    wqkv = jnp.asarray(
        RNG.normal(size=(d, 3 * d)).astype(np.float32) * d ** -0.5
    )
    bqkv = jnp.asarray(0.01 * RNG.normal(size=(3 * d,)).astype(np.float32))
    wproj = jnp.asarray(
        RNG.normal(size=(d, d)).astype(np.float32) * d ** -0.5
    )
    bproj = jnp.asarray(0.01 * RNG.normal(size=(d,)).astype(np.float32))
    return x, ln_s, ln_b, wqkv, bqkv, wproj, bproj


def test_reference_block_attn_is_bitwise_nn_composition():
    """The kernel's parity baseline IS the model's per-op path: ln1 +
    fused-QKV MHA on the XLA impl, op for op."""
    from rocket_tpu.nn.attention import MultiHeadAttention
    from rocket_tpu.nn.layers import LayerNorm
    from rocket_tpu.ops.fused_block import reference_block_attn

    d, h = 128, 2
    x, ln_s, ln_b, wqkv, bqkv, wproj, bproj = _block_operands(d=d)
    ln = LayerNorm(d)
    attn = MultiHeadAttention(d, h, impl="xla")
    y_nn, _ = ln.apply(
        {"params": {"scale": ln_s, "bias": ln_b}, "state": {}}, x
    )
    y_nn, _ = attn.apply(
        {"params": {"qkv": {"w": wqkv, "b": bqkv},
                    "proj": {"w": wproj, "b": bproj}}, "state": {}},
        y_nn, mode="eval",
    )
    y_ref = reference_block_attn(
        x, ln_s, ln_b, wqkv, bqkv, wproj, bproj, num_heads=h
    )
    np.testing.assert_array_equal(np.asarray(y_nn), np.asarray(y_ref))


@pytest.mark.parametrize("epilogue", ["fused", "separate"])
@pytest.mark.parametrize("block_b", [1, 2, 4])
def test_block_attn_half_parity(epilogue, block_b):
    from rocket_tpu.ops.fused_block import (
        block_attn_half,
        reference_block_attn,
    )

    args = _block_operands()
    ref = _value_and_grads(
        lambda *a: reference_block_attn(*a, num_heads=2, epilogue=epilogue),
        *args,
    )
    got = _value_and_grads(
        lambda *a: block_attn_half(
            *a, num_heads=2, epilogue=epilogue, block_b=block_b,
            interpret=True,
        ),
        *args,
    )
    ok, err = _space_parity("block_attn", ref, got, "float32")
    assert ok, (epilogue, block_b, err)


def test_block_attn_half_bf16_parity():
    from rocket_tpu.ops.fused_block import (
        block_attn_half,
        reference_block_attn,
    )

    args = tuple(
        a.astype(jnp.bfloat16) if i == 0 else a
        for i, a in enumerate(_block_operands())
    )
    ref = _value_and_grads(
        lambda *a: reference_block_attn(*a, num_heads=2), *args
    )
    got = _value_and_grads(
        lambda *a: block_attn_half(*a, num_heads=2, block_b=2,
                                   interpret=True),
        *args,
    )
    # Forward: the kernel and the per-op composition agree at the bf16
    # sweep bound (measured: one bf16 ulp apart).
    ok, err = _space_parity("block_attn", ref[0], got[0], "bfloat16")
    assert ok, err
    # Backward: both are bf16 computations of the same math, and a one-ulp
    # difference in the forward moves gradient entries that are small
    # sums of large terms by more than an elementwise 2e-2 bound — the
    # per-op reference ITSELF sits ~40x that bound away from f32 math.
    # So the kernel is held to what matters: it is no further from the
    # f32 result (same bf16-rounded input) than the reference is.
    truth = _value_and_grads(
        lambda *a: reference_block_attn(*a, num_heads=2),
        *(a.astype(jnp.float32) for a in args),
    )
    _, ref_err = check_parity(truth, ref, "bfloat16")
    _, got_err = check_parity(truth, got, "bfloat16")
    assert got_err <= 1.05 * ref_err, (got_err, ref_err)


def test_block_attn_half_rejects_bad_config():
    from rocket_tpu.ops.fused_block import block_attn_half

    args = _block_operands()
    with pytest.raises(ValueError, match="unknown epilogue"):
        block_attn_half(*args, num_heads=2, epilogue="retired",
                        interpret=True)
    with pytest.raises(ValueError, match="unsupported shape"):
        block_attn_half(*args, num_heads=2, block_b=3, interpret=True)


def _charlm_block(dropout=0.1):
    from rocket_tpu.models.transformer import Block, TransformerConfig

    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=128, num_layers=2,
        num_heads=2, dropout=dropout,
    )
    blk = Block(config, 0)
    return blk, blk.init_params(jax.random.key(3))


def test_block_seam_default_is_bitwise_reference():
    """With no table entry Block.apply's attention half IS the per-op
    ln1+attn chain — bitwise, train (dropout rng included) and eval."""
    blk, params = _charlm_block()
    x = _block_operands()[0]
    rng = jax.random.key(11)

    def seam(x, mode):
        y, _ = blk.apply({"params": params, "state": {}}, x, mode=mode,
                         rng=rng if mode == "train" else None)
        return y

    def manual(x, mode):
        r = (jax.random.split(jax.random.fold_in(rng, 0), 3)
             if mode == "train" else (None, None, None))
        h, _ = blk.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, _ = blk.attn.apply(
            {"params": params["attn"], "state": {}}, h, mode=mode,
            rng=r[0],
        )
        if blk.dropout is not None and mode == "train":
            h, _ = blk.dropout.apply({"params": {}, "state": {}}, h,
                                     mode=mode, rng=r[1])
        y = x + h
        h2, _ = blk.ln2.apply({"params": params["ln2"], "state": {}}, y)
        h2 = blk._mlp(params["mlp"], h2)
        if blk.dropout is not None and mode == "train":
            h2, _ = blk.dropout.apply({"params": {}, "state": {}}, h2,
                                      mode=mode, rng=r[2])
        return y + h2

    for mode in ("train", "eval"):
        np.testing.assert_array_equal(
            np.asarray(seam(x, mode)), np.asarray(manual(x, mode))
        )
    assert _pallas_calls(lambda x: seam(x, "eval"), x) == 0


@pytest.mark.parametrize("mode", ["eval", "train"])
def test_block_seam_engages_under_force(mode, monkeypatch):
    """Forced fused impl: one pallas program replaces the chain; parity
    holds in eval (full epilogue) AND train (dropout forces the
    separate-epilogue tail, which must reproduce the reference dropout
    mask exactly — same rng fold, same mask shape)."""
    blk, params = _charlm_block()
    x = _block_operands()[0]
    rng = jax.random.key(11)

    def step(x):
        y, _ = blk.apply({"params": params, "state": {}}, x, mode=mode,
                         rng=rng if mode == "train" else None)
        return y

    ref = _value_and_grads(step, x)
    monkeypatch.setenv("ROCKET_TPU_BLOCK_ATTN", "fused")
    assert _pallas_calls(step, x) == 1
    got = _value_and_grads(step, x)
    ok, err = _space_parity("block_attn", ref, got, "float32")
    assert ok, (mode, err)


def test_block_seam_ineligible_configs_stay_reference(monkeypatch):
    """RMSNorm/rope/GQA/ring blocks never consult the fused path even
    under force — the eligibility gate is static."""
    from rocket_tpu.models.transformer import Block, TransformerConfig

    monkeypatch.setenv("ROCKET_TPU_BLOCK_ATTN", "fused")
    config = TransformerConfig.llama_style(
        vocab_size=64, max_seq_len=64, dim=128, num_layers=2,
        num_heads=2, num_kv_heads=1,
    )
    blk = Block(config, 0)
    params = blk.init_params(jax.random.key(0))
    x = _block_operands()[0]
    assert not blk._block_attn_ok
    assert _pallas_calls(
        lambda x: blk.apply({"params": params, "state": {}}, x,
                            mode="eval")[0], x
    ) == 0


# -- gather-gmm (moe_gmm impl=fused) -----------------------------------------


def _routing(n_tok, e, key=1):
    rng = np.random.default_rng(key)
    pair_expert = jnp.asarray(rng.integers(0, e, size=n_tok).astype(np.int32))
    order = jnp.argsort(pair_expert, stable=True)
    sorted_token = jnp.arange(n_tok, dtype=jnp.int32)[order]
    counts = jnp.bincount(pair_expert, length=e).astype(jnp.int32)
    return sorted_token, counts


def test_padded_group_layout_invariants():
    from rocket_tpu.ops.gather_gmm import padded_group_layout

    e, tm, nk = 4, 16, 50
    sorted_token, counts = _routing(nk, e)
    row_ids, gsz, padded_pos, m = padded_group_layout(
        counts, sorted_token, tm, nk
    )
    assert m % tm == 0 and int(jnp.sum(gsz)) == m
    assert (np.asarray(gsz) % tm == 0).all()
    # Every sorted row lands at a unique padded position carrying its
    # source-token id.
    pos = np.asarray(padded_pos)
    assert len(set(pos.tolist())) == nk
    np.testing.assert_array_equal(
        np.asarray(row_ids)[pos], np.asarray(sorted_token)
    )


def test_padded_group_layout_empty_expert():
    """A zero-count expert contributes a zero-size padded group — the
    layout and kernel must survive it."""
    from rocket_tpu.ops.gather_gmm import gather_gmm, padded_group_layout

    e, tm, nk = 4, 8, 24
    # Everything routes to experts 0 and 3.
    pair_expert = jnp.asarray(([0] * 11) + ([3] * 13), jnp.int32)
    order = jnp.argsort(pair_expert, stable=True)
    sorted_token = jnp.arange(nk, dtype=jnp.int32)[order]
    counts = jnp.bincount(pair_expert, length=e).astype(jnp.int32)
    row_ids, gsz, padded_pos, m = padded_group_layout(
        counts, sorted_token, tm, nk
    )
    x = jnp.asarray(RNG.normal(size=(nk, 16)).astype(np.float32))
    rhs = jnp.asarray(RNG.normal(size=(e, 16, 128)).astype(np.float32))
    out = gather_gmm(x, rhs, row_ids, gsz, tile_m=tm, tile_n=128,
                     interpret=True)[padded_pos]
    expert_of = np.asarray(pair_expert)[np.argsort(np.asarray(pair_expert),
                                                   kind="stable")]
    want = np.stack([
        np.asarray(x)[int(t)] @ np.asarray(rhs)[int(ex)]
        for t, ex in zip(np.asarray(sorted_token), expert_of)
    ])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tile_m,tile_n", [(8, 128), (16, 128), (16, 256)])
def test_gather_gmm_parity(tile_m, tile_n):
    """The in-kernel-routed grouped matmul matches the explicit
    gather + grouped-matmul reference — fwd and grads."""
    from rocket_tpu.nn.moe import _grouped_matmul
    from rocket_tpu.ops.gather_gmm import gather_gmm, padded_group_layout

    n_tok, k, n_out, e = 48, 64, 256, 3
    x = jnp.asarray(RNG.normal(size=(n_tok, k)).astype(np.float32) * 0.2)
    rhs = jnp.asarray(
        RNG.normal(size=(e, k, n_out)).astype(np.float32) * 0.2
    )
    sorted_token, counts = _routing(n_tok, e, key=7)
    row_ids, gsz, padded_pos, _ = padded_group_layout(
        counts, sorted_token, tile_m, n_tok
    )

    def fused(x, rhs):
        return gather_gmm(x, rhs, row_ids, gsz, tile_m=tile_m,
                          tile_n=tile_n, interpret=True)[padded_pos]

    def reference(x, rhs):
        return _grouped_matmul(
            jnp.take(x, row_ids, axis=0), rhs, gsz
        )[padded_pos]

    ok, err = check_parity(
        _value_and_grads(reference, x, rhs),
        _value_and_grads(fused, x, rhs),
        "float32",
    )
    assert ok, (tile_m, tile_n, err)


def test_moe_dropless_fused_impl_parity(monkeypatch):
    """The whole dropless dispatch under impl=fused matches impl=gmm —
    outputs, aux and grads — and actually routes through the kernel."""
    from rocket_tpu.nn.moe import MoE

    moe = MoE(64, 128, 4, top_k=2, dispatch="dropless")
    params = moe.init_params(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, 64)) * 0.5

    def step(params, x):
        y, aux = moe.apply({"params": params, "state": {}}, x)
        return y

    ref = _value_and_grads(step, params, x)
    assert _pallas_calls(step, params, x) == 0
    monkeypatch.setenv("ROCKET_TPU_MOE_GMM", "fused")
    assert _pallas_calls(step, params, x) == 1
    got = _value_and_grads(step, params, x)
    ok, err = check_parity(ref, got, "float32")
    assert ok, err


def test_moe_dropless_vs_capacity_reference_dropped_token_diff(monkeypatch):
    """The dropped-token diff the dropless variant exists to remove:
    with ample capacity the einsum reference matches the fused dropless
    path; with tight capacity the reference DROPS routed pairs
    (frac_dropped > 0, outputs diverge) while dropless never does."""
    from rocket_tpu.nn.moe import MoE

    dim, hidden, e, k = 16, 32, 4, 2
    x = jax.random.normal(jax.random.key(0), (3, 24, dim))
    params = MoE(dim, hidden, e, top_k=k).init_params(jax.random.key(1))
    monkeypatch.setenv("ROCKET_TPU_MOE_GMM", "fused")
    moe_d = MoE(dim, hidden, e, top_k=k, dispatch="dropless")
    y_d, aux_d = moe_d.apply({"params": params, "state": {}}, x)
    assert float(aux_d["frac_dropped"]) == 0.0

    ample = MoE(dim, hidden, e, top_k=k, capacity_factor=e / k,
                dispatch="einsum")
    y_a, aux_a = ample.apply({"params": params, "state": {}}, x)
    assert float(aux_a["frac_dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(y_a), np.asarray(y_d), atol=1e-5)

    tight = MoE(dim, hidden, e, top_k=k, capacity_factor=0.4,
                dispatch="einsum")
    y_t, aux_t = tight.apply({"params": params, "state": {}}, x)
    assert float(aux_t["frac_dropped"]) > 0.0
    # The divergence IS the dropped tokens' lost expert contribution.
    assert float(jnp.abs(y_t - y_d).max()) > 1e-3


# -- sched_audit coverage (RKT504 over the fused programs) -------------------


def test_fused_kernels_sched_target_prices_all_three():
    from rocket_tpu.analysis.sched_audit import (
        SCHED_TARGETS,
        run_sched_target,
    )

    report = run_sched_target(SCHED_TARGETS["fused_kernels"])
    names = {fact.name for fact in report.pallas}
    assert {"_twopass_kernel", "_block_kernel",
            "_gather_gmm_kernel"} <= names
    assert report.findings == []
    for fact in report.pallas:
        assert fact.vmem_bytes_est < 16 << 20, fact


def test_pallas_fact_excludes_any_space_operands():
    """An ANY/HBM-resident operand (manually DMA'd, e.g. gather_gmm's
    token array) must not count toward the double-buffered VMEM
    estimate — it would flag every HBM-resident operand as an
    overflow."""
    from jax.experimental import pallas as pl

    from rocket_tpu.analysis.sched_audit import collect_pallas_facts

    big = 8192

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.zeros_like(o_ref)

    def step(variables, batch):
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
        )(batch["x"])
        return variables, out.sum()

    batch = {"x": jax.ShapeDtypeStruct((big, big), jnp.float32)}
    (fact,) = collect_pallas_facts(step, {"params": {}, "state": {}},
                                   batch)
    # Only the (8, 128) out block is double-buffered; the 256 MiB ANY
    # operand is excluded.
    assert fact.vmem_bytes_est == 2 * 8 * 128 * 4
