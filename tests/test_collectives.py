"""Overlapped collective matmuls (parallel/collectives.py), the ring
index math (ops/ring.py) and the bucketed gradient reduce-scatter
(parallel/grad_sync.py) — numerics contracts on the fake 8-device mesh.

The contracts pinned here are the ISSUE-12 acceptance surface:

* fp32 ``all_gather_matmul`` is BITWISE identical to gather-then-matmul
  in ring and bulk modes (chunk reordering is a pure gather);
* bulk ``matmul_reduce_scatter`` is BITWISE identical to einsum+psum;
  the ring form reassociates the cross-device sum (allclose);
* bf16-compressed gradients stay allclose to the fp32 reference while
  params remain fp32 masters (asserted through prec_audit's fact
  stream: the wire narrows are visible, certified facts);
* ``ROCKET_TPU_OVERLAP=0`` restores the plain GSPMD program exactly
  (compiled-HLO identity on the audit targets);
* bucket planning handles indivisible leaf counts and single-leaf
  buckets, and the fp32 bucket-sum correction makes each bucket's total
  gradient mass exact.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rocket_tpu.ops import ring as ring_lib
from rocket_tpu.parallel import collectives as coll
from rocket_tpu.parallel import grad_sync


def _mesh(shape):
    sizes = tuple(shape.values())
    need = int(np.prod(sizes))
    devices = jax.devices()[:need]
    if len(devices) < need:
        pytest.skip(f"needs {need} devices")
    return Mesh(np.asarray(devices).reshape(sizes), tuple(shape))


def _spec(mesh, mode="bulk", wire="bfloat16", axis="model"):
    return coll.OverlapSpec(mesh=mesh, axis=axis, mode=mode, wire=wire)


# -- ring index math ---------------------------------------------------------


def test_ring_index_math_matches_bruteforce():
    n = 8
    for d in range(n):
        # all-gather: after s hops device d holds chunk (d-s)%n; the
        # gather order must re-index arrival order into global order.
        arrival = [(d - s) % n for s in range(n)]
        order = np.asarray(ring_lib.gather_order(d, n))
        assert [arrival[int(j)] for j in order] == list(range(n))
        # reduce-scatter: seed + per-hop chunk picks must deliver, to
        # every device, the sum of ALL devices' partials for its chunk.
        accs = {dd: {(dd, int(ring_lib.rs_seed_index(dd, n)))}
                for dd in range(n)}
        for s in range(1, n):
            received = {dd: accs[(dd - 1) % n] for dd in range(n)}
            accs = {
                dd: received[dd] | {(dd, int(ring_lib.rs_chunk_index(dd, s, n)))}
                for dd in range(n)
            }
        assert accs[d] == {(src, d) for src in range(n)}


def test_use_ring_thresholds():
    assert ring_lib.use_ring(1, "ring", 1 << 20)
    assert not ring_lib.use_ring(1 << 30, "bulk", 1)
    assert ring_lib.use_ring(2 << 20, "auto", 1 << 20)
    assert not ring_lib.use_ring(1 << 10, "auto", 1 << 20)
    with pytest.raises(ValueError):
        ring_lib.use_ring(1, "nope", 1)


# -- collective matmul parity ------------------------------------------------


MESH_SHAPES = ({"data": 1, "model": 8}, {"data": 2, "model": 4})


@pytest.mark.parametrize("mode", ["bulk", "ring"])
@pytest.mark.parametrize("mesh_shape", MESH_SHAPES, ids=["1x8", "2x4"])
def test_all_gather_matmul_fp32_bitwise(mesh_shape, mode):
    mesh = _mesh(mesh_shape)
    n = mesh.shape["model"]
    key = jax.random.key(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 32))
    wa = jax.random.normal(jax.random.fold_in(key, 2), (32, 48))
    wb = jax.random.normal(jax.random.fold_in(key, 3), (32, 16))
    spec = _spec(mesh, mode)
    assert 16 % n == 0 and 48 % n == 0
    x_sh = jax.device_put(x, NamedSharding(mesh, P(None, "model", None)))
    with mesh:
        ya, yb = jax.jit(
            lambda x: coll.all_gather_matmul(spec, x, (wa, wb))
        )(x_sh)
    # Bitwise in BOTH modes: the ring's chunk re-ordering is a pure
    # gather; per-row dot products are untouched.
    assert jnp.array_equal(ya, x @ wa)
    assert jnp.array_equal(yb, x @ wb)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES, ids=["1x8", "2x4"])
def test_matmul_reduce_scatter_bulk_matches_psum(mesh_shape):
    mesh = _mesh(mesh_shape)
    key = jax.random.key(1)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(key, 2), (48, 32))
    x_sh = jax.device_put(x, NamedSharding(mesh, P(None, None, "model")))
    w_sh = jax.device_put(w, NamedSharding(mesh, P("model", None)))
    spec = _spec(mesh, "bulk")

    psum_ref = shard_map(
        lambda xl, wl: jax.lax.psum(xl @ wl, "model"), mesh=mesh,
        in_specs=(P(None, None, "model"), P("model", None)),
        out_specs=P(), check_vma=False,
    )
    with mesh:
        got = jax.jit(lambda x, w: coll.matmul_reduce_scatter(spec, x, w))(
            x_sh, w_sh
        )
        ref = jax.jit(psum_ref)(x_sh, w_sh)
    # The bulk path is the einsum+psum program, re-laid-out — but the
    # order in which XLA's reduce-scatter and its all-reduce sum the
    # per-device partials is XLA's business (jax 0.9.0: identical on the
    # 1x8 mesh, one ulp apart on 2x4), so the contract is fp32 rounding.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-5
    )


def test_matmul_reduce_scatter_ring_allclose():
    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(2)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(key, 2), (48, 32))
    spec = _spec(mesh, "ring")
    with mesh:
        got = jax.jit(lambda x, w: coll.matmul_reduce_scatter(spec, x, w))(
            x, w
        )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), rtol=0, atol=1e-4
    )


@pytest.mark.parametrize("mode", ["bulk", "ring"])
@pytest.mark.parametrize("mesh_shape", MESH_SHAPES, ids=["1x8", "2x4"])
def test_fwd_bwd_parity_vs_einsum_psum(mesh_shape, mode):
    """Full fwd+bwd chain through both primitives vs the plain
    reference: exact with the fp32 wire, allclose with the bf16 wire."""
    mesh = _mesh(mesh_shape)
    key = jax.random.key(3)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 32))
    w1 = jax.random.normal(jax.random.fold_in(key, 2), (32, 48))
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (48, 32))

    def ref_loss(x):
        return jnp.sum(((jnp.tanh(x @ w1)) @ w2) ** 2)

    g_ref = jax.grad(ref_loss)(x)

    for wire, tol in ((None, 5e-6), ("bfloat16", 2e-2)):
        spec = _spec(mesh, mode, wire=wire)

        def loss(x):
            (h,) = coll.all_gather_matmul(spec, x, (w1,))
            y = coll.matmul_reduce_scatter(spec, jnp.tanh(h), w2)
            return jnp.sum(y ** 2)

        with mesh:
            g = jax.jit(jax.grad(loss))(
                jax.device_put(x, NamedSharding(mesh, P(None, "model", None)))
            )
        scale = float(jnp.max(jnp.abs(g_ref)))
        assert float(jnp.max(jnp.abs(g - g_ref))) <= tol * scale, (mode, wire)


@pytest.mark.parametrize("mesh_shape", MESH_SHAPES, ids=["1x8", "2x4"])
def test_weight_grads_sum_over_data_axis(mesh_shape):
    """Weight/bias/table gradients are computed per BATCH shard inside
    the manual region and must psum over the data axes — on a 2x4 mesh
    a missing reduction silently drops half the batch's contribution
    (regression: caught in review, never by the x-only parity test)."""
    mesh = _mesh(mesh_shape)
    key = jax.random.key(21)
    w1 = jax.random.normal(jax.random.fold_in(key, 2), (32, 48))
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (48, 32))
    b2 = jax.random.normal(jax.random.fold_in(key, 4), (32,))
    table = jax.random.normal(jax.random.fold_in(key, 5), (64, 32))
    tokens = jax.random.randint(jax.random.fold_in(key, 6), (8, 16), 0, 64)
    spec = _spec(mesh, "bulk", wire=None)

    def loss(w1, w2, b2, table):
        emb = coll.embed_lookup_sharded(spec, table, tokens)
        (h,) = coll.all_gather_matmul(spec, emb, (w1,))
        y = coll.matmul_reduce_scatter(spec, jnp.tanh(h), w2, bias=b2)
        return jnp.sum(y ** 2)

    def ref(w1, w2, b2, table):
        emb = jnp.take(table, tokens, axis=0)
        return jnp.sum((jnp.tanh(emb @ w1) @ w2 + b2) ** 2)

    with mesh:
        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(w1, w2, b2, table)
    want = jax.grad(ref, argnums=(0, 1, 2, 3))(w1, w2, b2, table)
    for name, g, r in zip(("dw1", "dw2", "db2", "dtable"), got, want):
        scale = float(jnp.max(jnp.abs(r))) + 1e-9
        err = float(jnp.max(jnp.abs(g - r)))
        assert err <= 1e-4 * scale, (name, err, scale)


def test_mmrs_fused_bias_grad_is_local_and_exact():
    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(4)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(key, 2), (48, 32))
    b = jax.random.normal(jax.random.fold_in(key, 3), (32,))
    spec = _spec(mesh, "bulk", wire=None)

    def loss(x, w, b):
        return jnp.sum(coll.matmul_reduce_scatter(spec, x, w, bias=b) ** 2)

    def ref(x, w, b):
        return jnp.sum((x @ w + b) ** 2)

    with mesh:
        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)
    want = jax.grad(ref, argnums=(0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=1e-3)


# -- qkv weight views --------------------------------------------------------


def test_qkv_fused_views_match_global_slices():
    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(5)
    hw, kvw, d_in = 64, 32, 32
    w = jax.random.normal(jax.random.fold_in(key, 1), (d_in, hw + 2 * kvw))
    b = jax.random.normal(jax.random.fold_in(key, 2), (hw + 2 * kvw,))
    spec = _spec(mesh, "bulk")
    with mesh:
        wq, wk, wv, bq, bk, bv = jax.jit(
            lambda w, b: coll.qkv_fused_views(spec, w, b, hw, kvw)
        )(w, b)
    assert jnp.array_equal(wq, w[:, :hw])
    assert jnp.array_equal(wk, w[:, hw:hw + kvw])
    assert jnp.array_equal(wv, w[:, hw + kvw:])
    assert jnp.array_equal(bq, b[:hw])
    assert jnp.array_equal(bk, b[hw:hw + kvw])
    assert jnp.array_equal(bv, b[hw + kvw:])

    # Backward: gradients land back on the fused layout exactly.
    def loss(w, b):
        wq, wk, wv, bq, bk, bv = coll.qkv_fused_views(spec, w, b, hw, kvw)
        return (jnp.sum(wq ** 2) + 2 * jnp.sum(wk ** 2)
                + 3 * jnp.sum(wv ** 2) + jnp.sum(bq * bq)
                + jnp.sum(bk) + jnp.sum(bv ** 3))

    def ref(w, b):
        return (jnp.sum(w[:, :hw] ** 2) + 2 * jnp.sum(w[:, hw:hw + kvw] ** 2)
                + 3 * jnp.sum(w[:, hw + kvw:] ** 2) + jnp.sum(b[:hw] ** 2)
                + jnp.sum(b[hw:hw + kvw]) + jnp.sum(b[hw + kvw:] ** 3))

    with mesh:
        got = jax.jit(jax.grad(loss, argnums=(0, 1)))(w, b)
    want = jax.grad(ref, argnums=(0, 1))(w, b)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=1e-5)


# -- embedding + seq helpers -------------------------------------------------


def test_embed_lookup_sharded_fwd_bitwise_and_grads():
    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(6)
    v, d = 64, 32
    table = jax.random.normal(jax.random.fold_in(key, 1), (v, d))
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (4, 16), 0, v)
    spec = _spec(mesh, "bulk")
    with mesh:
        emb = jax.jit(
            lambda tb: coll.embed_lookup_sharded(spec, tb, tokens)
        )(table)
        assert jnp.array_equal(emb, jnp.take(table, tokens, axis=0))
        g = jax.jit(jax.grad(lambda tb: jnp.sum(
            coll.embed_lookup_sharded(spec, tb, tokens) ** 2
        )))(table)
    g_ref = jax.grad(
        lambda tb: jnp.sum(jnp.take(tb, tokens, axis=0) ** 2)
    )(table)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=0, atol=8e-2)


def test_embed_lookup_compute_dtype_bitwise_equal_to_cast_after():
    """Each row has exactly one nonzero contributor, so reducing at the
    compute dtype equals casting after the psum — the certified
    narrowing changes the WIRE, not the value."""
    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(7)
    table = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (4, 16), 0, 64)
    spec = _spec(mesh, "bulk")
    with mesh:
        emb = jax.jit(lambda tb: coll.embed_lookup_sharded(
            spec, tb, tokens, compute_dtype=jnp.bfloat16
        ))(table)
    ref = jnp.take(table, tokens, axis=0).astype(jnp.bfloat16)
    assert emb.dtype == jnp.bfloat16
    assert jnp.array_equal(emb, ref)


def test_seq_shard_gather_roundtrip_and_grads():
    mesh = _mesh({"data": 1, "model": 8})
    x = jax.random.normal(jax.random.key(8), (4, 16, 32))
    spec = _spec(mesh, "bulk")
    with mesh:
        xs = jax.jit(lambda x: coll.seq_shard(spec, x))(x)
        assert jnp.array_equal(xs, x)
        xr = jax.jit(lambda x: coll.seq_all_gather(spec, x))(xs)
        assert jnp.array_equal(xr, x)
        g = jax.jit(jax.grad(lambda x: jnp.sum(
            coll.seq_all_gather(spec, coll.seq_shard(spec, x)) ** 2
        )))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * x),
                               rtol=0, atol=5e-2)


# -- overlap context gating --------------------------------------------------


def test_tp_overlap_disabled_by_env(monkeypatch):
    mesh = _mesh({"data": 1, "model": 8})
    monkeypatch.setenv("ROCKET_TPU_OVERLAP", "0")
    with coll.tp_overlap(mesh) as spec:
        assert spec is None
        assert coll.current_tp() is None


def test_tp_overlap_noop_without_axis():
    mesh = _mesh({"data": 8})
    with coll.tp_overlap(mesh, axis="model") as spec:
        assert spec is None


def test_tp_overlap_active_and_restored():
    mesh = _mesh({"data": 1, "model": 8})
    assert coll.current_tp() is None
    with coll.tp_overlap(mesh) as spec:
        assert spec is not None
        assert coll.current_tp() is spec
    assert coll.current_tp() is None


def test_grad_wire_dtype_env(monkeypatch):
    monkeypatch.delenv("ROCKET_TPU_OVERLAP_WIRE", raising=False)
    assert coll.grad_wire_dtype() == jnp.bfloat16
    monkeypatch.setenv("ROCKET_TPU_OVERLAP_WIRE", "fp32")
    assert coll.grad_wire_dtype() is None
    monkeypatch.setenv("ROCKET_TPU_OVERLAP_WIRE", "off")
    assert coll.grad_wire_dtype() is None


# -- overlap-off step identity ----------------------------------------------


def _without_locations(hlo_text):
    """Compiled HLO text minus where it was traced from: the FileNames /
    FunctionNames / FileLocations / StackFrames tables and every op's
    ``stack_frame_id``. They follow the Python call stack of the trace
    (two builds of one program from two call sites differ there), not
    the program."""
    import re

    out, in_table = [], False
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            in_table = True
        elif in_table and not line.strip():
            in_table = False
        elif not in_table:
            out.append(re.sub(r" ?stack_frame_id=\d+", "", line))
    return "\n".join(out)


def test_overlap_off_restores_plain_program(monkeypatch):
    """ROCKET_TPU_OVERLAP=0 must rebuild the EXACT pre-overlap GSPMD
    program: the compiled HLO of the tp_1x8 audit step with the kill
    switch equals the step built with no markers at all."""
    from rocket_tpu.analysis import shard_audit as sa
    from rocket_tpu.parallel.sharding import gpt2_tp_rules

    mesh = sa._mesh_from_shape({"data": 1, "model": 8})

    def compiled_text():
        step_fn, variables, batch, rules, donate = sa._tp_parts()
        abs_v, abs_b, _s, _f = sa.resolve_placement(
            variables, batch, rules=rules, mesh=mesh
        )
        compiled, findings = sa.aot_compile_step(
            step_fn, abs_v, abs_b, mesh=mesh, donate_argnums=donate
        )
        assert findings == []
        return compiled.as_text()

    monkeypatch.setenv("ROCKET_TPU_OVERLAP", "0")
    off_text = compiled_text()

    # Reference: the same model/rules WITHOUT overlap markers.
    monkeypatch.delenv("ROCKET_TPU_OVERLAP", raising=False)
    bare_rules = gpt2_tp_rules(axis="model")
    del bare_rules.tp_axis
    step_fn, variables, batch, _r, donate = sa._lm_parts(
        bare_rules, mesh_shape={"data": 1, "model": 8}
    )
    abs_v, abs_b, _s, _f = sa.resolve_placement(
        variables, batch, rules=bare_rules, mesh=mesh
    )
    compiled, _ = sa.aot_compile_step(
        step_fn, abs_v, abs_b, mesh=mesh, donate_argnums=donate
    )
    assert _without_locations(off_text) == \
        _without_locations(compiled.as_text())


def test_overlap_on_step_allclose_to_off():
    """The overlapped tp_1x8 train step computes the same update as the
    plain GSPMD step (fp32 model, bf16 gradient wire -> loose grads but
    tight loss)."""
    from rocket_tpu.analysis import shard_audit as sa

    mesh = sa._mesh_from_shape({"data": 1, "model": 8})
    step_fn, variables, batch, rules, _d = sa._tp_parts()

    key = jax.random.key(0)
    from rocket_tpu.models.transformer import TransformerLM

    model = TransformerLM(sa._lm_config())
    concrete = jax.jit(model.init)(key)
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1), (16, model.config.max_seq_len), 0, 256
    )
    with mesh:
        new_state, loss = jax.jit(step_fn)(
            {"params": concrete["params"], "state": concrete["state"]},
            {"tokens": tokens},
        )

    import os
    assert os.environ.get("ROCKET_TPU_OVERLAP", "1") != "0"
    # Plain reference (no mesh context, single logical program).
    import optax

    def ref_loss(variables, batch):
        out, _ = model.apply(variables, dict(batch), mode="train")
        logits = out["logits"][:, :-1].astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, out["tokens"][:, 1:]
        ).mean()

    ref_l, ref_g = jax.value_and_grad(ref_loss)(
        {"params": concrete["params"], "state": concrete["state"]},
        {"tokens": tokens},
    )
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
    # Updated params: p - 1e-3 g, grads crossed the bf16 wire.
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        new_state["params"]
    )[0]:
        ref_leaf = jax.tree_util.tree_flatten_with_path(
            jax.tree.map(
                lambda p, g: p - 1e-3 * g,
                concrete["params"], ref_g["params"],
            )
        )[0]
    got = np.concatenate([
        np.ravel(l) for l in jax.tree.leaves(new_state["params"])
    ])
    want = np.concatenate([
        np.ravel(l) for l in jax.tree.leaves(jax.tree.map(
            lambda p, g: p - 1e-3 * g, concrete["params"], ref_g["params"]
        ))
    ])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# -- fp32 masters via the prec fact stream -----------------------------------


def test_bf16_wire_facts_show_fp32_masters_and_certify():
    """The compressed-gradient wire is VISIBLE: prec_audit records the
    narrowed collectives with their fp32 master dtype, and the
    certification turns them from findings into an audit trail."""
    from rocket_tpu.analysis.prec_audit import (
        audit_precision, certify_collectives, collect_dtype_flow,
    )

    mesh = _mesh({"data": 1, "model": 8})
    spec = _spec(mesh, "bulk", wire="bfloat16")
    x = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 48), jnp.float32)

    def step(variables, batch):
        def loss(w):
            (h,) = coll.all_gather_matmul(spec, batch["x"], (w,))
            return jnp.sum(h ** 2)

        return variables, jax.grad(loss)(variables["params"]["w"])

    variables = {"params": {"w": w}, "state": {}}
    batch = {"x": x}
    with mesh:
        flow, _i, _o = collect_dtype_flow(step, variables, batch)
    wire_facts = [
        f for f in flow.collectives if "ring_wire" in f.param_path
    ]
    assert wire_facts, [f.param_path for f in flow.collectives]
    for fact in wire_facts:
        # fp32 master guarantee: the value was narrowed FROM fp32.
        assert np.dtype(fact.master_dtype) == np.float32

    with mesh:
        rep = audit_precision(step, variables, batch)
    assert any(f.rule == "RKT403" for f in rep.findings)
    certified = certify_collectives("*ring_wire*")(step)
    with mesh:
        rep2 = audit_precision(certified, variables, batch)
    assert [f for f in rep2.findings if f.rule == "RKT403"] == []
    assert rep2.record["certified_collectives"] == 1


# -- grad_sync ---------------------------------------------------------------


def test_bucket_plan_edges():
    leaves = [
        (0, jax.ShapeDtypeStruct((100,), jnp.float32)),   # 400 B
        (1, jax.ShapeDtypeStruct((100,), jnp.float32)),
        (2, jax.ShapeDtypeStruct((1000,), jnp.float32)),  # oversized
        (3, jax.ShapeDtypeStruct((10,), jnp.bfloat16)),   # dtype break
        (4, jax.ShapeDtypeStruct((10,), jnp.bfloat16)),
    ]
    buckets = grad_sync.bucket_plan(leaves, bucket_bytes=900)
    # 0+1 fit; 2 overflows into its own; 3+4 split by dtype.
    assert buckets == [[0, 1], [2], [3, 4]]
    # Single-param bucket: one oversized leaf still reduces.
    assert grad_sync.bucket_plan(leaves[2:3], bucket_bytes=1) == [[2]]


@pytest.mark.parametrize("wire", ["bfloat16", None])
def test_value_and_grad_sharded_matches_reference(wire):
    mesh = _mesh({"data": 8})
    key = jax.random.key(9)
    d, h = 32, 64
    params = {
        "w1": jax.random.normal(jax.random.fold_in(key, 1), (d, h)),
        "b1": jnp.full((h,), 0.1),
        "w2": jax.random.normal(jax.random.fold_in(key, 2), (h, 4)),
        # 7 elements: the bucket pad path (not divisible by 8).
        "scale": jnp.ones((7,)),
    }
    batch = {
        "x": jax.random.normal(jax.random.fold_in(key, 3), (32, d)),
        "y": jax.random.normal(jax.random.fold_in(key, 4), (32, 4)),
    }

    def spec_fn(path, leaf):
        return ("data", None) if path[-1] in ("w1", "w2") else None

    def loss_fn(p, b):
        hidden = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        pred = (hidden @ p["w2"]) * p["scale"][:4].sum()
        return jnp.mean((pred - b["y"]) ** 2)

    ref_l, ref_g = jax.value_and_grad(loss_fn)(params, batch)
    placed = {
        k: jax.device_put(v, NamedSharding(
            mesh, P("data") if k in ("w1", "w2") else P()
        ))
        for k, v in params.items()
    }
    with mesh:
        (loss, _aux), grads = jax.jit(lambda p, b: grad_sync.value_and_grad_sharded(
            loss_fn, p, b, mesh=mesh, spec_fn=spec_fn, wire_dtype=wire,
            bucket_bytes=64,
        ))(placed, batch)
    # mean-of-local-means reassociates the mean: relative, not bitwise.
    assert abs(float(loss - ref_l)) / (abs(float(ref_l)) + 1e-9) < 1e-5
    tol = 5e-6 if wire is None else 5e-3
    for k in params:
        scale = float(jnp.max(jnp.abs(ref_g[k]))) + 1e-9
        err = float(jnp.max(jnp.abs(grads[k] - ref_g[k])))
        assert err <= tol * scale, (k, err, scale)
    if wire is not None:
        # fp32 bucket-sum correction: replicated buckets preserve the
        # exact fp32 gradient mass.
        for k in ("b1", "scale"):
            assert abs(float(jnp.sum(grads[k]) - jnp.sum(ref_g[k]))) < 1e-3


def test_value_and_grad_sharded_rejects_unshardable_aux():
    """A non-scalar, non-batch-led aux leaf cannot be reassembled from
    the manual region under EITHER spec — the builder must fail loudly
    (silently concatenating n identical copies was the alternative)."""
    mesh = _mesh({"data": 8})
    params = {"w": jnp.ones((8, 8))}
    batch = {"x": jnp.ones((16, 8))}

    def loss_fn(p, b):
        out = b["x"] @ p["w"]
        return jnp.mean(out ** 2), {"per_layer": jnp.ones((5,))}

    with pytest.raises(ValueError, match="batch-led"):
        grad_sync.value_and_grad_sharded(
            loss_fn, params, batch, mesh=mesh, has_aux=True
        )


def test_value_and_grad_sharded_single_device_fallback():
    mesh = _mesh({"data": 8})
    small = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    params = {"w": jnp.ones((4, 4))}
    batch = {"x": jnp.ones((8, 4))}

    def loss_fn(p, b):
        return jnp.sum((b["x"] @ p["w"]) ** 2)

    (loss, _aux), grads = grad_sync.value_and_grad_sharded(
        loss_fn, params, batch, mesh=small
    )
    ref_l, ref_g = jax.value_and_grad(loss_fn)(params, batch)
    assert jnp.allclose(loss, ref_l)
    assert jnp.allclose(grads["w"], ref_g["w"])
    del mesh


def test_value_and_grad_sharded_aux_structure():
    mesh = _mesh({"data": 8})
    params = {"w": jax.random.normal(jax.random.key(10), (8, 8))}
    batch = {"x": jax.random.normal(jax.random.key(11), (16, 8))}

    def loss_fn(p, b):
        out = b["x"] @ p["w"]
        loss = jnp.mean(out ** 2)
        return loss, {"out": out * 1.0, "scalar": loss * 3.0}

    with mesh:
        (loss, aux), _g = jax.jit(lambda p, b: grad_sync.value_and_grad_sharded(
            loss_fn, p, b, mesh=mesh, wire_dtype=None, has_aux=True
        ))(params, batch)
    assert np.asarray(aux["out"]).shape == (16, 8)
    np.testing.assert_allclose(
        np.asarray(aux["out"]), np.asarray(batch["x"] @ params["w"]),
        rtol=1e-6,
    )
    np.testing.assert_allclose(float(aux["scalar"]), 3 * float(loss),
                               rtol=1e-5)


# -- Dense tp_role -----------------------------------------------------------


def test_dense_tp_roles_under_context():
    from rocket_tpu.nn.layers import Dense

    mesh = _mesh({"data": 1, "model": 8})
    key = jax.random.key(12)
    col = Dense(32, 64, tp_role="column")
    row = Dense(64, 32, tp_role="row")
    pc = col.init(key)["params"]
    pr = row.init(jax.random.fold_in(key, 1))["params"]
    x = jax.random.normal(jax.random.fold_in(key, 2), (4, 16, 32))

    def fwd(x):
        with coll.tp_overlap(mesh, wire=None):
            h, _ = col.apply({"params": pc, "state": {}}, x)
            y, _ = row.apply({"params": pr, "state": {}}, h)
        return h, y

    with mesh:
        h, y = jax.jit(fwd)(x)
    h_ref = x @ pc["w"] + pc["b"]
    y_ref = h_ref @ pr["w"] + pr["b"]
    # Column role = gather-then-matmul: every output element is one
    # device's whole dot product, but a per-shard matmul may run a
    # different dot kernel than the full-size one — fp32 rounding, not
    # bit identity (what IS bit-identical is the overlap-OFF program,
    # test_overlap_off_restores_plain_program).
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=0, atol=1e-4)
    with pytest.raises(ValueError):
        Dense(4, 4, tp_role="diagonal")
