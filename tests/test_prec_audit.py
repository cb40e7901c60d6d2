"""prec_audit: dtype-flow rule checks (RKT401-405) with true positives
and clean negatives per rule, provenance propagation (casts, transparent
ops, pjit bodies, shard_map collectives), the numerics budget gate
(RKT406), rocketlint-directive suppression parity, and the builtin
self-gate / seeded-bad ``badprec`` targets.
"""

import jax
import jax.numpy as jnp
import pytest

from rocket_tpu.analysis import budgets
from rocket_tpu.analysis.prec_audit import (
    PREC_TARGETS,
    audit_precision,
    collect_dtype_flow,
    run_prec_target,
)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def rules_in(findings):
    return sorted({f.rule for f in findings})


def variables(**params):
    return {"params": dict(params), "state": {}}


# -- RKT401: low-precision accumulation --------------------------------------

def test_large_bf16_matmul_fires():
    vs = variables(w=sds((4096, 64), jnp.float32))
    batch = {"x": sds((4, 4096), jnp.bfloat16)}

    def step(vs, batch):
        return batch["x"] @ vs["params"]["w"].astype(jnp.bfloat16)

    findings = audit_precision(step, vs, batch, check_state=False).findings
    assert rules_in(findings) == ["RKT401"]
    assert "4096-long contraction" in findings[0].message
    assert "params/w" in findings[0].message


def test_fp32_accumulated_or_small_matmuls_clean():
    vs = variables(w=sds((4096, 64), jnp.float32),
                   w_small=sds((256, 64), jnp.float32))
    batch = {"x": sds((4, 4096), jnp.bfloat16),
             "xs": sds((4, 256), jnp.bfloat16)}

    def step(vs, batch):
        # Large contraction, but fp32 accumulation declared: clean.
        big = jnp.einsum(
            "bk,kn->bn", batch["x"],
            vs["params"]["w"].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)
        # Sub-threshold contraction in pure bf16 is the convention (the
        # MXU accumulates a single dot in f32 internally): clean.
        small = batch["xs"] @ vs["params"]["w_small"].astype(jnp.bfloat16)
        return big.sum() + small.sum()

    assert audit_precision(step, vs, batch, check_state=False).findings == []


def test_ragged_dot_fires_at_any_size_unless_fp32():
    vs = variables(w=sds((4, 64, 32), jnp.float32))
    batch = {"x": sds((16, 64), jnp.bfloat16),
             "sizes": sds((4,), jnp.int32)}

    def bad(vs, batch):
        return jax.lax.ragged_dot(
            batch["x"], vs["params"]["w"].astype(jnp.bfloat16),
            batch["sizes"], preferred_element_type=jnp.bfloat16,
        )

    findings = audit_precision(bad, vs, batch, check_state=False).findings
    assert rules_in(findings) == ["RKT401"]
    assert "grouped partial sums" in findings[0].message

    def good(vs, batch):
        return jax.lax.ragged_dot(
            batch["x"], vs["params"]["w"].astype(jnp.bfloat16),
            batch["sizes"], preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)

    assert audit_precision(good, vs, batch, check_state=False).findings == []


def test_large_bf16_reduction_fires_small_or_fp32_clean():
    batch = {"big": sds((4, 8192), jnp.bfloat16),
             "small": sds((4, 128), jnp.bfloat16)}

    def bad(vs, batch):
        # jnp.sum upcasts bf16 accumulation to f32 by itself (that is
        # the convention working), so the raw-monoid form stands in for
        # the places XLA keeps the operand dtype — transpose-of-broadcast
        # bias gradients are the in-tree shape of this reduce.
        import numpy as np
        return jax.lax.reduce(
            batch["big"], np.array(0, jnp.bfloat16), jax.lax.add, (1,)
        )

    findings = audit_precision(bad, {}, batch, check_state=False).findings
    assert rules_in(findings) == ["RKT401"]
    assert "8192 elements" in findings[0].message

    def good(vs, batch):
        return (
            jnp.sum(batch["big"].astype(jnp.float32), axis=-1)
            + jnp.sum(batch["small"], axis=-1).astype(jnp.float32)
        )

    assert audit_precision(good, {}, batch, check_state=False).findings == []


# -- RKT402: sub-fp32 transcendentals ----------------------------------------

def test_bf16_softmax_fires_fp32_softmax_clean():
    batch = {"x": sds((4, 128), jnp.bfloat16)}

    def bad(vs, batch):
        return jax.nn.softmax(batch["x"], axis=-1)

    findings = audit_precision(bad, {}, batch, check_state=False).findings
    assert "RKT402" in rules_in(findings)
    assert "exp" in findings[0].message

    def good(vs, batch):
        return jax.nn.softmax(
            batch["x"].astype(jnp.float32), axis=-1
        ).astype(batch["x"].dtype)

    assert audit_precision(good, {}, batch, check_state=False).findings == []


def test_bounded_activations_stay_exempt():
    """gelu/silu (tanh/erf/logistic) at bf16 are the convention — only
    the exp/log family counts for RKT402."""
    batch = {"x": sds((4, 128), jnp.bfloat16)}

    def step(vs, batch):
        return jax.nn.gelu(batch["x"]) + jax.nn.silu(batch["x"])

    assert audit_precision(step, {}, batch, check_state=False).findings == []


# -- RKT403: state narrowing + collective operands ---------------------------

def test_state_narrowed_on_exit_fires():
    vs = {"params": {"w": sds((8, 8), jnp.float32)},
          "state": {"ema": sds((8, 8), jnp.float32)}}
    batch = {"x": sds((4, 8), jnp.float32)}

    def bad(vs, batch):
        ema = (0.9 * vs["state"]["ema"]).astype(jnp.bfloat16)
        return {"params": vs["params"], "state": {"ema": ema}}, 0.0

    findings = audit_precision(bad, vs, batch).findings
    assert rules_in(findings) == ["RKT403"]
    assert "state/ema" in findings[0].message

    def good(vs, batch):
        ema = 0.9 * vs["state"]["ema"] + 0.1 * jnp.sum(batch["x"])
        return {"params": vs["params"], "state": {"ema": ema}}, 0.0

    assert audit_precision(good, vs, batch).findings == []


def test_collective_operand_narrowed_from_param_fires():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(jax.devices()[:8], ("d",))
    vs = variables(w=sds((8, 8), jnp.float32))
    batch = {"x": sds((8, 8), jnp.float32)}

    def bad(vs, batch):
        w16 = vs["params"]["w"].astype(jnp.bfloat16)
        return shard_map(
            lambda w: jax.lax.psum(w, "d"),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )(w16)

    findings = audit_precision(bad, vs, batch, check_state=False).findings
    assert "RKT403" in rules_in(findings)
    assert "psum" in findings[0].message

    def good(vs, batch):
        return shard_map(
            lambda w: jax.lax.psum(w, "d"),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )(vs["params"]["w"])

    assert audit_precision(good, vs, batch, check_state=False).findings == []


# -- RKT404: cast churn ------------------------------------------------------

def test_widen_narrow_roundtrip_fires_even_through_reshape():
    batch = {"x": sds((4, 64), jnp.bfloat16)}

    def bad(vs, batch):
        return batch["x"].astype(jnp.float32).astype(jnp.bfloat16).sum()

    report = audit_precision(bad, {}, batch, check_state=False)
    assert rules_in(report.findings) == ["RKT404"]
    assert report.record["cast_churn"] == 1

    def bad_reshaped(vs, batch):
        # The round trip survives dtype-preserving ops in between.
        wide = batch["x"].astype(jnp.float32).reshape(8, 32)
        return wide.astype(jnp.bfloat16).sum()

    report = audit_precision(bad_reshaped, {}, batch, check_state=False)
    assert rules_in(report.findings) == ["RKT404"]


def test_work_inside_widened_window_is_not_churn():
    batch = {"x": sds((4, 64), jnp.bfloat16)}

    def good(vs, batch):
        wide = batch["x"].astype(jnp.float32)
        stats = wide - jnp.mean(wide, axis=-1, keepdims=True)
        return stats.astype(jnp.bfloat16).sum()

    report = audit_precision(good, {}, batch, check_state=False)
    assert report.findings == []
    assert report.record["cast_churn"] == 0


# -- RKT405: params never cast at use ----------------------------------------

def test_uncast_fp32_param_in_declared_bf16_step_fires():
    vs = variables(w=sds((512, 512), jnp.float32))  # 1 MiB
    batch = {"x": sds((4, 512), jnp.float32)}

    def bad(vs, batch):
        return batch["x"] @ vs["params"]["w"]

    findings = audit_precision(
        bad, vs, batch, compute_dtype=jnp.bfloat16, check_state=False
    ).findings
    assert rules_in(findings) == ["RKT405"]
    assert "params/w" in findings[0].message

    # Without a declared compute dtype there is no convention to break.
    assert audit_precision(bad, vs, batch, check_state=False).findings == []


def test_cast_at_use_island_and_small_params_exempt():
    vs = variables(
        w=sds((512, 512), jnp.float32),
        w_island=sds((512, 512), jnp.float32),
        scale=sds((512,), jnp.float32),  # small: policy, not hazard
    )
    batch = {"x": sds((4, 512), jnp.bfloat16)}

    def good(vs, batch):
        p = vs["params"]
        y = batch["x"] @ p["w"].astype(batch["x"].dtype)
        # Deliberate fp32 island: the activation is widened explicitly
        # (the MoE-router pattern), so the uncast param is exempt.
        r = batch["x"].astype(jnp.float32) @ p["w_island"]
        return (y * p["scale"].astype(y.dtype)).sum() + r.sum()

    assert audit_precision(
        good, vs, batch, compute_dtype=jnp.bfloat16, check_state=False
    ).findings == []


def test_fp32_island_widened_inside_scan_stays_exempt():
    """The widen-the-activation exemption must survive a scan boundary:
    ys stacked out of a scan body keep their widened_from provenance."""
    vs = variables(w=sds((512, 512), jnp.float32))
    batch = {"x": sds((4, 4, 512), jnp.bfloat16)}

    def step(vs, batch):
        def body(carry, x):
            return carry, x.astype(jnp.float32)

        _, wide = jax.lax.scan(body, jnp.zeros(()), batch["x"])
        return (wide.reshape(-1, 512) @ vs["params"]["w"]).sum()

    findings = audit_precision(
        step, vs, batch, compute_dtype=jnp.bfloat16, check_state=False
    ).findings
    assert findings == []


def test_provenance_threads_through_pjit():
    vs = variables(w=sds((512, 512), jnp.float32))
    batch = {"x": sds((4, 512), jnp.float32)}

    def bad(vs, batch):
        inner = jax.jit(lambda w, x: x @ w)
        return inner(vs["params"]["w"], batch["x"])

    findings = audit_precision(
        bad, vs, batch, compute_dtype=jnp.bfloat16, check_state=False
    ).findings
    assert rules_in(findings) == ["RKT405"]


def test_cond_narrowing_survives_identity_branch():
    """Provenance merges across lax.cond branches: a bf16 round trip in
    ONE branch (master erosion) must not hide behind an identity branch.
    The eroding branch is the FALSE one — first in the branches tuple —
    so a last-branch-wins walk would drop exactly this narrowing."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(jax.devices()[:8], ("d",))
    vs = variables(w=sds((8, 8), jnp.float32))
    batch = {"flag": sds((), jnp.bool_)}

    def bad(vs, batch):
        w = jax.lax.cond(
            batch["flag"],
            lambda w: w,                                          # true
            lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),  # false
            vs["params"]["w"],
        )
        return shard_map(
            lambda w: jax.lax.psum(w, "d"),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )(w)

    findings = audit_precision(bad, vs, batch, check_state=False).findings
    assert "RKT403" in rules_in(findings)


# -- suppression parity ------------------------------------------------------

def test_step_function_directive_suppresses_rule():
    batch = {"x": sds((4, 128), jnp.bfloat16)}

    def step(vs, batch):
        # rocketlint: disable=RKT402 — demonstration: bf16 softmax waived
        probs = jax.nn.softmax(batch["x"], axis=-1)
        return jnp.sum(batch["x"].astype(jnp.float32)
                       .astype(jnp.bfloat16)) + probs.sum()

    findings = audit_precision(step, {}, batch, check_state=False).findings
    # RKT402 suppressed; the unrelated churn finding survives.
    assert rules_in(findings) == ["RKT404"]


# -- RKT406: numerics budgets ------------------------------------------------

def prec_record(fraction=0.5, widen=10, narrow=12):
    return {"fp32_bytes_fraction": fraction, "widen_casts": widen,
            "narrow_casts": narrow, "cast_churn": 0}


def test_prec_budget_diff_gates_fraction_and_casts(tmp_path):
    budgets.write_budget(str(tmp_path), "t", prec_record())
    committed = budgets.load_budget(str(tmp_path), "t")

    def diff(measured):
        return budgets.diff_budget(
            "t", committed, measured, keys=budgets.PREC_GATED_KEYS,
            rule="RKT406", family="prec",
        )

    assert diff(prec_record(0.54, 11, 13)) == []          # within 10%
    findings = diff(prec_record(0.58, 10, 12))            # fraction +16%
    assert rules_in(findings) == ["RKT406"]
    assert "fp32_bytes_fraction" in findings[0].message
    assert findings[0].path == "<prec:t>"
    findings = diff(prec_record(0.5, 14, 12))             # widen +40%
    assert "widen_casts" in findings[0].message
    assert diff(prec_record(0.1, 2, 3)) == []             # shrinking is fine


def test_prec_budget_missing_names_prec_cli():
    findings = budgets.diff_budget(
        "absent", None, prec_record(), keys=budgets.PREC_GATED_KEYS,
        rule="RKT406", family="prec",
    )
    assert rules_in(findings) == ["RKT406"]
    assert "prec" in findings[0].message


# -- integration: the builtin targets ----------------------------------------

def test_tp_target_is_clean_and_records_numerics():
    report = run_prec_target(PREC_TARGETS["tp_2x4"])
    assert report.findings == [], [f.render() for f in report.findings]
    assert 0.0 < report.record["fp32_bytes_fraction"] < 1.0
    assert report.record["narrow_casts"] > 0
    assert report.record["cast_churn"] == 0


@pytest.mark.slow
def test_all_builtin_self_gate_targets_are_clean():
    """The repo's own train/eval steps under the bf16 convention: zero
    findings on every non-demo target (the in-process version of the
    CLI gate). Covers the unrolled, scan-layers and gelu/tied layer
    sets plus eval."""
    for name, target in PREC_TARGETS.items():
        if target.demo:
            continue
        report = run_prec_target(target)
        assert report.findings == [], (
            name + ":\n" + "\n".join(f.render() for f in report.findings)
        )
        assert report.record["float_value_bytes"] > 0


def test_badprec_target_reports_all_five_families():
    report = run_prec_target(PREC_TARGETS["badprec"])
    assert rules_in(report.findings) == [
        "RKT401", "RKT402", "RKT403", "RKT404", "RKT405"
    ]


def test_collect_dtype_flow_exposes_facts():
    """The fact stream is a public API: the precision tests in
    tests/test_precision.py assert on specific dots, so pin the shape."""
    vs = variables(w=sds((256, 64), jnp.float32))
    batch = {"x": sds((4, 256), jnp.bfloat16)}

    def step(vs, batch):
        return batch["x"] @ vs["params"]["w"].astype(jnp.bfloat16)

    flow, in_dtypes, _out_dtypes = collect_dtype_flow(step, vs, batch)
    assert len(flow.dots) == 1
    dot = flow.dots[0]
    assert dot.contract_size == 256
    assert dot.param_path == ("params", "w")
    assert in_dtypes[("params", "w")] == jnp.float32
    assert flow.narrow_casts == 1


# -- RKT403 certification: deliberate low-precision collectives --------------

def _lowprec_collective_parts():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(jax.devices()[:8], ("d",))
    vs = variables(w=sds((8, 8), jnp.float32))
    batch = {"x": sds((8, 8), jnp.float32)}

    def step(vs, batch):
        # Deliberate compressed-gradient-style collective: the fp32
        # master is narrowed to bf16 before crossing the mesh.
        w16 = vs["params"]["w"].astype(jnp.bfloat16)
        return shard_map(
            lambda w: jax.lax.psum(w, "d"),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )(w16)

    return step, vs, batch


def test_certified_collective_passes_and_counts():
    from rocket_tpu.analysis.prec_audit import certify_collectives

    step, vs, batch = _lowprec_collective_parts()
    certified = certify_collectives("params/w")(step)
    report = audit_precision(certified, vs, batch, check_state=False)
    assert report.findings == []
    assert report.record["certified_collectives"] == 1


def test_certification_kwarg_matches_decorator():
    step, vs, batch = _lowprec_collective_parts()
    report = audit_precision(
        step, vs, batch, check_state=False,
        certified_collectives=("params/*",),
    )
    assert report.findings == []


def test_uncertified_collective_still_fires_with_hint():
    step, vs, batch = _lowprec_collective_parts()
    findings = audit_precision(step, vs, batch, check_state=False).findings
    assert rules_in(findings) == ["RKT403"]
    assert "certify_collectives" in findings[0].message


def test_overlapping_certifications_both_count_as_used():
    """A specific glob listed alongside a broader overlapping one must
    not read as stale — every matching glob is credited."""
    from rocket_tpu.analysis.prec_audit import certify_collectives

    step, vs, batch = _lowprec_collective_parts()
    certified = certify_collectives("params/*", "params/w")(step)
    report = audit_precision(certified, vs, batch, check_state=False)
    assert report.findings == []


def test_stale_certification_is_a_finding():
    """A glob that certifies nothing must flag — the certification list
    is an exact audit trail, not a blanket suppression."""
    from rocket_tpu.analysis.prec_audit import certify_collectives

    step, vs, batch = _lowprec_collective_parts()
    certified = certify_collectives(
        "params/w", "params/no_such_param"
    )(step)
    findings = audit_precision(certified, vs, batch,
                               check_state=False).findings
    assert rules_in(findings) == ["RKT403"]
    assert "no_such_param" in findings[0].message
    assert "matched no" in findings[0].message
