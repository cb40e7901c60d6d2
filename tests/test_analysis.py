"""rocket_tpu.analysis: one true-positive + one clean-negative per rule,
suppression syntax, and the CLI contract.

AST rules (RKT1xx) run over the known-bad/known-good snippets in
``tests/fixtures/analysis/``; jaxpr rules (RKT2xx) run over small step
functions built inline (the auditor needs callables, not files).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.analysis import (
    audit_retraces,
    audit_step,
    lint_file,
    lint_paths,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def fixture(name):
    return os.path.join(FIXTURES, name)


def rules_in(findings):
    return sorted({f.rule for f in findings})


# -- AST rules: fixture pairs ------------------------------------------------

AST_CASES = [
    ("RKT101", "tracer_leak"),
    ("RKT102", "jit_side_effect"),
    ("RKT103", "sync_in_loop"),
    ("RKT104", "capsule_super"),
    ("RKT105", "handler_signature"),
    ("RKT106", "launch_host_sync"),
    ("RKT107", "fork_start_method"),
    ("RKT108", "string_dtype"),
    ("RKT109", "unlocked_mutation"),
    ("RKT110", "swallowed_interrupt"),
    ("RKT111", "undonated_jit_state"),
    ("RKT112", "unordered_iteration"),
    ("RKT113", "ambient_entropy"),
    ("RKT114", "nonatomic_artifact_write"),
]


@pytest.mark.parametrize("rule_id,slug", AST_CASES)
def test_ast_rule_fires_on_bad_fixture(rule_id, slug):
    findings = lint_file(fixture(f"bad_{slug}.py"))
    assert rule_id in rules_in(findings), (
        f"{rule_id} did not fire on bad_{slug}.py; got {rules_in(findings)}"
    )
    # Every bad fixture plants at least two violations of its rule.
    assert sum(f.rule == rule_id for f in findings) >= 2
    assert all(f.line > 0 for f in findings)


@pytest.mark.parametrize("rule_id,slug", AST_CASES)
def test_ast_rule_clean_on_good_fixture(rule_id, slug):
    findings = lint_file(fixture(f"good_{slug}.py"))
    assert rule_id not in rules_in(findings), (
        f"{rule_id} false-positive on good_{slug}.py: "
        f"{[f.render() for f in findings if f.rule == rule_id]}"
    )


def test_good_fixtures_fully_clean():
    """The good fixtures are clean under EVERY rule, not just their own."""
    for _, slug in AST_CASES:
        findings = lint_file(fixture(f"good_{slug}.py"))
        assert findings == [], [f.render() for f in findings]


def test_suppression_inline_and_file_wide():
    # suppressed.py plants RKT103 (x2, file-wide directive) and RKT101
    # (inline directive): everything must be silenced.
    findings = lint_file(fixture("suppressed.py"))
    assert findings == [], [f.render() for f in findings]
    # The same hazards WITHOUT directives do fire (bad fixtures prove the
    # rules are live, so the empty result above is the suppressions).
    assert "RKT103" in rules_in(lint_file(fixture("bad_sync_in_loop.py")))
    assert "RKT101" in rules_in(lint_file(fixture("bad_tracer_leak.py")))


def test_select_and_ignore_filter_rules():
    path = fixture("bad_tracer_leak.py")
    only = lint_file(path, select=["RKT101"])
    assert rules_in(only) == ["RKT101"]
    none = lint_file(path, ignore=["RKT101"])
    assert "RKT101" not in rules_in(none)


def test_lint_paths_walks_directories():
    findings = lint_paths([FIXTURES])
    hit_rules = rules_in(findings)
    for rule_id, _ in AST_CASES:
        assert rule_id in hit_rules


# -- jaxpr audit rules -------------------------------------------------------


def test_audit_donation_clean_and_unused():
    def good(state, batch):
        params = state["params"] - 0.1 * batch.mean(0)
        return {"params": params}, params.sum()

    state = {"params": jnp.ones((4,))}
    batch = jnp.ones((2, 4))
    assert audit_step(good, state, batch, donate_argnums=(0,)) == []

    def bad(state, batch):
        return batch.sum()  # donated state matches no output

    findings = audit_step(bad, state, batch, donate_argnums=(0,))
    assert rules_in(findings) == ["RKT201"]


def test_audit_duplicate_donation():
    shared = jnp.ones((4,))
    state = {"a": shared, "b": shared}  # one buffer, two donated leaves

    def step(state, batch):
        return (
            {"a": state["a"] - 1.0, "b": state["b"] - 1.0},
            batch.sum(),
        )

    findings = audit_step(step, state, jnp.ones((2, 4)), donate_argnums=(0,))
    assert "RKT202" in rules_in(findings)

    distinct = {"a": jnp.ones((4,)), "b": jnp.ones((4,))}
    assert audit_step(step, distinct, jnp.ones((2, 4)),
                      donate_argnums=(0,)) == []


def test_audit_host_callback():
    def chatty(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2

    findings = audit_step(chatty, jnp.ones((3,)))
    assert "RKT203" in rules_in(findings)

    def quiet(x):
        return x * 2

    assert audit_step(quiet, jnp.ones((3,))) == []


def test_audit_weak_type_input():
    findings = audit_step(lambda x, s: x * s, jnp.ones((3,)), 2.0)
    assert "RKT204" in rules_in(findings)
    strong = jnp.asarray(2.0, jnp.float32)
    assert audit_step(lambda x, s: x * s, jnp.ones((3,)), strong) == []


def test_audit_wide_dtype():
    with jax.enable_x64(True):
        findings = audit_step(lambda x: x * 2,
                              jnp.ones((3,), jnp.float64))
    assert "RKT206" in rules_in(findings)
    assert audit_step(lambda x: x * 2, jnp.ones((3,), jnp.float32)) == []


def test_audit_step_honors_rocketlint_suppressions():
    """Rocketlint parity: a ``# rocketlint: disable=RKT2xx`` directive in
    the step function's own source suppresses that rule for the audit —
    the same reviewable audit trail as the AST linter, instead of
    'fix the step or don't audit'."""
    def chatty_but_justified(x):  # rocketlint: disable=RKT203 — debug build
        jax.debug.print("x = {x}", x=x)
        return x * 2

    assert audit_step(chatty_but_justified, jnp.ones((3,))) == []

    def chatty(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2  # rocketlint: disable=RKT204 — wrong rule: no effect

    assert rules_in(audit_step(chatty, jnp.ones((3,)))) == ["RKT203"]

    def chatty_all(x):
        y = x.sum()  # rocketlint: disable=all — AST-scoped, NOT audit-wide
        jax.debug.print("y = {y}", y=y)
        return x * 2

    # Only explicit RKT2xx ids reach the jaxpr audit: a line-scoped
    # `disable=all` (or an RKT1xx id) must not blank the whole audit.
    assert rules_in(audit_step(chatty_all, jnp.ones((3,)))) == ["RKT203"]


def test_audit_retraces_budget():
    stable = [{"x": np.ones((8, 4), np.float32)} for _ in range(5)]
    assert audit_retraces(stable, max_traces=1) == []

    ragged = [
        {"x": np.ones((n, 4), np.float32)} for n in (8, 7, 6, 8, 5)
    ]
    findings = audit_retraces(ragged, max_traces=1)
    assert rules_in(findings) == ["RKT205"]
    # A declared-finite shape set within budget is fine.
    assert audit_retraces(ragged, max_traces=4) == []


# -- strict mode (runtime enforcement of the same contracts) -----------------


def test_strict_mode_retrace_counter():
    from rocket_tpu.runtime.context import StrictMode

    strict = StrictMode(max_retraces=1)
    strict.activate()
    try:
        fn = jax.jit(lambda x: x * 2)
        fn(jnp.ones((2,)))
        assert strict.note_retraces("step", fn) == 1
        fn(jnp.ones((3,)))  # second shape -> second compile
        with pytest.raises(RuntimeError, match="compiled 2 times"):
            strict.note_retraces("step", fn)
        assert strict.retrace_counts["step"] == 2
    finally:
        strict.deactivate()
    # Deactivated: note_retraces is a no-op.
    assert strict.note_retraces("step", fn) is None


def test_strict_mode_loop_guard_blocks_implicit_transfer():
    """Inside a strict Looper wave, an implicit H2D (numpy leaking into a
    compiled step past the warmup iteration) raises at the offending line."""
    from rocket_tpu.core.capsule import Capsule
    from rocket_tpu.core.loop import Looper
    from rocket_tpu.runtime.context import Runtime

    runtime = Runtime(strict=True)
    try:

        class Leaky(Capsule):
            def __init__(self):
                super().__init__()
                self.calls = 0

            def launch(self, attrs=None):
                self.calls += 1
                # Implicit H2D every iteration (jnp.asarray on host data).
                jnp.asarray(np.ones((4,), np.float32)) * self.calls

        leaky = Leaky()
        loop = Looper([leaky], repeats=3, progress=False, runtime=runtime)
        leaky.bind(runtime)
        loop.set(None)
        with pytest.raises(Exception, match="[Dd]isallowed"):
            loop.launch(None)
        # Warmup wave ran unguarded; the second wave tripped the guard.
        assert leaky.calls == 2
    finally:
        runtime.strict.deactivate()


def test_strict_mode_env_and_explicit_transfers():
    """Explicit device_put/device_get stay legal under the global guard."""
    from rocket_tpu.runtime.context import Runtime

    runtime = Runtime(strict=True)
    try:
        assert runtime.strict.enabled
        x = jax.device_put(np.ones((3,), np.float32))
        y = jax.jit(lambda a: a.sum())(x)
        assert float(np.asarray(jax.device_get(y))) == 3.0
    finally:
        runtime.strict.deactivate()
    off = Runtime()
    assert not off.strict.enabled


# -- CLI ---------------------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "rocket_tpu.analysis", *args],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_cli_exit_codes_and_output():
    bad = _run_cli(fixture("bad_tracer_leak.py"))
    assert bad.returncode == 1
    assert "RKT101" in bad.stdout

    good = _run_cli(fixture("good_tracer_leak.py"))
    assert good.returncode == 0
    assert good.stdout.strip() == ""


def test_cli_list_rules():
    out = _run_cli("--list-rules")
    assert out.returncode == 0
    for rule_id in ("RKT101", "RKT107", "RKT201", "RKT206"):
        assert rule_id in out.stdout
