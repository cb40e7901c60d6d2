"""trace_audit — abstract-eval a step function and audit its jaxpr.

The AST linter sees what the source *says*; this pass sees what a step
actually *traces to*. ``audit_step`` runs ``jax.make_jaxpr`` on the
function with example inputs (abstract evaluation — no FLOPs, no device
required) and checks the hot-path contracts the framework's fused step
relies on:

* **RKT201 donation-unused** — a donated argument's buffer matches no
  output, so XLA cannot alias it: the donation silently degrades to a
  copy (and jax warns at dispatch, once, where nobody looks).
* **RKT202 donation-duplicate** — one concrete buffer appears at two
  leaves of a donated argument: double-donation is undefined.
* **RKT203 host-callback-in-step** — a ``pure_callback`` / ``io_callback``
  / ``debug_callback`` / ``debug_print`` primitive traced into the step
  forces a device->host round trip every iteration.
* **RKT204 weak-type-input** — an input traced with ``weak_type=True``
  (a Python scalar leaked into the step signature): promotion drift plus
  a retrace the first time a strongly-typed value arrives instead.
* **RKT206 wide-dtype** — float64/complex128 anywhere in the jaxpr:
  silent 64-bit upcasts are unsupported-or-slow on TPU.

``audit_retraces`` (RKT205) checks a *set* of example inputs against a
compile budget: each distinct (structure, shape, dtype) signature is one
XLA compilation; shape-polymorphic callers (unpadded trailing batches,
growing decode lengths) blow the budget and spend the run recompiling.

All checks return :class:`~rocket_tpu.analysis.findings.Finding` lists —
empty means clean. Suppressions have rocketlint parity: a
``# rocketlint: disable=RKT2xx`` comment anywhere in the audited step
function's own source suppresses that rule for the audit (jaxpr findings
carry no source line, so a line-scoped directive inside the function is
read as scoping to the function). Runtime enforcement of the same
contracts (transfer guard + retrace counter) lives in
``runtime/context.py`` strict mode.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import numpy as np

from rocket_tpu.analysis.findings import Finding, parse_suppressions

__all__ = ["audit_step", "audit_retraces", "trace_signature"]


def _trace_path(label: str) -> str:
    return f"<trace:{label}>"


def _fn_suppressed_rules(fn: Callable, prefix: str = "RKT2") -> set:
    """Rule ids disabled by ``# rocketlint: disable=...`` directives in
    the step function's own source (rocketlint-parity for the jaxpr
    audit; the precision auditor reuses this with ``prefix="RKT4"``).
    Jaxpr findings have no line numbers, so a directive anywhere in the
    function body applies to the whole audit of that function — which is
    exactly why only EXPLICIT ids of the auditing family (``prefix``)
    count here: a line-scoped ``disable=all`` or an AST-rule id placed
    to silence rocketlint must not blank the entire jaxpr audit.
    Functions without retrievable source (C callables, REPL lambdas)
    suppress nothing."""
    try:
        source = inspect.getsource(inspect.unwrap(fn))
    except (OSError, TypeError):
        return set()
    sup = parse_suppressions(source)
    rules = set(sup.file_wide)
    for line_rules in sup.by_line.values():
        rules |= set(line_rules)
    return {r for r in rules if r.startswith(prefix)}


def _filter_suppressed(findings: list[Finding],
                       suppressed: Optional[set]) -> list[Finding]:
    if not suppressed:
        return findings
    return [f for f in findings if f.rule not in suppressed]


def _aval_key(aval) -> tuple:
    return (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "?")))


def _walk_jaxprs(jaxpr) -> Iterable[Any]:
    """Yield ``jaxpr`` and every jaxpr nested in its equations' params
    (pjit bodies, scan/while/cond branches, remat, custom_vjp...)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in _as_jaxprs(value):
                yield from _walk_jaxprs(sub)


def _as_jaxprs(value) -> Iterable[Any]:
    if hasattr(value, "eqns"):  # open Jaxpr
        yield value
    elif hasattr(value, "jaxpr"):  # ClosedJaxpr
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _as_jaxprs(item)


def _donated_leaf_ids(args: Sequence[Any], donate_argnums: Sequence[int],
                      label: str) -> list[Finding]:
    """RKT202: the same concrete buffer at two donated leaves."""
    findings = []
    seen: dict[int, str] = {}
    for argnum in donate_argnums:
        if argnum >= len(args):
            continue
        leaves = jax.tree_util.tree_leaves(args[argnum])
        for leaf in leaves:
            if not isinstance(leaf, (jax.Array, np.ndarray)):
                continue
            key = id(leaf)
            where = f"argument {argnum}"
            if key in seen:
                findings.append(Finding(
                    "RKT202", _trace_path(label), 0,
                    f"donation-duplicate: the same buffer object appears at "
                    f"two donated leaves ({seen[key]} and {where}); aliased "
                    "leaves in a donated pytree are donated twice",
                ))
            else:
                seen[key] = where
    return findings


def audit_step(fn: Callable, *example_args,
               donate_argnums: Sequence[int] = (),
               label: str = "step",
               static_argnums: Sequence[int] = (),
               **example_kwargs) -> list[Finding]:
    """Abstract-eval ``fn(*example_args, **example_kwargs)`` and audit the
    resulting jaxpr. Returns the findings; empty list means the step is
    clean. A ``# rocketlint: disable=RKT2xx`` comment inside ``fn``'s own
    source suppresses that rule for this audit (same syntax and audit
    trail as the AST linter)."""
    suppressed = _fn_suppressed_rules(fn)
    path = _trace_path(label)
    findings = list(_donated_leaf_ids(example_args, donate_argnums, label))

    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *example_args, **example_kwargs
    )
    jaxpr = closed.jaxpr

    # Map donated argnums to their flat invars (same flatten order as
    # make_jaxpr: args left-to-right, then kwargs).
    donated_invars = []
    offset = 0
    n_static = set(static_argnums)
    for argnum, arg in enumerate(example_args):
        if argnum in n_static:
            continue
        leaves = jax.tree_util.tree_leaves(arg)
        if argnum in donate_argnums:
            donated_invars.extend(jaxpr.invars[offset:offset + len(leaves)])
        offset += len(leaves)

    # RKT201: every donated input aval needs a distinct same-aval output.
    out_pool: dict[tuple, int] = {}
    for var in jaxpr.outvars:
        key = _aval_key(var.aval)
        out_pool[key] = out_pool.get(key, 0) + 1
    for var in donated_invars:
        key = _aval_key(var.aval)
        if out_pool.get(key, 0) > 0:
            out_pool[key] -= 1
        else:
            shape, dtype = key
            findings.append(Finding(
                "RKT201", path, 0,
                f"donation-unused: donated input {dtype}{list(shape)} "
                "matches no output buffer — XLA cannot alias it and the "
                "donation degrades to a copy (did the step stop returning "
                "this piece of state?)",
            ))

    # RKT203 / RKT206: scan every (nested) equation.
    callbacks = 0
    wide: set[str] = set()
    for sub in _walk_jaxprs(jaxpr):
        for eqn in sub.eqns:
            # jax.debug.print traces to its own ``debug_print`` primitive
            # (a host round trip like the *_callback family).
            if "callback" in eqn.primitive.name \
                    or eqn.primitive.name == "debug_print":
                callbacks += 1
                findings.append(Finding(
                    "RKT203", path, 0,
                    f"host-callback-in-step: primitive "
                    f"'{eqn.primitive.name}' traced into the step — a "
                    "device->host round trip every iteration (jax.debug."
                    "print / pure_callback left in the hot path?)",
                ))
            for var in eqn.outvars:
                dtype = getattr(var.aval, "dtype", None)
                if dtype is not None and dtype in (
                    np.dtype("float64"), np.dtype("complex128")
                ):
                    wide.add(str(dtype))
    for var in list(jaxpr.invars) + list(jaxpr.outvars):
        dtype = getattr(var.aval, "dtype", None)
        if dtype is not None and dtype in (
            np.dtype("float64"), np.dtype("complex128")
        ):
            wide.add(str(dtype))
    for dtype in sorted(wide):
        findings.append(Finding(
            "RKT206", path, 0,
            f"wide-dtype: {dtype} flows through the step — 64-bit math is "
            "unsupported-or-slow on TPU; cast explicitly or keep "
            "jax_enable_x64 off",
        ))

    # RKT204: weak-typed step inputs.
    for var in jaxpr.invars:
        if getattr(var.aval, "weak_type", False):
            shape, dtype = _aval_key(var.aval)
            findings.append(Finding(
                "RKT204", path, 0,
                f"weak-type-input: input {dtype}{list(shape)} traced with "
                "weak_type=True (a Python scalar in the step signature); "
                "pass jnp.asarray(x, dtype) so the signature is stable",
            ))
    return _filter_suppressed(findings, suppressed)


def trace_signature(tree) -> tuple:
    """Hashable (structure, shapes, dtypes) signature of an input pytree —
    two inputs with different signatures force two compilations."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def leaf_sig(leaf):
        if isinstance(leaf, (jax.Array, np.ndarray)):
            return (tuple(leaf.shape), str(leaf.dtype))
        return ("pyscalar", type(leaf).__name__)

    return (str(treedef), tuple(leaf_sig(leaf) for leaf in leaves))


def audit_retraces(example_inputs: Sequence[Any], max_traces: int = 1,
                   label: str = "step") -> list[Finding]:
    """RKT205: count distinct trace signatures over ``example_inputs``
    (e.g. the first epoch's batches) against a compile budget."""
    signatures: dict[tuple, int] = {}
    total = 0  # counted in the walk: example_inputs may be a one-shot iterator
    for tree in example_inputs:
        sig = trace_signature(tree)
        signatures[sig] = signatures.get(sig, 0) + 1
        total += 1
    if len(signatures) <= max_traces:
        return []
    shapes = "; ".join(
        f"{count}x {sig[1]}" for sig, count in list(signatures.items())[:4]
    )
    return [Finding(
        "RKT205", _trace_path(label), 0,
        f"retrace-excess: {len(signatures)} distinct trace signatures over "
        f"{total} example inputs (budget {max_traces}) — "
        f"every new shape/dtype recompiles the step. Signatures: {shapes}",
    )]
