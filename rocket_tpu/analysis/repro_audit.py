"""Static determinism / RNG-discipline audit (RKT901-906).

The repo's headline contracts are bitwise: eviction/resume in serve
replays identically, resilience resumes-not-restarts, the overlap
off-switch compiles the identical program. This auditor proves the two
preconditions those contracts stand on, before anything runs:

* **Key discipline** (RKT901): a prec_audit-style jaxpr walk threads
  PRNG-key *provenance* — every key value gets a structural identity
  built from how it was made (seed literal, fold_in chain, split slice)
  — through pjit/scan/while/cond, recording which random primitive
  consumed which key value. Two consumptions of one identity = reuse;
  a loop body consuming a loop-invariant key = the same draw every
  iteration.
* **Compiled determinism** (RKT902): the optimized HLO the other
  auditors already parse is scanned for nondeterministic ops — float
  scatter-add without ``unique_indices``, backend-default
  rng-bit-generator algorithms, known-nondeterministic custom-calls.
* **Resume identity** (RKT903): the train step is compiled fresh and
  compiled again from state round-tripped through
  ``runtime.checkpoint_io``; the canonicalized compiled-HLO
  fingerprints must match — the static form of "resume is
  bit-identical".
* **Wave-replay identity** (RKT904): the k-wave greedy decode program's
  per-wave scan body must fingerprint identically for every
  ``waves_per_dispatch`` — the engine's eviction-resume contract holds
  only because the per-wave math never reads k.
* **Replay sentinel** (RKT905): the tiny gpt2 sentinel step EXECUTES
  twice from identical donated state on CPU; params and the health word
  must match byte for byte. The one dynamic leg, cheap enough for every
  CI run.
* **Budget gate** (RKT906): program fingerprints and the RNG-consumer
  count are committed under ``tests/fixtures/budgets/repro/`` and
  diffed by the shared :func:`rocket_tpu.analysis.budgets.diff_budget`.

Pure abstract evaluation + XLA compilation everywhere except RKT905's
micro-execution. CLI: ``python -m rocket_tpu.analysis repro``.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import re
import tempfile
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rocket_tpu.analysis.findings import Finding
from rocket_tpu.analysis.rules.repro_rules import (
    check_key_reuse,
    check_nondet_hlo,
    check_replay_sentinel,
    check_resume_identity,
    check_wave_invariance,
)
from rocket_tpu.analysis.sched_audit import parse_hlo_module
from rocket_tpu.analysis.shard_audit import (
    _mesh_from_shape,
    aot_compile_step,
    resolve_placement,
)

__all__ = [
    "KeyFlow",
    "analyze_key_provenance",
    "scan_nondeterministic_hlo",
    "hlo_fingerprint",
    "jaxpr_fingerprint",
    "prove_wave_invariance",
    "run_replay_sentinel",
    "ReproAuditReport",
    "audit_train_repro",
    "ReproTarget",
    "REPRO_TARGETS",
    "run_repro_target",
]


# -- PRNG-key provenance over the jaxpr --------------------------------------

#: Primitives that CREATE a key value.
_KEY_CREATORS = frozenset({"random_seed", "random_wrap"})
#: Primitives that DERIVE a new key value from an existing one.
_KEY_DERIVERS = frozenset({"random_fold_in", "random_split"})
#: Primitives that CONSUME a key value to produce randomness. Consuming
#: the same value twice yields correlated (or identical) draws.
_KEY_CONSUMERS = frozenset({"random_bits", "threefry2x32", "random_gamma"})
#: Value-preserving ops on key arrays: the result holds (a view of) the
#: same key material, so identity threads through when the op's shape
#: parameters are static.
_KEY_TRANSPARENT = frozenset({
    "slice", "dynamic_slice", "squeeze", "reshape", "broadcast_in_dim",
    "transpose", "concatenate", "rev", "gather", "copy", "device_put",
})

_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _as_open(jaxpr_like):
    return jaxpr_like.jaxpr if hasattr(jaxpr_like, "jaxpr") else jaxpr_like


def _is_lit(var) -> bool:
    return hasattr(var, "val")


def _eqn_where(eqn) -> str:
    """``file:line (function)`` of the user code that emitted the eqn —
    the name_stack is empty under ``make_jaxpr``, so source provenance
    is what makes RKT901/902 sites recognizable and allow-listable."""
    from jax.extend import source_info_util

    try:
        return str(source_info_util.summarize(eqn.source_info))
    except Exception:
        return ""


def _is_key_aval(aval) -> bool:
    try:
        return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


@dataclass(frozen=True)
class _KeyProv:
    """Provenance of one key value: a structural identity (two values
    with equal ``kid`` are provably the same key material), a human
    origin for messages, and whether the value is provably identical on
    every iteration of the loop body it currently lives in."""

    kid: tuple
    origin: str
    loop_fixed: bool = False


@dataclass
class KeyFlow:
    """Facts the RKT901 checks consume."""

    #: key identity -> consumption sites (primitive@scope strings)
    consumptions: dict = field(default_factory=dict)
    #: {(site, origin)} loop-body consumptions of loop-invariant keys
    unfolded: set = field(default_factory=set)
    n_creations: int = 0
    n_derivations: int = 0
    #: every key-consuming primitive, tracked or not (the budget metric:
    #: the step's RNG surface)
    n_consumers: int = 0


class _KeyWalker:
    """Recursive jaxpr walk threading key provenance + loop variance."""

    def __init__(self) -> None:
        self.flow = KeyFlow()
        self._uniq = itertools.count()

    def _fresh(self, why: str) -> tuple:
        # Unprovable value: a unique identity that can never collide, so
        # it can never false-positive a reuse.
        return ("uniq", next(self._uniq), why)

    @staticmethod
    def _read(env, var) -> Optional[_KeyProv]:
        if _is_lit(var):
            return None
        return env.get(var)

    @staticmethod
    def _varies(varying, var) -> bool:
        return (not _is_lit(var)) and var in varying

    @staticmethod
    def _site(eqn) -> str:
        return f"{eqn.primitive.name}@{_eqn_where(eqn)}"

    @staticmethod
    def _static_id(var) -> tuple:
        """Identity of a non-key data operand (fold_in data): literals by
        value, jaxpr vars by their trace-stable count — the same var
        folded into the same key twice provably yields the same key."""
        if _is_lit(var):
            return ("lit", str(np.asarray(var.val).tolist()))
        return ("var", getattr(var, "count", id(var)))

    def _consume(self, prov: Optional[_KeyProv], eqn, in_loop: bool) -> None:
        self.flow.n_consumers += 1
        if prov is None:
            return
        site = self._site(eqn)
        self.flow.consumptions.setdefault(prov.kid, []).append(site)
        if in_loop and prov.loop_fixed:
            self.flow.unfolded.add((site, prov.origin))

    # -- the walk ----------------------------------------------------------

    def walk(self, jaxpr, env, varying, in_loop: bool) -> list:
        """Returns the provenance of ``jaxpr.outvars`` (None per non-key
        slot). ``env`` maps Var -> Optional[_KeyProv]; ``varying`` is the
        set of Vars not provably loop-invariant in the enclosing loop."""
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_provs = [self._read(env, v) for v in eqn.invars]
            in_vary = [self._varies(varying, v) for v in eqn.invars]

            if name == "scan":
                self._walk_scan(eqn, env, varying, in_provs, in_vary)
            elif name == "while":
                self._walk_while(eqn, env, varying, in_provs, in_vary)
            elif name == "cond":
                self._walk_cond(eqn, env, varying, in_provs, in_vary,
                                in_loop)
            else:
                sub_like = next(
                    (eqn.params[k] for k in _CALL_JAXPR_KEYS
                     if hasattr(eqn.params.get(k), "eqns")
                     or hasattr(eqn.params.get(k), "jaxpr")),
                    None,
                )
                if sub_like is not None:
                    self._walk_call(eqn, env, varying, in_provs, in_vary,
                                    in_loop, _as_open(sub_like))
                else:
                    self._walk_leaf(eqn, env, in_provs, in_vary, in_loop)

            if any(in_vary):
                varying.update(
                    v for v in eqn.outvars if not _is_lit(v)
                )
        return [self._read(env, v) for v in jaxpr.outvars]

    def _walk_call(self, eqn, env, varying, in_provs, in_vary, in_loop,
                   sub) -> None:
        if len(sub.invars) == len(eqn.invars):
            sub_env = {
                v: p for v, p in zip(sub.invars, in_provs) if p is not None
            }
            sub_vary = {
                v for v, vy in zip(sub.invars, in_vary) if vy
            }
        else:
            # Unknown calling convention: identities do not thread, but
            # the inner consumers still count and reuse WITHIN the body
            # is still caught.
            sub_env, sub_vary = {}, set()
        out_provs = self.walk(sub, sub_env, sub_vary, in_loop)
        for var, prov in zip(eqn.outvars, out_provs):
            if prov is not None:
                env[var] = prov

    def _walk_scan(self, eqn, env, varying, in_provs, in_vary) -> None:
        sub = _as_open(eqn.params["jaxpr"])
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        sub_env, sub_vary = {}, set()
        for i, var in enumerate(sub.invars):
            prov = in_provs[i] if i < len(in_provs) else None
            if i < nc:
                # A closure const holds the same value every iteration:
                # consuming it in the body without folding in the carry
                # is the unfolded-loop-key bug.
                if prov is not None and not in_vary[i]:
                    prov = _KeyProv(prov.kid, prov.origin, loop_fixed=True)
            else:
                sub_vary.add(var)
            if prov is not None:
                sub_env[var] = prov
        before = {
            kid: len(sites)
            for kid, sites in self.flow.consumptions.items()
        }
        out_provs = self.walk(sub, sub_env, sub_vary, in_loop=True)
        self._carry_unchanged(
            in_provs[nc:nc + ncar], out_provs[:ncar], before
        )
        for i, var in enumerate(eqn.outvars):
            prov = out_provs[i] if i < len(out_provs) else None
            if prov is None:
                continue
            if i >= ncar:
                # Stacked ys: per-iteration values, each distinct.
                prov = _KeyProv(
                    self._fresh("stacked-ys"), prov.origin, False
                )
            else:
                prov = _KeyProv(prov.kid, prov.origin, False)
            env[var] = prov

    def _walk_while(self, eqn, env, varying, in_provs, in_vary) -> None:
        cond_n = int(eqn.params.get("cond_nconsts", 0))
        body_n = int(eqn.params.get("body_nconsts", 0))
        cond = _as_open(eqn.params["cond_jaxpr"])
        body = _as_open(eqn.params["body_jaxpr"])
        carry_provs = in_provs[cond_n + body_n:]
        n_carry = len(carry_provs)

        def loop_env(invars, const_provs, const_vary):
            sub_env, sub_vary = {}, set()
            provs = list(const_provs) + list(carry_provs)
            for i, var in enumerate(invars):
                prov = provs[i] if i < len(provs) else None
                if i < len(const_provs):
                    if prov is not None and not const_vary[i]:
                        prov = _KeyProv(prov.kid, prov.origin, True)
                else:
                    sub_vary.add(var)
                if prov is not None:
                    sub_env[var] = prov
            return sub_env, sub_vary

        c_env, c_vary = loop_env(
            cond.invars, in_provs[:cond_n], in_vary[:cond_n]
        )
        self.walk(cond, c_env, c_vary, in_loop=True)
        b_env, b_vary = loop_env(
            body.invars, in_provs[cond_n:cond_n + body_n],
            in_vary[cond_n:cond_n + body_n],
        )
        before = {
            kid: len(sites)
            for kid, sites in self.flow.consumptions.items()
        }
        out_provs = self.walk(body, b_env, b_vary, in_loop=True)
        self._carry_unchanged(carry_provs, out_provs[:n_carry], before)
        for var, prov in zip(eqn.outvars, out_provs):
            if prov is not None:
                env[var] = _KeyProv(prov.kid, prov.origin, False)

    def _carry_unchanged(self, in_carry, out_carry, before) -> None:
        """A key carried through the loop UNCHANGED while the body
        consumed it: the same value feeds every iteration — the unfolded
        bug in carry clothing."""
        for inp, outp in zip(in_carry, out_carry):
            if inp is None or outp is None or inp.kid != outp.kid:
                continue
            sites = self.flow.consumptions.get(inp.kid, [])
            if len(sites) > before.get(inp.kid, 0):
                self.flow.unfolded.add(
                    (sites[-1], inp.origin + " (carried unchanged)")
                )

    def _walk_cond(self, eqn, env, varying, in_provs, in_vary,
                   in_loop) -> None:
        # Only ONE branch executes: per-kid consumption is the MAX over
        # branches, not the sum — summing would flag cond(p, normal,
        # uniform, key) as reuse.
        base = {k: list(v) for k, v in self.flow.consumptions.items()}
        base_consumers = self.flow.n_consumers
        deltas, consumer_deltas = [], []
        merged = None
        for branch in eqn.params["branches"]:
            sub = _as_open(branch)
            self.flow.consumptions = {k: list(v) for k, v in base.items()}
            self.flow.n_consumers = base_consumers
            sub_env = {
                v: p for v, p in zip(sub.invars, in_provs[1:])
                if p is not None
            }
            sub_vary = {
                v for v, vy in zip(sub.invars, in_vary[1:]) if vy
            }
            out = self.walk(sub, sub_env, sub_vary, in_loop)
            delta = {}
            for kid, sites in self.flow.consumptions.items():
                extra = sites[len(base.get(kid, ())):]
                if extra:
                    delta[kid] = extra
            deltas.append(delta)
            consumer_deltas.append(self.flow.n_consumers - base_consumers)
            if merged is None:
                merged = list(out)
            else:
                merged = [
                    a if (a is not None and b is not None
                          and a.kid == b.kid) else None
                    for a, b in zip(merged, out)
                ]
        self.flow.consumptions = base
        self.flow.n_consumers = base_consumers + (
            max(consumer_deltas) if consumer_deltas else 0
        )
        for kid in sorted({k for d in deltas for k in d}, key=str):
            best = max((d.get(kid, []) for d in deltas), key=len)
            self.flow.consumptions.setdefault(kid, []).extend(best)
        for var, prov in zip(eqn.outvars, merged or ()):
            if prov is not None:
                env[var] = prov

    def _walk_leaf(self, eqn, env, in_provs, in_vary, in_loop) -> None:
        name = eqn.primitive.name
        fixed_here = in_loop and not any(in_vary)

        if name in _KEY_CONSUMERS:
            self._consume(in_provs[0], eqn, in_loop)
            return

        if name == "random_seed":
            self.flow.n_creations += 1
            kid = ("seed", self._static_id(eqn.invars[0]))
            env[eqn.outvars[0]] = _KeyProv(
                kid, f"seed {self._site(eqn)}", loop_fixed=fixed_here
            )
            return
        if name == "random_wrap":
            self.flow.n_creations += 1
            src = in_provs[0]
            if src is not None:
                kid, origin = ("via", src.kid, "wrap"), src.origin
                fixed = src.loop_fixed
            else:
                kid = self._fresh("wrap")
                origin, fixed = f"wrap {self._site(eqn)}", fixed_here
            env[eqn.outvars[0]] = _KeyProv(kid, origin, fixed)
            return

        if name == "random_fold_in":
            self.flow.n_derivations += 1
            src = in_provs[0]
            src_kid = src.kid if src is not None else self._fresh("fold-src")
            data = eqn.invars[1]
            if in_vary[1] if len(in_vary) > 1 else False:
                # Folding with a loop-varying value: a genuinely new key
                # every iteration.
                kid = self._fresh("fold-varying")
                fixed = False
            else:
                kid = ("fold", src_kid, self._static_id(data))
                fixed = (src.loop_fixed if src is not None else fixed_here)
            origin = src.origin if src is not None else self._site(eqn)
            env[eqn.outvars[0]] = _KeyProv(kid, origin, fixed)
            return
        if name == "random_split":
            self.flow.n_derivations += 1
            src = in_provs[0]
            src_kid = src.kid if src is not None else self._fresh("split-src")
            shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()) or ())
            kid = ("split", src_kid, shape)
            fixed = src.loop_fixed if src is not None else False
            origin = src.origin if src is not None else self._site(eqn)
            env[eqn.outvars[0]] = _KeyProv(kid, origin, fixed)
            return

        src = in_provs[0] if in_provs else None
        if name in _KEY_TRANSPARENT and src is not None:
            others_static = all(
                _is_lit(v) for v in eqn.invars[1:]
            )
            if others_static:
                params = repr(sorted(
                    (k, v) for k, v in eqn.params.items()
                    if isinstance(v, (int, bool, str, tuple, type(None)))
                ))
                kid = ("via", src.kid, name, params)
            else:
                # Dynamic index/operand: cannot prove which element —
                # never collide, never false-positive.
                kid = self._fresh(name)
            env[eqn.outvars[0]] = _KeyProv(kid, src.origin, src.loop_fixed)
            return

        # Any other primitive producing a key-typed value (select_n,
        # pad, ...): track it but give it an uncollidable identity.
        tracked = next((p for p in in_provs if p is not None), None)
        for var in eqn.outvars:
            if _is_key_aval(var.aval):
                env[var] = _KeyProv(
                    self._fresh(name),
                    tracked.origin if tracked else self._site(eqn),
                    tracked.loop_fixed if tracked else False,
                )


def analyze_key_provenance(closed) -> KeyFlow:
    """Walk a ``ClosedJaxpr`` (``jax.make_jaxpr`` output) and return the
    key-provenance facts :func:`check_key_reuse` consumes."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    walker = _KeyWalker()
    env = {}
    for i, var in enumerate(jaxpr.invars):
        if _is_key_aval(var.aval):
            env[var] = _KeyProv(("in", i), f"input[{i}]")
    for var in getattr(jaxpr, "constvars", ()):
        if _is_key_aval(var.aval):
            env[var] = _KeyProv(
                ("const", getattr(var, "count", 0)), "closure const"
            )
    walker.walk(jaxpr, env, set(), in_loop=False)
    return walker.flow


# -- RKT902: nondeterministic ops in the optimized HLO -----------------------

#: custom_call_target substrings with documented nondeterministic
#: accumulation order (GPU autotuned kernels; none appear in the CPU/TPU
#: modules the audit compiles, but the HLO scan is backend-agnostic).
_NONDET_CUSTOM_CALLS = ("__cudnn", "cub_segmented", "cub::DeviceSegmented")

_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


def _is_float_hlo(dtype: str) -> bool:
    return dtype.startswith(("f", "bf"))


def scan_nondeterministic_hlo(hlo_text: str) -> list[tuple]:
    """``(kind, name, detail)`` triples for every nondeterministic op in
    the module — every computation, not just ENTRY (scatters live inside
    fusions)."""
    _entry, computations = parse_hlo_module(hlo_text)
    out = []
    for comp_name in sorted(computations):
        for instr in computations[comp_name]:
            op = instr.opcode
            if op == "scatter":
                if "unique_indices=true" in instr.attrs:
                    continue
                if not _is_float_hlo(instr.dtype):
                    continue
                combiner_adds = any(
                    ci.opcode == "add" and _is_float_hlo(ci.dtype)
                    for called in instr.called
                    for ci in computations.get(called, ())
                )
                if not combiner_adds:
                    continue
                out.append((
                    "scatter", instr.name, instr.where or comp_name
                ))
            elif op == "rng-bit-generator":
                if "algorithm=rng_default" in instr.attrs:
                    out.append(("rng", instr.name, "algorithm=rng_default"))
            elif op == "rng":
                out.append((
                    "rng", instr.name, "legacy rng op (backend-defined)"
                ))
            elif op == "custom-call":
                m = _CUSTOM_CALL_TARGET_RE.search(instr.attrs)
                target = m.group(1) if m else ""
                if any(p in target for p in _NONDET_CUSTOM_CALLS):
                    out.append(("custom-call", instr.name, target))
    return out


#: Scatter primitives whose combiner accumulates (order-sensitive over
#: duplicate indices). Plain ``scatter`` overwrites — last write wins is
#: still order-dependent, but JAX only emits it for indexed *assignment*
#: where duplicate behavior is documented as unspecified, not silently
#: nondeterministic accumulation — so only the accumulating forms gate.
_NONDET_SCATTER_PRIMS = frozenset({"scatter-add", "scatter_add"})


def scan_nondet_jaxpr(closed, _scope: str = "") -> list[tuple]:
    """Jaxpr-level leg of the RKT902 scan: float accumulating scatters
    with ``unique_indices=False``, found *before* backend lowering — the
    CPU scatter-expander rewrites them into ``while`` loops, so the
    optimized-HLO scan alone would go blind exactly where CI runs."""
    jaxpr = _as_open(closed)
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _NONDET_SCATTER_PRIMS:
            unique = bool(eqn.params.get("unique_indices", False))
            dtype = eqn.outvars[0].aval.dtype
            if not unique and jnp.issubdtype(dtype, jnp.floating):
                where = _eqn_where(eqn) or _scope
                out.append((
                    "scatter", f"{name}@{where}" if where else name,
                    "unique_indices=False (traced program)",
                ))
            continue
        for key, sub in eqn.params.items() if hasattr(eqn, "params") else ():
            if key == "branches":
                for branch in sub:
                    out.extend(scan_nondet_jaxpr(branch, _scope))
            elif hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                out.extend(scan_nondet_jaxpr(sub, _scope))
    return out


# -- canonical fingerprints --------------------------------------------------

_FP_IDENT_RE = re.compile(r"%[\w\.\-]+")
_FP_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
_FP_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def hlo_fingerprint(hlo_text: str) -> str:
    """Canonicalized hash of a compiled module: the header line and
    ``metadata={...}`` blobs (source paths, op names) are stripped and
    every ``%identifier`` is renamed in first-occurrence order, so two
    compiles of the same program fingerprint identically even when XLA
    numbers values differently."""
    text = "\n".join(
        line for line in hlo_text.splitlines()
        if not line.startswith("HloModule")
    )
    text = _FP_METADATA_RE.sub("", text)
    names: dict[str, str] = {}

    def rename(match):
        return names.setdefault(match.group(0), f"%v{len(names)}")

    text = _FP_IDENT_RE.sub(rename, text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def jaxpr_fingerprint(jaxpr_like) -> str:
    """Canonicalized hash of a (sub-)jaxpr's pretty-print — the
    PROGRAM identity the budget gate commits: stable across machines for
    one jax version, unlike compiled-HLO text (which the record keeps as
    ungated context)."""
    text = str(jaxpr_like)
    text = _FP_ADDR_RE.sub("0x0", text)
    text = re.sub(r"\s+", " ", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# -- RKT903: resume identity through the checkpoint path ---------------------


def _concrete_zeros(tree):
    """Concrete zero arrays matching the abstract inputs' shardings —
    program IDENTITY depends on shapes/dtypes/shardings, not values, so
    zeros prove the restore path as well as a real checkpoint."""
    return jax.tree.map(
        lambda leaf: jax.device_put(
            np.zeros(leaf.shape, leaf.dtype),
            getattr(leaf, "sharding", None),
        ),
        tree,
    )


def _restored_fingerprint(step_fn, abs_variables, abs_batch, *, mesh,
                          donate, label):
    """Compile the step from state round-tripped through
    ``checkpoint_io.save_pytree``/``load_pytree``; returns
    ``(fingerprint | None, findings)``."""
    from rocket_tpu.runtime.checkpoint_io import load_pytree, save_pytree

    extended = [
        str(path) for path, leaf in
        jax.tree_util.tree_flatten_with_path(abs_variables)[0]
        if jnp.issubdtype(leaf.dtype, jax.dtypes.extended)
    ]
    if extended:
        return None, [Finding(
            "RKT903", f"<repro:{label}>", 0,
            f"resume-identity: state holds extended-dtype (PRNG key) "
            f"leaves {extended[:3]} — checkpoint_io cannot restore them, "
            "so resume-not-restart is unprovable; keep counter-based RNG "
            "state (fold_in(key, step)) instead of key-typed state",
        )]
    zeros = _concrete_zeros(abs_variables)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "ckpt")
        save_pytree(ckpt, zeros)
        restored = load_pytree(ckpt, template=zeros)
    compiled, findings = aot_compile_step(
        step_fn, restored, abs_batch, mesh=mesh,
        donate_argnums=donate, label=label,
    )
    if compiled is None:
        return None, findings
    return hlo_fingerprint(compiled.as_text()), findings


# -- RKT904: wave-replay identity --------------------------------------------


def _find_scan_body(jaxpr, length: int, _depth: int = 0):
    """The sub-jaxpr of the scan of ``length`` — top level first (the
    wave scan sits at the decode program's top level; model-internal
    scans live deeper), then recursing."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" \
                and int(eqn.params.get("length") or -1) == length:
            return eqn.params["jaxpr"]
    if _depth >= 4:
        return None
    for eqn in jaxpr.eqns:
        for key in _CALL_JAXPR_KEYS + ("body_jaxpr",):
            sub = eqn.params.get(key) if hasattr(eqn, "params") else None
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                found = _find_scan_body(_as_open(sub), length, _depth + 1)
                if found is not None:
                    return found
    return None


def prove_wave_invariance(model, serve_config, *, waves_list=(1, 2, 4),
                          label: str = "serve"):
    """Trace the decode program at several ``waves_per_dispatch`` values
    and fingerprint the per-wave scan BODY of each; returns
    ``(fingerprints {k: fp}, traced {k: ClosedJaxpr}, decode_args)``.
    The decode signature is k-invariant, so one abstract input set
    serves every k."""
    from rocket_tpu.serve.engine import abstract_wave_inputs, build_decode_wave

    spec, mb, _num_blocks, _waves = serve_config.resolve(model.config)
    decode_args, _prefill_args = abstract_wave_inputs(
        model, spec, max_slots=serve_config.max_slots,
        max_blocks_per_seq=mb, prefill_chunk=serve_config.prefill_chunk,
    )
    fingerprints, traced = {}, {}
    for k in waves_list:
        closed = jax.make_jaxpr(build_decode_wave(model, waves=k))(
            *decode_args
        )
        body = _find_scan_body(closed.jaxpr, int(k))
        fingerprints[int(k)] = jaxpr_fingerprint(
            body if body is not None else closed
        )
        traced[int(k)] = closed
    return fingerprints, traced, decode_args


# -- RKT905: the executed replay sentinel ------------------------------------


def _sentinel_parts():
    """The tiny gpt2-shaped sentinel step (shard_audit's ``_lm_config``)
    with the health word folded into the outputs, so the bitwise-replay
    proof covers exactly what production monitors: new params, loss,
    grad norm, param norm and the ok flags, all from one value_and_grad
    pass. Returns ``(step_fn, variables_shapes, batch_shapes)``."""
    import optax

    from rocket_tpu.analysis.shard_audit import _lm_config
    from rocket_tpu.models.transformer import TransformerLM
    from rocket_tpu.obs.health import branch_sumsq, step_flags

    model = TransformerLM(_lm_config())

    def loss_fn(variables, batch):
        out, _state = model.apply(variables, dict(batch), mode="train")
        logits = out["logits"][:, :-1].astype(jnp.float32)
        targets = out["tokens"][:, 1:]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    def sentinel_step(variables, batch):
        loss, grads = jax.value_and_grad(loss_fn)(variables, batch)
        step_ok, loss_ok, _grad_branch_ok, grad_norm = step_flags(
            loss, grads
        )
        params = jax.tree.map(
            lambda p, g: (p - 1e-3 * g).astype(p.dtype),
            variables["params"], grads["params"],
        )
        param_norm = jnp.sqrt(jnp.sum(branch_sumsq(params)))
        word = jnp.stack([
            jnp.asarray(loss, jnp.float32),
            grad_norm,
            param_norm,
            jnp.asarray(step_ok, jnp.float32),
            jnp.asarray(loss_ok, jnp.float32),
        ])
        return {"params": params, "state": variables["state"]}, word

    variables = jax.eval_shape(model.init, jax.random.key(0))
    batch = {
        "tokens": jax.ShapeDtypeStruct(
            (4, model.config.max_seq_len), jnp.int32
        )
    }
    return sentinel_step, variables, batch


def _leaf_seed(path_str: str) -> int:
    return int(hashlib.sha256(path_str.encode()).hexdigest()[:8], 16) \
        % (2**31 - 1)


def _materialize(tree, int_leaf):
    """Deterministic concrete arrays for abstract ``tree``: per-leaf
    seeded normals for floats (zeros would be degenerate — dead gradient
    paths prove nothing), ``int_leaf(rs, leaf)`` for ints."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        rs = np.random.RandomState(_leaf_seed(jax.tree_util.keystr(path)))
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            arr = (rs.standard_normal(leaf.shape) * 0.02).astype(leaf.dtype)
        else:
            arr = int_leaf(rs, leaf)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def run_replay_sentinel(label: str = "gpt2_sentinel"):
    """Execute the sentinel step twice from identical donated state and
    byte-compare every output leaf; returns ``(mismatches, n_leaves)``."""
    step_fn, var_shapes, batch_shapes = _sentinel_parts()
    host_vars = _materialize(
        var_shapes, lambda rs, leaf: np.zeros(leaf.shape, leaf.dtype)
    )
    host_batch = _materialize(
        batch_shapes,
        lambda rs, leaf: rs.randint(0, 256, size=leaf.shape).astype(
            leaf.dtype
        ),
    )
    run = jax.jit(step_fn, donate_argnums=(0,))
    outs = []
    with warnings.catch_warnings():
        # CPU backends may decline donation with a warning; the replay
        # proof holds either way.
        warnings.simplefilter("ignore")
        for _ in range(2):
            variables = jax.tree.map(
                lambda a: jax.device_put(np.copy(a)), host_vars
            )
            batch = jax.tree.map(jax.device_put, host_batch)
            outs.append(run(variables, batch))
        outs = [jax.device_get(out) for out in outs]
    flat1 = jax.tree_util.tree_flatten_with_path(outs[0])[0]
    flat2 = jax.tree_util.tree_flatten_with_path(outs[1])[0]
    mismatches = [
        jax.tree_util.keystr(p1)
        for (p1, l1), (_p2, l2) in zip(flat1, flat2)
        if np.asarray(l1).tobytes() != np.asarray(l2).tobytes()
    ]
    return mismatches, len(flat1)


# -- the audits --------------------------------------------------------------


@dataclass
class ReproAuditReport:
    label: str
    findings: list = field(default_factory=list)
    record: dict = field(default_factory=dict)
    key_flow: Optional[KeyFlow] = None

    @property
    def clean(self) -> bool:
        return not self.findings


def _key_record(flow: KeyFlow) -> dict:
    return {
        "random_consumers": int(flow.n_consumers),
        "key_creations": int(flow.n_creations),
        "key_derivations": int(flow.n_derivations),
    }


def audit_train_repro(
    step_fn: Callable,
    variables,
    batch,
    *,
    rules,
    mesh_shape: Mapping[str, int],
    donate_argnums: Sequence[int] = (),
    scatter_allow: Sequence[str] = (),
    label: str = "step",
) -> ReproAuditReport:
    """RKT901 + RKT902 + RKT903 over one train step on its fake mesh.

    Placement findings (RKT30x) are the SPMD audit's job and are not
    re-reported here; a failed AOT compile surfaces as RKT303 via the
    shared harness so the trace-level checks still run."""
    report = ReproAuditReport(label=label)
    findings: list[Finding] = []
    mesh = _mesh_from_shape(dict(mesh_shape))
    if rules is None:
        def rules(path, leaf):  # replicate everything
            return None
    abs_variables, abs_batch, _specs, _placement = resolve_placement(
        variables, batch, rules=rules, mesh=mesh, label=label,
    )
    with mesh:
        closed = jax.make_jaxpr(step_fn)(abs_variables, abs_batch)
    flow = analyze_key_provenance(closed)
    report.key_flow = flow
    findings.extend(check_key_reuse(
        flow.consumptions, flow.unfolded, label=label
    ))

    fresh_fp = None
    nondet: list[tuple] = list(scan_nondet_jaxpr(closed))
    compiled, compile_findings = aot_compile_step(
        step_fn, abs_variables, abs_batch, mesh=mesh,
        donate_argnums=donate_argnums, label=label,
    )
    findings.extend(compile_findings)
    if compiled is not None:
        hlo = compiled.as_text()
        nondet.extend(scan_nondeterministic_hlo(hlo))
    findings.extend(check_nondet_hlo(
        nondet, scatter_allow=scatter_allow, label=label
    ))
    if compiled is not None:
        fresh_fp = hlo_fingerprint(hlo)
        restored_fp, restore_findings = _restored_fingerprint(
            step_fn, abs_variables, abs_batch, mesh=mesh,
            donate=donate_argnums, label=label,
        )
        findings.extend(restore_findings)
        findings.extend(check_resume_identity(
            fresh_fp, restored_fp, label=label
        ))

    report.record = {
        "program_fingerprint": jaxpr_fingerprint(closed),
        "compiled_fingerprint": fresh_fp or "",
        "nondet_ops": len(nondet),
        **_key_record(flow),
    }
    report.findings = findings
    return report


def audit_serve_repro(
    model,
    serve_config,
    *,
    scatter_allow: Sequence[str] = (),
    waves_list: Sequence[int] = (1, 2, 4),
    label: str = "serve",
) -> ReproAuditReport:
    """RKT904 (per-wave body invariant to k) + RKT901/902 on the decode
    program the engine actually dispatches."""
    report = ReproAuditReport(label=label)
    findings: list[Finding] = []
    fingerprints, traced, decode_args = prove_wave_invariance(
        model, serve_config, waves_list=waves_list, label=label,
    )
    findings.extend(check_wave_invariance(fingerprints, label=label))

    _spec, _mb, _nb, waves = serve_config.resolve(model.config)
    probe_k = int(waves) if int(waves) in traced else max(traced)
    flow = analyze_key_provenance(traced[probe_k])
    report.key_flow = flow
    findings.extend(check_key_reuse(
        flow.consumptions, flow.unfolded, label=label
    ))

    from rocket_tpu.serve import engine as engine_mod

    donate = getattr(engine_mod, "DECODE_DONATE", (1, 2))
    compiled_fp = ""
    try:
        compiled = jax.jit(
            engine_mod.build_decode_wave(model, waves=probe_k),
            donate_argnums=tuple(donate),
        ).lower(*decode_args).compile()
    except (ValueError, RuntimeError) as exc:
        findings.append(Finding(
            "RKT904", f"<repro:{label}>", 0,
            "wave-replay-identity: the decode program failed to compile, "
            f"so the replay proof could not complete: "
            f"{str(exc).splitlines()[0][:200]}",
        ))
    else:
        hlo = compiled.as_text()
        nondet = list(scan_nondet_jaxpr(traced[probe_k]))
        nondet.extend(scan_nondeterministic_hlo(hlo))
        findings.extend(check_nondet_hlo(
            nondet, scatter_allow=scatter_allow, label=label,
        ))
        compiled_fp = hlo_fingerprint(hlo)

    report.record = {
        # THE gated identity: the per-wave body, invariant to k by
        # construction (RKT904 is what guarantees the invariance).
        "program_fingerprint": fingerprints[min(fingerprints)],
        "compiled_fingerprint": compiled_fp,
        "waves_checked": sorted(fingerprints),
        **_key_record(flow),
    }
    report.findings = findings
    return report


def audit_sentinel_repro(label: str = "gpt2_sentinel") -> ReproAuditReport:
    """RKT905: the executed bitwise-replay proof, plus the static key
    walk and program fingerprint of the sentinel step."""
    report = ReproAuditReport(label=label)
    findings: list[Finding] = []
    step_fn, var_shapes, batch_shapes = _sentinel_parts()
    closed = jax.make_jaxpr(step_fn)(var_shapes, batch_shapes)
    flow = analyze_key_provenance(closed)
    report.key_flow = flow
    findings.extend(check_key_reuse(
        flow.consumptions, flow.unfolded, label=label
    ))
    executed = True
    mismatches: list[str] = []
    n_leaves = 0
    try:
        mismatches, n_leaves = run_replay_sentinel(label=label)
    except Exception:
        executed = False
    findings.extend(check_replay_sentinel(
        mismatches, executed=executed, label=label
    ))
    report.record = {
        "program_fingerprint": jaxpr_fingerprint(closed),
        "compiled_fingerprint": "",
        "replay_leaves_checked": int(n_leaves),
        **_key_record(flow),
    }
    report.findings = findings
    return report


# -- builtin targets ---------------------------------------------------------


@dataclass(frozen=True)
class ReproTarget:
    """One determinism self-gate configuration the CLI audits.

    ``kind`` selects the harness: ``train`` (key walk + nondet HLO +
    resume identity on the fake mesh), ``serve`` (wave-replay proof on
    the decode program), ``exec`` (the executed replay sentinel).
    ``scatter_allow`` lists reviewed op_name substrings exempt from the
    float-scatter-add check (see :func:`check_nondet_hlo`)."""

    name: str
    kind: str
    build: Callable[[], tuple]
    mesh_shape: Mapping[str, int] = field(default_factory=dict)
    scatter_allow: Tuple[str, ...] = ()
    demo: bool = False


def _shard_builder(name):
    def build():
        import rocket_tpu.analysis.shard_audit as shard_audit

        return getattr(shard_audit, name)()
    return build


def _sched_builder(name):
    def build():
        import rocket_tpu.analysis.sched_audit as sched_audit

        return getattr(sched_audit, name)()
    return build


def _moe_parts():
    """The RNG-heavy target: dropout in every block plus the MoE router,
    with resume-not-restart key discipline — state carries an int32 step
    counter and the step derives ``rng = fold_in(key(<const>),
    rng_step)``, so a restored counter replays the exact dropout masks a
    continuous run would have drawn (key-typed state would be both
    unrestorable and un-auditable)."""
    import optax

    from rocket_tpu.analysis.shard_audit import _lm_config
    from rocket_tpu.models.transformer import TransformerLM

    model = TransformerLM(_lm_config(
        num_experts=4, expert_top_k=2, mlp="gelu", dropout=0.1,
    ))
    variables = dict(jax.eval_shape(model.init, jax.random.key(0)))
    variables["rng_step"] = jax.ShapeDtypeStruct((), jnp.int32)
    batch = {
        "tokens": jax.ShapeDtypeStruct(
            (16, model.config.max_seq_len), jnp.int32
        )
    }

    def loss_fn(params, variables, batch, rng):
        out, _state = model.apply(
            dict(variables, params=params), dict(batch),
            mode="train", rng=rng,
        )
        logits = out["logits"][:, :-1].astype(jnp.float32)
        targets = out["tokens"][:, 1:]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()
        aux = out.get("moe_aux_loss")
        if aux is not None:
            loss = loss + jnp.asarray(aux, jnp.float32)
        return loss

    def train_step(variables, batch):
        rng = jax.random.fold_in(
            jax.random.key(20260806), variables["rng_step"]
        )
        loss, grads = jax.value_and_grad(loss_fn)(
            variables["params"], variables, batch, rng
        )
        params = jax.tree.map(
            lambda p, g: (p - 1e-3 * g).astype(p.dtype),
            variables["params"], grads,
        )
        new_variables = dict(
            variables, params=params,
            rng_step=variables["rng_step"] + jnp.int32(1),
        )
        return new_variables, loss

    return train_step, variables, batch, None, (0,)


def _charlm_wave_parts():
    from rocket_tpu.analysis.serve_audit import _charlm_serve_parts

    return _charlm_serve_parts()


def _badrepro_parts():
    """Seeded-bad step for the true-positive fixture tests: one key
    consumed by two random primitives (RKT901 reuse), a closure key
    consumed raw inside a scan body (RKT901 unfolded), and a float
    scatter-add over duplicate-capable batch indices (RKT902)."""
    variables = {
        "params": {
            "w": jax.ShapeDtypeStruct((64, 64), jnp.float32),
            "emb": jax.ShapeDtypeStruct((32, 64), jnp.float32),
        },
        "state": {},
    }
    batch = {
        "x": jax.ShapeDtypeStruct((8, 64), jnp.float32),
        "idx": jax.ShapeDtypeStruct((8,), jnp.int32),
    }

    def bad_step(variables, batch):
        key = jax.random.key(0)
        noise_a = jax.random.normal(key, (8, 64))    # consumption 1
        noise_b = jax.random.uniform(key, (8, 64))   # consumption 2
        loop_key = jax.random.key(1)

        def body(carry, _):
            # The unfolded-loop bug: every iteration draws the SAME eps.
            eps = jax.random.normal(loop_key, (64,))
            return carry + eps.sum(), None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=4)
        h = (batch["x"] + noise_a * noise_b) @ variables["params"]["w"]
        # Duplicate-capable indices + float add = RKT902.
        emb = variables["params"]["emb"].at[batch["idx"] % 32].add(h * 1e-3)
        loss = (h * h).mean() + (emb * emb).mean() + acc * 0.0
        params = {"w": variables["params"]["w"] * 0.999, "emb": emb}
        return {"params": params, "state": variables["state"]}, loss

    return bad_step, variables, batch, None, ()


#: Reviewed float scatter-add sites, matched against the finding's
#: ``primitive@file:line (function)`` site string — each entry is an
#: explicit, greppable exception like a certified collective.
#:
#: Cross-entropy integer-label transpose: one scattered index per
#: (batch, position) row, provably unique; jax can't thread
#: ``unique_indices`` through optax's take_along_axis.
_XENT_GRAD_ALLOW = ("(loss_fn)",)
#: Embedding-table gradient (transpose of the token-id gather in
#: ``models/transformer.py`` / the sharded custom-vjp lookup):
#: duplicate token ids DO accumulate, but XLA expands the scatter with
#: a fixed combine order on the CPU/TPU backends the repo targets —
#: deterministic run-to-run on one binary.
_EMBED_GRAD_ALLOW = (
    "rocket_tpu/models/transformer.py",
    "(embed_lookup_sharded)",
)
#: MoE top_k transpose in ``nn/moe.py``: k distinct positions per row,
#: provably unique.
_MOE_TOPK_ALLOW = ("rocket_tpu/nn/moe.py",)

#: name -> target. The default sweep runs the non-demo entries: the
#: tp/fsdp/resnet pairings the other audits gate, the RNG-heavy MoE
#: step, the charlm serve wave, and the executed replay sentinel.
REPRO_TARGETS: dict[str, ReproTarget] = {}


def _register_targets():
    for target in (
        ReproTarget(
            name="tp_1x8",
            kind="train",
            build=_shard_builder("_tp_parts"),
            mesh_shape={"data": 1, "model": 8},
            scatter_allow=_XENT_GRAD_ALLOW + _EMBED_GRAD_ALLOW,
        ),
        ReproTarget(
            name="fsdp_1x8",
            kind="train",
            build=_shard_builder("_fsdp_parts"),
            mesh_shape={"data": 8},
            scatter_allow=_XENT_GRAD_ALLOW + _EMBED_GRAD_ALLOW,
        ),
        ReproTarget(
            name="dp_resnet_1x8",
            kind="train",
            build=_sched_builder("_resnet_parts"),
            mesh_shape={"data": 8},
            scatter_allow=_XENT_GRAD_ALLOW,
        ),
        ReproTarget(
            name="moe",
            kind="train",
            build=_moe_parts,
            mesh_shape={"data": 8},
            scatter_allow=(
                _XENT_GRAD_ALLOW + _EMBED_GRAD_ALLOW + _MOE_TOPK_ALLOW
            ),
        ),
        ReproTarget(
            name="charlm_wave",
            kind="serve",
            build=_charlm_wave_parts,
        ),
        ReproTarget(
            name="gpt2_sentinel",
            kind="exec",
            build=_sentinel_parts,
            mesh_shape={"data": 1},
        ),
        ReproTarget(
            name="badrepro",
            kind="train",
            build=_badrepro_parts,
            mesh_shape={"data": 1},
            demo=True,
        ),
    ):
        REPRO_TARGETS[target.name] = target


_register_targets()


def run_repro_target(target: ReproTarget) -> ReproAuditReport:
    if target.kind == "serve":
        model, serve_config = target.build()
        return audit_serve_repro(
            model, serve_config, scatter_allow=target.scatter_allow,
            label=target.name,
        )
    if target.kind == "exec":
        return audit_sentinel_repro(label=target.name)
    step_fn, variables, batch, rules, donate = target.build()
    return audit_train_repro(
        step_fn, variables, batch, rules=rules,
        mesh_shape=target.mesh_shape, donate_argnums=donate,
        scatter_allow=target.scatter_allow, label=target.name,
    )
