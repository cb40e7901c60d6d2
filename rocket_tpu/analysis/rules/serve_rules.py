"""Serving-path audit rules (``RKT6xx``) — checks over the AOT-compiled
serving programs and the scheduler's admission-state lattice.

The serving engine's load-bearing invariants — exactly two compiled
programs with zero retraces across every admission state, pool-bounded
HBM, one small host transfer per wave — were until now verified only
*dynamically*, by running the engine and reading its trace counters.
This family proves them statically, the same way ``sched_audit``
(RKT5xx) extended ``shard_audit`` from bytes to time: the REAL decode
wave / prefill chunk programs are AOT-compiled on the fake-mesh harness
(no params, no FLOPs), priced with the roofline cost model, and the
REAL host scheduler is driven through the full admission lattice against
a recording engine so every wave's input signature is observed.

The lattice driving, compilation, roofline math and builtin targets live
in :mod:`rocket_tpu.analysis.serve_audit`; this module holds the catalog
plus the fact->Finding checks, so the rule logic is testable without
compiling anything.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

from rocket_tpu.analysis.findings import Finding

__all__ = [
    "SERVE_RULES",
    "check_retrace_surface",
    "check_decode_roofline",
    "check_hbm_fit",
    "check_serve_donation",
    "check_latency_ceilings",
]

#: (id, slug, contract) — the catalog, same shape as SCHED_RULES.
SERVE_RULES = (
    ("RKT601", "serve-retrace-surface",
     "an admission state (partial/full slots, EOS mid-wave, eviction, "
     "refill, final prefill chunk) feeds the compiled wave a different "
     "trace signature — a python-value-dependent shape, dtype drift or "
     "weak-type promotion that would retrace the serving engine at "
     "runtime; all states must hash to ONE signature per program"),
    ("RKT602", "decode-overfetch",
     "the compiled decode wave's predicted HBM traffic exceeds the "
     "analytic floor (master params + active-KV gather + pool scatter) "
     "by more than the allowed ratio: the wave moves bytes the model "
     "does not need — oversized transients, a wide pool dtype, or lost "
     "fusion on the decode path"),
    ("RKT603", "kv-pool-hbm-overflow",
     "pool bytes + master params + compiled temps exceed the device "
     "kind's HBM capacity: the serve config cannot be loaded on the "
     "target hardware — shrink (slots, blocks) to the reported frontier "
     "or narrow the pool dtype"),
    ("RKT604", "serve-donation-sync",
     "a pool buffer is not donated/aliased through a compiled serving "
     "program (the pool would be copied every wave), or the per-wave "
     "non-aliased output exceeds the host-transfer budget (serving "
     "fetches more than the sampled tokens), or the prefill program "
     "returns anything beyond the aliased pool (a hidden per-chunk "
     "transfer)"),
    ("RKT605", "serve-latency-ceiling",
     "the roofline-predicted inter-token latency or time-to-first-token "
     "exceeds this target's declared ceiling: the compiled serving path "
     "regressed structurally even if no budget metric moved"),
    ("RKT606", "serve-budget-regression",
     "predicted ITL/TTFT or the engine HBM footprint grew more than the "
     "tolerance over the checked-in serving budget file"),
)


def _serve_path(label: str) -> str:
    return f"<serve:{label}>"


def check_retrace_surface(
    observations: Sequence,   # serve_audit.WaveObservation
    *,
    label: str = "serve",
) -> list[Finding]:
    """RKT601: one trace signature per program across the whole lattice.

    ``observations`` is the recorded call stream of the REAL scheduler
    driven through the admission lattice: each entry carries the program
    name (``decode``/``prefill``), the state label the harness assigned,
    and the hashable input signature (shapes/dtypes for arrays; type AND
    VALUE for python scalars — a python value in the wave signature is
    exactly the retrace surface this rule exists to catch).
    """
    findings = []
    by_program: dict[str, dict] = {}
    for obs in observations:
        by_program.setdefault(obs.program, {}).setdefault(
            obs.signature, []
        ).append(obs.state)
    for program, sigs in sorted(by_program.items()):
        if len(sigs) > 1:
            groups = sorted(sigs.items(), key=lambda kv: -len(kv[1]))
            majority_sig, majority_states = groups[0]
            for sig, states in groups[1:]:
                diff = [
                    (i, a, b) for i, (a, b) in
                    enumerate(zip(majority_sig, sig)) if a != b
                ] or [(len(majority_sig), "<missing>", "<extra>")]
                i, a, b = diff[0]
                findings.append(Finding(
                    "RKT601", _serve_path(label), 0,
                    f"serve-retrace-surface: the {program} program sees "
                    f"{len(sigs)} distinct trace signatures across the "
                    f"admission lattice — state(s) {sorted(set(states))} "
                    f"diverge from {sorted(set(majority_states))[:3]} at "
                    f"input {i}: {'/'.join(map(str, a))} vs "
                    f"{'/'.join(map(str, b))}; every admission state must "
                    "change array VALUES only, never shapes, dtypes or "
                    "python-level inputs",
                ))
    # Python scalars in ANY wave signature are a hazard even when the
    # enumerated lattice happened not to vary them: a python value in
    # the compiled signature either retraces per value (static) or
    # weak-type-promotes (a dtype drift the trace auditor flags as
    # RKT204 in training steps).
    seen_hazards: set = set()
    for obs in observations:
        for i, leaf in enumerate(obs.signature):
            if leaf and leaf[0] == "pyval" and (obs.program, i) not in seen_hazards:
                seen_hazards.add((obs.program, i))
                findings.append(Finding(
                    "RKT601", _serve_path(label), 0,
                    f"serve-retrace-surface: the {obs.program} "
                    f"program's input {i} is a python-level value "
                    f"({leaf[1]}) — it bakes into the compiled program "
                    "(retrace per distinct value) or weak-type-promotes; "
                    "pass it as a fixed-dtype device array instead",
                ))
    return findings


def check_decode_roofline(
    traffic_bytes: Optional[int],
    floor_bytes: int,
    *,
    overfetch_ratio: float = 16.0,
    label: str = "serve",
) -> list[Finding]:
    """RKT602: compiled decode-wave HBM traffic vs the analytic floor.

    ``floor_bytes`` is what ONE wave fundamentally streams: the master
    params (decode is parameter-bound), the active-KV gather for every
    slot's mapped blocks, and the one-row-per-slot pool scatter.
    ``traffic_bytes`` is the compiled wave's unique traffic (arguments +
    outputs + temps twice). The compiled program legitimately moves more
    than the floor (transient context materialization, logits,
    softmax temporaries), so the gate is a RATIO with headroom — it
    fires when the wave moves far more than the model needs, which is
    how a wide pool dtype, an oversized transient or a lost fusion on
    the decode path shows up.
    """
    traffic = traffic_bytes
    if not traffic or floor_bytes <= 0:
        return []
    ratio = traffic / floor_bytes
    if ratio <= overfetch_ratio:
        return []
    return [Finding(
        "RKT602", _serve_path(label), 0,
        f"decode-overfetch: the compiled decode wave moves "
        f"{traffic / 2**20:.1f} MiB of HBM traffic vs the "
        f"{floor_bytes / 2**20:.1f} MiB analytic floor (params + active-"
        f"KV gather + scatter) — {ratio:.1f}x, over the {overfetch_ratio:.0f}x "
        "allowance; check the pool dtype, the gathered context size and "
        "the decode path's fusions",
    )]


def check_hbm_fit(
    hbm: Mapping,
    *,
    label: str = "serve",
) -> list[Finding]:
    """RKT603: engine steady-state HBM vs the device kind's capacity.

    ``hbm`` is the fit record: pool/params/temps/total bytes, the
    capacity, and the frontier (max blocks and max full-context slots
    that WOULD fit). The finding reports the frontier so the fix is a
    config edit, not a search.
    """
    total = hbm.get("total_bytes") or 0
    capacity = hbm.get("capacity_bytes") or 0
    if not capacity or total <= capacity:
        return []
    frontier = hbm.get("frontier") or {}
    return [Finding(
        "RKT603", _serve_path(label), 0,
        f"kv-pool-hbm-overflow: pool {hbm.get('pool_bytes', 0) / 2**30:.2f} "
        f"GiB + params {hbm.get('params_bytes', 0) / 2**30:.2f} GiB + "
        f"compiled temps {hbm.get('temp_bytes', 0) / 2**30:.2f} GiB = "
        f"{total / 2**30:.2f} GiB exceeds the {capacity / 2**30:.0f} GiB "
        f"{hbm.get('device_kind', 'device')} HBM — max that fits: "
        f"{frontier.get('max_num_blocks', 0)} blocks "
        f"({frontier.get('max_full_context_slots', 0)} full-context "
        "slots); shrink (slots, blocks) or narrow the pool dtype",
    )]


def check_serve_donation(
    programs: Sequence,   # serve_audit.CompiledServeProgram
    pool_bytes: int,
    *,
    host_bytes_max: int = 64 << 10,
    label: str = "serve",
) -> list[Finding]:
    """RKT604: pool donation + the one-small-host-transfer-per-wave story.

    Every compiled program must alias EVERY pool buffer input->output
    (``pool_bytes`` of aliasing — ``KVPoolSpec.pool_bytes`` covers all
    the pool's arrays, K and V or the one latent array; anything less
    means XLA inserted a pool copy somewhere
    on the wave path); the decode wave's non-aliased output (what the
    driver's single ``device_get`` fetches) must stay under
    ``host_bytes_max``; and the prefill program must return nothing
    beyond the aliased pool plus tuple/layout padding (it is
    fire-and-forget — a real extra output is a hidden per-chunk
    transfer).
    """
    findings = []
    for prog in programs:
        expected = pool_bytes
        if prog.aliased_bytes < expected:
            findings.append(Finding(
                "RKT604", _serve_path(label), 0,
                f"serve-donation-sync: the {prog.name} program aliases "
                f"only {prog.aliased_bytes / 2**20:.2f} MiB of the "
                f"{expected / 2**20:.2f} MiB donated pool buffers "
                "(every array of the pool) — the pool is copied every "
                f"{prog.name} call; donate the pool argument and keep "
                "its arrays flowing input->output unchanged in shape/dtype",
            ))
        # Prefill returns only the aliased pool; a few bytes of tuple/
        # layout padding show up in output accounting on some backends.
        # "decode_wave" (the k>1 targets' single-wave attribution
        # compile) shares decode's budget — it returns the same token/
        # done/emitted rows for one wave.
        budget = host_bytes_max if prog.name.startswith("decode") else 256
        if prog.non_aliased_output_bytes > budget:
            what = (
                "fetches more than the sampled tokens/done flags"
                if prog.name == "decode"
                else "returns data beyond the aliased pool (prefill is "
                     "fire-and-forget; any output here is a hidden "
                     "per-chunk transfer)"
            )
            findings.append(Finding(
                "RKT604", _serve_path(label), 0,
                f"serve-donation-sync: the {prog.name} program's "
                f"non-aliased output is "
                f"{prog.non_aliased_output_bytes:,} bytes (budget "
                f"{budget:,}) — the wave {what}",
            ))
    return findings


def check_latency_ceilings(
    record: Mapping,
    *,
    itl_ceiling_us: float = 0.0,
    ttft_ceiling_us: float = 0.0,
    label: str = "serve",
) -> list[Finding]:
    """RKT605: predicted ITL/TTFT vs this target's declared ceilings
    (0 disables a ceiling, like RKT505's mfu_floor)."""
    findings = []
    checks = (
        ("predicted_itl_us", itl_ceiling_us, "inter-token latency"),
        ("predicted_ttft_us", ttft_ceiling_us, "time-to-first-token"),
    )
    for key, ceiling, name in checks:
        value = record.get(key)
        if ceiling <= 0 or not isinstance(value, (int, float)):
            continue
        if value > ceiling:
            findings.append(Finding(
                "RKT605", _serve_path(label), 0,
                f"serve-latency-ceiling: roofline-predicted {name} "
                f"{value:.1f}us exceeds this target's ceiling "
                f"{ceiling:.1f}us — the compiled serving path regressed "
                "(lost fusion, wider pool traffic, slower prefill "
                "schedule); inspect the wave attribution and re-baseline "
                "the ceiling only if the regression is intended",
            ))
    return findings
