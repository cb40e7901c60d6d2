"""serve_audit — the static serving-path auditor.

Four layers, mirroring the module:

* rule-check units (RKT601-605) on synthetic facts — no compilation;
* roofline/HBM math units (decode floor, fit frontier) — exact
  arithmetic;
* the admission-state lattice driven against the REAL scheduler with a
  recording engine: completeness (every REQUIRED state observed), the
  one-signature-per-program proof, and the seeded python-leak true
  positive;
* the full audit on the builtin ``tiny`` target (AOT compile + all
  rules + budget gate), plus the BENCH_DETAIL calibration tie.
"""

import os
from dataclasses import replace

import numpy as np
import pytest

from rocket_tpu.analysis.rules.serve_rules import (
    check_decode_roofline,
    check_hbm_fit,
    check_latency_ceilings,
    check_retrace_surface,
    check_serve_donation,
)
from rocket_tpu.analysis.serve_audit import (
    REQUIRED_LATTICE_STATES,
    CompiledServeProgram,
    RecordingEngine,
    WaveObservation,
    decode_floor_bytes,
    enumerate_admission_lattice,
    estimate_serve_hbm,
    wave_signature,
)
from rocket_tpu.serve.kv_pool import KVPoolSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_in(findings):
    return sorted({f.rule for f in findings})


# -- wave signatures ---------------------------------------------------------

def test_wave_signature_is_aval_only_for_arrays():
    """Two calls differing ONLY in array values share one signature —
    exactly the jit cache-key semantics the proof relies on."""
    a = wave_signature([np.zeros((4, 8), np.int32), np.ones((4,), bool)])
    b = wave_signature([np.full((4, 8), 7, np.int32),
                        np.zeros((4,), bool)])
    assert a == b
    # A shape or dtype change is a different signature.
    assert a != wave_signature([np.zeros((4, 9), np.int32),
                                np.ones((4,), bool)])
    assert a != wave_signature([np.zeros((4, 8), np.int64),
                                np.ones((4,), bool)])


def test_wave_signature_carries_python_values():
    """Python scalars keep their VALUE in the signature: value-varying
    python inputs across lattice states is the retrace surface."""
    assert wave_signature([3]) != wave_signature([4])
    assert wave_signature([3])[0][0] == "pyval"


# -- RKT601: retrace surface -------------------------------------------------

def _obs(program, state, sig):
    return WaveObservation(program=program, state=state, signature=sig)


def test_retrace_surface_clean_on_one_signature():
    sig = wave_signature([np.zeros((4,), np.int32)])
    obs = [_obs("decode", s, sig) for s in ("first_admit", "full_slots")]
    assert check_retrace_surface(obs) == []


def test_retrace_surface_flags_divergent_states_and_names_them():
    good = wave_signature([np.zeros((4,), np.int32)])
    bad = wave_signature([np.zeros((5,), np.int32)])
    obs = [
        _obs("decode", "first_admit", good),
        _obs("decode", "full_slots", good),
        _obs("decode", "eviction", bad),
    ]
    findings = check_retrace_surface(obs, label="t")
    assert rules_in(findings) == ["RKT601"]
    assert "eviction" in findings[0].message
    assert "2 distinct trace signatures" in findings[0].message


def test_retrace_surface_flags_python_value_even_when_constant():
    """A python scalar in the signature is a hazard even if the lattice
    never varied it."""
    sig = wave_signature([np.zeros((4,), np.int32), 7])
    obs = [_obs("decode", s, sig) for s in ("first_admit", "full_slots")]
    findings = check_retrace_surface(obs)
    assert rules_in(findings) == ["RKT601"]
    assert "python-level value" in findings[0].message


# -- RKT602: decode roofline -------------------------------------------------

def test_decode_roofline_passes_within_ratio_and_fires_beyond():
    assert check_decode_roofline(10 * 2**20, 2 * 2**20,
                                 overfetch_ratio=16.0) == []
    findings = check_decode_roofline(40 * 2**20, 2 * 2**20,
                                     overfetch_ratio=16.0, label="t")
    assert rules_in(findings) == ["RKT602"]
    assert "20.0x" in findings[0].message


def test_decode_floor_bytes_exact():
    # floor = params + 2*L*S*MB*BL*row (gather, K and V) + 2*L*S*row
    # (one new row per slot, K and V), row = Hkv*D*itemsize.
    spec = KVPoolSpec(num_layers=2, num_blocks=9, block_len=4,
                      num_kv_heads=3, head_dim=5, dtype="float32")
    row = 3 * 5 * 4
    expected = 1000 + 2 * 2 * 7 * 2 * 4 * row + 2 * 2 * 7 * row
    assert decode_floor_bytes(
        spec, 1000, max_slots=7, max_blocks_per_seq=2
    ) == expected


def test_fused_decode_bytes_is_floor_plus_logits_traffic():
    """The fused-kernel byte model (ISSUE 11): the active-pages-only
    gather floor plus the (S, V) f32 logits written once and re-read by
    the runtime-knob sampling core — nothing pool-sized beyond the
    mapped pages."""
    from rocket_tpu.analysis.serve_audit import fused_decode_bytes

    spec = KVPoolSpec(num_layers=2, num_blocks=9, block_len=4,
                      num_kv_heads=3, head_dim=5, dtype="float32")
    floor = decode_floor_bytes(spec, 1000, max_slots=7,
                               max_blocks_per_seq=2)
    fused = fused_decode_bytes(spec, 1000, max_slots=7,
                               max_blocks_per_seq=2, vocab_size=50)
    assert fused == floor + 4 * 7 * 50 * 4
    # The model is independent of num_blocks: the kernel streams mapped
    # pages, not the pool — a 100x pool prices identically.
    big = KVPoolSpec(num_layers=2, num_blocks=900, block_len=4,
                     num_kv_heads=3, head_dim=5, dtype="float32")
    assert fused == fused_decode_bytes(big, 1000, max_slots=7,
                                       max_blocks_per_seq=2, vocab_size=50)


# -- RKT603: HBM fit ---------------------------------------------------------

class _Dev:
    kind = "TPU test"

    def __init__(self, hbm_bytes):
        self.hbm_bytes = hbm_bytes


def _prog(name="decode", temp=0, aliased=0, out_extra=0):
    return CompiledServeProgram(
        name=name, record={}, wave_time_us=1.0, wave_hbm_bytes=1,
        aliased_bytes=aliased, non_aliased_output_bytes=out_extra,
        temp_bytes=temp, abstract_signature=(),
    )


def test_hbm_fit_frontier_math_and_gate():
    spec = KVPoolSpec(num_layers=1, num_blocks=11, block_len=8,
                      num_kv_heads=2, head_dim=4, dtype="float32")
    # block_bytes = 2*1*8*2*4*4 = 512; pool = 11*512 = 5632.
    assert spec.block_bytes == 512
    programs = [_prog(temp=1000), _prog("prefill", temp=400)]
    hbm = estimate_serve_hbm(spec, 2000, programs, _Dev(100_000),
                             max_blocks_per_seq=4)
    # Steady state: pool + params + max(temp) — the programs never run
    # concurrently.
    assert hbm["total_bytes"] == 5632 + 2000 + 1000
    # Frontier: (capacity - params - temp) // block_bytes blocks; one
    # reserved; full-context slots at 4 blocks each.
    headroom = 100_000 - 2000 - 1000
    assert hbm["frontier"]["max_num_blocks"] == headroom // 512
    assert hbm["frontier"]["max_full_context_slots"] == \
        (headroom // 512 - 1) // 4
    assert check_hbm_fit(hbm) == []

    tight = estimate_serve_hbm(spec, 2000, programs, _Dev(6000),
                               max_blocks_per_seq=4)
    findings = check_hbm_fit(tight, label="t")
    assert rules_in(findings) == ["RKT603"]
    assert "max that fits" in findings[0].message


# -- RKT604: donation / host transfer ----------------------------------------

def test_serve_donation_clean_when_pool_aliased_and_output_small():
    programs = [
        _prog("decode", aliased=4096, out_extra=52),
        _prog("prefill", aliased=4096, out_extra=16),
    ]
    assert check_serve_donation(programs, pool_bytes=4096) == []


def test_serve_donation_flags_missing_alias_and_large_fetch():
    programs = [
        _prog("decode", aliased=0, out_extra=1 << 20),
        _prog("prefill", aliased=4096, out_extra=4096),
    ]
    findings = check_serve_donation(programs, pool_bytes=4096)
    assert rules_in(findings) == ["RKT604"]
    messages = " ".join(f.message for f in findings)
    assert "copied every decode call" in messages
    assert "fetches more than the sampled tokens" in messages
    assert "hidden per-chunk transfer" in messages


# -- RKT605: latency ceilings ------------------------------------------------

def test_latency_ceilings_disabled_passing_and_firing():
    record = {"predicted_itl_us": 100.0, "predicted_ttft_us": 400.0}
    assert check_latency_ceilings(record) == []  # 0 disables
    assert check_latency_ceilings(
        record, itl_ceiling_us=150.0, ttft_ceiling_us=500.0
    ) == []
    findings = check_latency_ceilings(
        record, itl_ceiling_us=80.0, ttft_ceiling_us=300.0, label="t"
    )
    assert len(findings) == 2 and rules_in(findings) == ["RKT605"]


# -- the admission-state lattice ---------------------------------------------

def _tiny_engine(engine_cls=RecordingEngine):
    spec = KVPoolSpec(num_layers=2, num_blocks=33, block_len=16,
                      num_kv_heads=4, head_dim=16, dtype="float32")
    return engine_cls(spec, max_slots=4, max_blocks_per_seq=8,
                      prefill_chunk=16, max_seq_len=128)


def test_lattice_enumeration_is_complete_and_single_signature():
    """The harness drives the REAL Scheduler through every required
    admission state, and all recorded calls hash to ONE signature per
    program — the non-vacuous retrace proof."""
    engine = _tiny_engine()
    observations, findings, states = enumerate_admission_lattice(engine)
    assert findings == [], [f.render() for f in findings]
    assert REQUIRED_LATTICE_STATES <= states
    decode_sigs = {o.signature for o in observations
                   if o.program == "decode"}
    prefill_sigs = {o.signature for o in observations
                    if o.program == "prefill"}
    assert len(decode_sigs) == 1
    assert len(prefill_sigs) == 1
    assert check_retrace_surface(observations) == []
    # The decode signature is the scheduler's 11 fixed-shape mirrors
    # (``fresh`` the last: which slots the host sets this dispatch).
    (sig,) = decode_sigs
    assert len(sig) == 11 and all(leaf[0] == "array" for leaf in sig)
    assert sig[-1] == ("array", (engine.max_slots,), "bool")


def test_lattice_respects_non_block_multiple_max_seq_len():
    """Scheduler.submit enforces model max_seq_len separately from the
    block context; a max_seq_len that is NOT a block multiple must bound
    the harness prompts, not crash the drive with a ValueError."""
    spec = KVPoolSpec(num_layers=2, num_blocks=33, block_len=16,
                      num_kv_heads=4, head_dim=16, dtype="float32")
    engine = RecordingEngine(spec, max_slots=4, max_blocks_per_seq=7,
                             prefill_chunk=64, max_seq_len=100)
    observations, findings, states = enumerate_admission_lattice(engine)
    assert observations  # the drive ran to completion
    assert all(f.rule == "RKT601" for f in findings)


def test_lattice_survives_one_block_slots():
    """A geometry where each slot is ONE block (max_new_tokens would
    exceed the context unclamped) must still drive to completion."""
    spec = KVPoolSpec(num_layers=2, num_blocks=9, block_len=128,
                      num_kv_heads=4, head_dim=16, dtype="float32")
    engine = RecordingEngine(spec, max_slots=4, max_blocks_per_seq=1,
                             prefill_chunk=16, max_seq_len=128)
    observations, findings, _states = enumerate_admission_lattice(engine)
    assert observations
    assert all(f.rule == "RKT601" for f in findings)


def test_lattice_missing_required_state_is_a_finding():
    """A geometry whose drive cannot observe a required state must fail
    loudly (vacuous proof), not audit clean: with prefill_chunk >= the
    longest admissible prompt, multi_chunk_prefill never happens."""
    spec = KVPoolSpec(num_layers=2, num_blocks=33, block_len=16,
                      num_kv_heads=4, head_dim=16, dtype="float32")
    engine = RecordingEngine(spec, max_slots=4, max_blocks_per_seq=4,
                             prefill_chunk=128, max_seq_len=64)
    _observations, findings, states = enumerate_admission_lattice(engine)
    assert "multi_chunk_prefill" not in states
    assert any(
        f.rule == "RKT601" and "multi_chunk_prefill" in f.message
        for f in findings
    ), [f.render() for f in findings]


def test_lattice_python_leak_is_caught():
    """The seeded-bad engine leaks the python active-count into the wave
    signature: distinct values across states -> RKT601."""
    from rocket_tpu.analysis.serve_audit import _PyLeakRecordingEngine

    engine = _tiny_engine(_PyLeakRecordingEngine)
    observations, _findings, _states = enumerate_admission_lattice(engine)
    findings = check_retrace_surface(observations)
    assert "RKT601" in rules_in(findings)
    assert any("python-level value" in f.message for f in findings)
    assert any("distinct trace signatures" in f.message for f in findings)


# -- the full audit on the builtin targets -----------------------------------

@pytest.fixture(scope="module")
def tiny_report():
    from rocket_tpu.analysis.serve_audit import SERVE_TARGETS, run_serve_target

    return run_serve_target(SERVE_TARGETS["tiny"])


def test_tiny_target_audits_clean(tiny_report):
    assert tiny_report.findings == [], \
        [f.render() for f in tiny_report.findings]


def test_tiny_target_proves_two_programs_one_signature_each(tiny_report):
    assert {p.name for p in tiny_report.programs} == {"decode", "prefill"}
    lattice = tiny_report.record["lattice"]
    assert lattice["decode_signatures"] == 1
    assert lattice["prefill_signatures"] == 1
    assert set(REQUIRED_LATTICE_STATES) <= set(lattice["states"])


def test_tiny_target_record_carries_the_gated_keys(tiny_report):
    from rocket_tpu.analysis.budgets import SERVE_GATED_KEYS

    record = tiny_report.record
    for key in SERVE_GATED_KEYS:
        assert isinstance(record[key], (int, float)) and record[key] > 0
    # TTFT decomposes into the chunk schedule + the first wave: for the
    # tiny target (ref 48, chunk 16) that is ceil(47/16)=3 chunks.
    assert record["predicted_ttft_us"] == pytest.approx(
        3 * record["prefill_chunk_us"] + record["predicted_itl_us"],
        rel=1e-6,
    )
    # The wave is HBM-bound and moves at least the analytic floor.
    assert record["overfetch_ratio"] >= 1.0
    # The one host transfer per wave is a few hundred bytes, not pools.
    assert 0 < record["host_bytes_per_wave"] < 4096


def test_tiny_target_pool_donated_through_both_programs(tiny_report):
    spec_pool = tiny_report.record["hbm"]["pool_bytes"]
    for prog in tiny_report.programs:
        assert prog.aliased_bytes >= spec_pool


def test_serve_budget_gate_fires_on_growth_only():
    from rocket_tpu.analysis.budgets import SERVE_GATED_KEYS, diff_budget

    committed = {"predicted_itl_us": 10.0, "predicted_ttft_us": 40.0,
                 "hbm_total_bytes": 1000}
    grown = dict(committed, predicted_itl_us=12.0)
    findings = diff_budget("tiny", committed, grown,
                           keys=SERVE_GATED_KEYS, rule="RKT606",
                           family="serve")
    assert rules_in(findings) == ["RKT606"]
    assert "analysis serve" in diff_budget(
        "tiny", None, grown, keys=SERVE_GATED_KEYS, rule="RKT606",
        family="serve",
    )[0].message
    shrunk = dict(committed, predicted_itl_us=8.0, hbm_total_bytes=900)
    assert diff_budget("tiny", committed, shrunk, keys=SERVE_GATED_KEYS,
                       rule="RKT606", family="serve") == []


def test_committed_budgets_match_the_builtin_targets():
    """Every non-demo serve target has a committed budget and vice
    versa — a new target must land with its baseline or CI gates
    nothing."""
    from rocket_tpu.analysis.budgets import SERVE_DIR, load_budget
    from rocket_tpu.analysis.serve_audit import SERVE_TARGETS

    budget_dir = os.path.join(REPO, SERVE_DIR)
    names = {os.path.splitext(f)[0] for f in os.listdir(budget_dir)
             if f.endswith(".json")}
    expected = {n for n, t in SERVE_TARGETS.items() if not t.demo}
    assert names == expected
    for name in names:
        assert load_budget(budget_dir, name) is not None


@pytest.fixture(scope="module")
def charlm_report():
    from rocket_tpu.analysis.serve_audit import SERVE_TARGETS, run_serve_target

    return run_serve_target(SERVE_TARGETS["charlm"])


def test_kwave_target_audits_clean_with_scan_pricing(charlm_report):
    """The charlm target scans k=4 waves per dispatch: the audit
    compiles the REAL scanned program (plus a single-wave attribution
    compile), prices per-TOKEN ITL under the fused-kernel byte model,
    and decomposes TTFT with the k-wave observation delay."""
    report = charlm_report
    assert report.findings == [], [f.render() for f in report.findings]
    names = {p.name for p in report.programs}
    assert names == {"decode", "decode_wave", "prefill"}
    record = report.record
    assert record["waves_per_dispatch"] == 4
    assert record["byte_model"] == "fused-paged"
    # Per-token ITL prices the FUSED bytes, far under the XLA gather's.
    assert record["decode_traffic_bytes"] == record["fused_decode_bytes"]
    assert record["decode_traffic_bytes"] < record["xla_traffic_bytes"]
    assert record["predicted_itl_us"] < record["xla_traffic_bytes"] / \
        record["decode_traffic_bytes"] * record["itl_floor_us"] * 2
    # TTFT = chunk schedule + k waves (first token observed when the
    # whole first dispatch returns): ceil(63/32) = 2 chunks, k = 4.
    assert record["predicted_ttft_us"] == pytest.approx(
        2 * record["prefill_chunk_us"] + 4 * record["predicted_itl_us"],
        rel=1e-6,
    )
    # The overfetch ratio still audits the compiled XLA fallback path.
    assert record["overfetch_ratio"] == pytest.approx(
        record["xla_traffic_bytes"] / record["decode_floor_bytes"],
        rel=0.01,
    )


def test_kwave_lattice_drives_scanned_recording_engine(charlm_report):
    """The lattice proof is non-vacuous at k=4: every required state
    observed through the pipelined scheduler, one signature, and the
    recording engine simulated k waves per recorded dispatch."""
    lattice = charlm_report.record["lattice"]
    assert set(REQUIRED_LATTICE_STATES) <= set(lattice["states"])
    assert lattice["decode_signatures"] == 1


def test_recording_engine_scan_freezes_mid_dispatch():
    """The recording engine's k-wave simulation matches the compiled
    scan's carry semantics: a slot hitting its limit mid-dispatch stops
    emitting in later waves of the same dispatch."""
    engine = _tiny_engine()
    engine.waves_per_dispatch = 4
    block_table = np.zeros((4, 8), np.int32)
    lengths = np.asarray([0, 0, 0, 0], np.int32)
    last = np.asarray([1, 2, 3, 4], np.int32)
    run = np.asarray([True, True, False, False])
    limits = np.asarray([2, 10, 0, 0], np.int32)  # slot 0 done after 2
    z_i = np.zeros((4,), np.int32)
    z_f = np.zeros((4,), np.float32)
    toks, done, emitted = engine.decode(
        block_table, lengths, last, run, limits, z_f, z_i,
        np.ones((4,), np.float32), np.full((4,), -1, np.int32), z_i,
    )
    assert toks.shape == (4, 4)
    # Slot 0 emits waves 0-1 then freezes; slot 1 emits all 4 waves.
    np.testing.assert_array_equal(emitted[:, 0], [True, True, False, False])
    np.testing.assert_array_equal(emitted[:, 1], [True] * 4)
    np.testing.assert_array_equal(done[:, 0], [False, True, False, False])
    # Inactive slots never emit.
    assert not emitted[:, 2].any() and not emitted[:, 3].any()
    assert engine.device_gets == 1 and engine.decode_dispatches == 1
    assert engine.decode_waves == 4


def test_recording_engine_carries_its_scan_state_across_dispatches():
    """As the compiled program does: a dispatch takes ``lengths`` and
    ``last_tok`` from the host only where ``fresh`` says so, and a slot
    the dispatch before finished stays frozen though the host's mask
    still names it — the scheduler enqueues before it has harvested."""
    engine = _tiny_engine()
    block_table = np.zeros((4, 8), np.int32)
    z_i, z_f = np.zeros((4,), np.int32), np.zeros((4,), np.float32)
    knobs = (z_f, z_i, np.ones((4,), np.float32), np.full((4,), -1, np.int32), z_i)
    run = np.asarray([True, True, False, False])
    limits = np.asarray([1, 10, 0, 0], np.int32)     # slot 0 ends in the first
    first = engine.decode_dispatch(
        block_table, z_i, np.asarray([1, 2, 3, 4], np.int32), run, limits,
        *knobs, np.ones((4,), bool))
    # The host's mirrors are stale (still 0 / the old tokens) and say so.
    second = engine.decode_dispatch(
        block_table, z_i, np.asarray([1, 2, 3, 4], np.int32), run, limits,
        *knobs, np.zeros((4,), bool))
    assert engine.decode_dispatches - engine.device_gets == 2
    toks1, done1, emitted1 = engine.harvest(first)
    toks2, _, emitted2 = engine.harvest(second)
    np.testing.assert_array_equal(emitted1[0], [True, True, False, False])
    np.testing.assert_array_equal(done1[0], [True, False, False, False])
    np.testing.assert_array_equal(emitted2[0], [False, True, False, False])
    assert toks2[0, 1] == (toks1[0, 1] + 1) % 7      # continued, not restarted
    np.testing.assert_array_equal(engine.carry[0], [1, 2, 0, 0])
    # A fresh slot takes the host's values whatever the carry holds.
    third = engine.decode_dispatch(
        block_table, np.asarray([5, 0, 0, 0], np.int32),
        np.asarray([3, 0, 0, 0], np.int32), run, np.full((4,), 10, np.int32),
        *knobs, np.asarray([True, False, False, False]))
    toks3, _, emitted3 = engine.harvest(third)
    np.testing.assert_array_equal(emitted3[0], [True, True, False, False])
    assert toks3[0, 0] == 4
    np.testing.assert_array_equal(engine.carry[0], [6, 3, 0, 0])


def test_the_lattice_is_driven_with_two_dispatches_in_flight():
    """The drive that sees all nine states runs the scheduler's real
    order: a decode dispatch is enqueued while the one before it is
    unharvested, and the drained ticks of the eviction phase are the only
    ones that harvest first."""
    engine = _tiny_engine()
    outstanding = []
    dispatch = engine.decode_dispatch

    def counted(*args):
        outstanding.append(engine.decode_dispatches - engine.device_gets)
        return dispatch(*args)

    engine.decode_dispatch = counted
    _, findings, states = enumerate_admission_lattice(engine)
    assert findings == [] and REQUIRED_LATTICE_STATES <= states
    assert set(outstanding) == {0, 1}
    assert outstanding.count(1) > outstanding.count(0)


# -- target hygiene ----------------------------------------------------------

def test_targets_declare_ceilings_with_headroom():
    """Each non-demo target's RKT605 ceilings sit ABOVE its committed
    budget prediction (they gate structure, the budget gates drift) —
    and the demo target's sit below (it must fire)."""
    from rocket_tpu.analysis.budgets import SERVE_DIR, load_budget
    from rocket_tpu.analysis.serve_audit import SERVE_TARGETS

    budget_dir = os.path.join(REPO, SERVE_DIR)
    for name, target in SERVE_TARGETS.items():
        if target.demo:
            continue
        record = load_budget(budget_dir, name)
        assert target.itl_ceiling_us > record["predicted_itl_us"]
        assert target.ttft_ceiling_us > record["predicted_ttft_us"]


def test_recording_engine_replace_keeps_dataclass_contract():
    """WaveObservation is a frozen record — replace() derives variants
    (the tests and any future dedup rely on value semantics)."""
    obs = _obs("decode", "first_admit", wave_signature([1]))
    other = replace(obs, state="full_slots")
    assert other.state == "full_slots" and other.signature == obs.signature
