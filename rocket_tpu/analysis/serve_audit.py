"""serve_audit — static audit of the serving path (retrace surface,
latency roofline, HBM fit, donation/sync), before any request is served.

``rocket_tpu.serve``'s invariants — exactly two compiled programs with
zero retraces across every admission state, pool-bounded HBM, one small
host transfer per wave — are verified dynamically by the engine's trace
counters and the serve smoke. This pass proves the same properties
**statically**, on the fake-mesh harness every other auditor already
uses:

1. the REAL decode-wave and prefill-chunk step functions
   (:func:`rocket_tpu.serve.engine.build_decode_wave` /
   :func:`~rocket_tpu.serve.engine.build_prefill_step` — the exact
   functions the live engine jits) are AOT-compiled from abstract
   inputs (:func:`~rocket_tpu.serve.engine.abstract_wave_inputs`) — no
   params materialize, no FLOPs run;
2. the REAL host :class:`~rocket_tpu.serve.scheduler.Scheduler` is
   driven through the full admission-state lattice (empty, partial and
   full slots, EOS mid-wave, eviction + resume, refill, multi-chunk and
   final-partial-chunk prefill) against a *recording* engine, and every
   wave's input signature is hashed — all states must produce ONE
   signature per program, and every decode signature must match the
   compiled program's abstract signature exactly (RKT601);
3. both programs are priced with the sched_audit roofline
   (:func:`~rocket_tpu.analysis.sched_audit.predict_compiled`): the
   decode wave's predicted time IS the inter-token latency, the prefill
   chunk time times the chunk schedule (plus the first wave) is the
   TTFT — per device kind, gated against the analytic HBM floor
   (RKT602) and per-target ceilings (RKT605);
4. the engine's steady-state HBM (pool + master params + compiled
   temps) is compared against the device kind's capacity with the max
   (slots, blocks) frontier reported (RKT603);
5. the compiled modules' ``input_output_alias`` maps prove both pool
   buffers are donated through both programs with no hidden copies, and
   the non-aliased output (the driver's one ``device_get``) stays
   within the host-transfer budget (RKT604);
6. the record is gated against checked-in budgets
   (``tests/fixtures/budgets/serve/``, RKT606).

CLI: ``python -m rocket_tpu.analysis serve`` audits the repo's builtin
serve configs (the self-gate CI runs via ``scripts/check.sh``). Library
entries: :func:`audit_serving` for user configs,
:func:`enumerate_admission_lattice` for the scheduler-side proof alone.
docs/analysis.md has the rule table and the capacity-frontier math.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax

from rocket_tpu.analysis.findings import Finding
from rocket_tpu.analysis.rules.serve_rules import (
    check_decode_roofline,
    check_hbm_fit,
    check_latency_ceilings,
    check_retrace_surface,
    check_serve_donation,
)
from rocket_tpu.analysis.sched_audit import DEFAULT_DEVICE_KIND, predict_compiled
from rocket_tpu.utils.perf import device_spec

__all__ = [
    "WaveObservation",
    "RecordingEngine",
    "enumerate_admission_lattice",
    "REQUIRED_LATTICE_STATES",
    "wave_signature",
    "CompiledServeProgram",
    "compile_serve_programs",
    "decode_floor_bytes",
    "fused_decode_bytes",
    "estimate_serve_hbm",
    "audit_serving",
    "ServeAuditReport",
    "SERVE_TARGETS",
    "run_serve_target",
]


# -- wave signatures ---------------------------------------------------------


def wave_signature(args: Sequence) -> Tuple:
    """Hashable trace signature of one compiled-step call's inputs.

    Arrays contribute ``(shape, dtype)`` — the aval, exactly what keys
    jax's compile cache. Python/numpy scalars contribute their type AND
    value: a python value in a wave signature is the retrace surface
    (static shape dependence retraces per value; a bare scalar
    weak-type-promotes), so the signature must distinguish values to
    surface it.
    """
    leaves = []
    for leaf in jax.tree_util.tree_leaves(list(args)):
        if isinstance(leaf, (bool, int, float, np.integer, np.floating)):
            leaves.append(("pyval", type(leaf).__name__, repr(leaf)))
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            leaves.append(("array", tuple(leaf.shape), str(leaf.dtype)))
        else:
            leaves.append(("obj", type(leaf).__name__))
    return tuple(leaves)


def _abstract_signature(abs_args: Sequence) -> Tuple:
    """The compiled program's signature in the same vocabulary, from the
    ``ShapeDtypeStruct`` argument tuple."""
    return tuple(
        ("array", tuple(leaf.shape), str(np.dtype(leaf.dtype)))
        if not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key)
        else ("array", tuple(leaf.shape), "prng_key")
        for leaf in jax.tree_util.tree_leaves(list(abs_args))
    )


# -- the admission-state lattice ---------------------------------------------


@dataclass(frozen=True)
class WaveObservation:
    """One recorded compiled-step call from the lattice drive."""

    program: str        # "decode" | "prefill"
    state: str          # lattice state label at call time
    signature: Tuple


#: The scheduler-supplied decode-wave inputs, in call order — the
#: arguments after (params, k_pages, v_pages) and before the PRNG key.
#: One definition shared by :class:`RecordingEngine.decode_dispatch`'s
#: recording and the mirror-vs-compiled-aval cross-check in
#: :func:`audit_serving`, so a future arity change cannot silently
#: vacuate the check.
SCHEDULER_WAVE_ARGS = (
    "block_table", "lengths", "last_tok", "run_mask", "limits",
    "temp", "top_k", "top_p", "eos", "seeds", "fresh",
)

#: State labels :func:`enumerate_admission_lattice` must observe for the
#: proof to be NON-VACUOUS — a lattice drive that never evicted proves
#: nothing about eviction. The completeness test pins this set.
REQUIRED_LATTICE_STATES = frozenset({
    "first_admit",          # empty engine -> one slot
    "partial_slots",        # 0 < active < max_slots
    "full_slots",           # every slot occupied
    "multi_chunk_prefill",  # a prompt spanning several prefill chunks
    "final_partial_chunk",  # the tail chunk with valid < prefill_chunk
    "eos_mid_wave",         # one slot finishes while others keep running
    "refill",               # a freed slot re-admits from the queue
    "eviction",             # pool exhaustion preempts the youngest
    "post_evict_resume",    # the evicted request re-admits and resumes
})


class RecordingEngine:
    """A stand-in :class:`~rocket_tpu.serve.engine.SlotEngine` that
    RECORDS every compiled-step call's input signature instead of
    dispatching to a device.

    The scheduler's host logic (mirror mutation, admission, eviction,
    two dispatches in flight) runs for real; only the device half
    is simulated: ``decode_dispatch`` replays the k-wave scan's carry
    exactly the way the compiled program does (per-wave ``done`` from
    ``lengths + active >= limits``, the run mask freezing mid-scan
    finishes) and KEEPS it for the next dispatch, which takes the host's
    ``lengths`` / ``last_tok`` only where ``fresh`` says so — the
    scheduler enqueues that dispatch before it has harvested this one.
    ``force_eos`` lets the lattice driver finish a chosen
    slot early — the EOS-mid-wave state.
    """

    def __init__(self, spec, *, max_slots: int, max_blocks_per_seq: int,
                 prefill_chunk: int, max_seq_len: int,
                 waves_per_dispatch: int = 1) -> None:
        from types import SimpleNamespace

        self.spec = spec
        self.max_slots = int(max_slots)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.prefill_chunk = int(prefill_chunk)
        self.waves_per_dispatch = int(waves_per_dispatch)
        # The scheduler only reads model.config.max_seq_len.
        self.model = SimpleNamespace(
            config=SimpleNamespace(max_seq_len=int(max_seq_len))
        )
        self.decode_traces = 1
        self.prefill_traces = 1
        self.decode_waves = 0
        self.decode_dispatches = 0
        self.device_gets = 0
        self.harvest_wait_s = 0.0
        self.prefill_chunks = 0
        self.observations: list[WaveObservation] = []
        #: The scan's last (lengths, last_tok, run): the device's carry.
        self.carry = (
            np.zeros((self.max_slots,), np.int32),
            np.zeros((self.max_slots,), np.int32),
            np.zeros((self.max_slots,), bool),
        )
        self.state = "init"
        #: slot -> remaining waves before a forced EOS finish.
        self.force_eos: dict[int, int] = {}

    # -- the SlotEngine surface the Scheduler drives -----------------------

    def _record(self, program: str, args: Sequence) -> None:
        self.observations.append(WaveObservation(
            program=program, state=self.state,
            signature=self._signature(program, args),
        ))

    def _signature(self, program: str, args: Sequence) -> Tuple:
        return wave_signature(args)

    def decode_dispatch(self, block_table, lengths, last_tok, run_mask,
                        limits, temp, top_k, top_p, eos, seeds, fresh=None):
        from rocket_tpu.serve.engine import WaveHandle

        if fresh is None:
            fresh = np.ones((self.max_slots,), bool)
        seq = self.decode_dispatches
        self.decode_dispatches += 1
        self.decode_waves += self.waves_per_dispatch
        self.last_dispatch_at = time.perf_counter()
        args = (block_table, lengths, last_tok, run_mask, limits,
                temp, top_k, top_p, eos, seeds, fresh)
        assert len(args) == len(SCHEDULER_WAVE_ARGS)
        self._record("decode", args)
        held_lengths, held_last, held_run = self.carry
        lengths = np.where(fresh, lengths, held_lengths)
        last = np.where(fresh, last_tok, held_last)
        run = np.asarray(run_mask) & (fresh | held_run)
        toks, done, emitted = [], [], []
        for _wave in range(self.waves_per_dispatch):
            valid = run.astype(np.int32)
            nxt = np.where(run, (last + 1) % 7, last).astype(np.int32)
            d = (lengths + valid >= limits) & run
            for slot in list(self.force_eos):
                self.force_eos[slot] -= 1
                if self.force_eos[slot] <= 0 and run[slot]:
                    d[slot] = True
                    del self.force_eos[slot]
            toks.append(nxt)
            done.append(d)
            emitted.append(run.copy())
            lengths = lengths + valid
            last = nxt
            run = run & ~d
        self.carry = (lengths, last, run)
        return WaveHandle(np.stack(toks), np.stack(done), np.stack(emitted),
                          seq=seq)

    def harvest(self, handle):
        self.device_gets += 1
        self.last_harvest_at = time.perf_counter()
        return handle.tokens, handle.done, handle.emitted

    def decode(self, *args):
        """Dispatch-and-wait convenience, mirroring SlotEngine."""
        return self.harvest(self.decode_dispatch(*args))

    def prefill(self, block_table_row, tokens, position, valid,
                slot: Optional[int] = None) -> None:
        # ``slot`` reaches the program as a fixed (1,) int32 only for a
        # model with per-slot state: a value, never a shape.
        self.prefill_chunks += 1
        self._record("prefill", (block_table_row, tokens, position, valid))


class _PyLeakRecordingEngine(RecordingEngine):
    """The seeded-bad engine for the ``badserve`` demo: its decode driver
    passes the python active-slot COUNT into the wave (the classic
    ``int(mask.sum())``-shaped bug — a python value the compiled body
    would bake in as a constant/shape, retracing per distinct value).
    """

    def _signature(self, program: str, args: Sequence) -> Tuple:
        if program == "decode":
            run_mask = args[3]
            args = tuple(args) + (int(np.sum(run_mask)),)
        return wave_signature(args)


def enumerate_admission_lattice(
    engine: RecordingEngine,
    *,
    scheduler=None,
) -> tuple[list[WaveObservation], list[Finding], set]:
    """Drive the REAL scheduler through the full admission lattice.

    Returns ``(observations, findings, states_seen)``. The script is
    sized from the engine's own geometry (slots, blocks, chunk), so one
    driver covers every target: it admits to partial then full
    occupancy, streams a prompt long enough for several prefill chunks
    plus a partial tail, forces one EOS mid-wave, refills the freed
    slot, and shrinks effective pool headroom until the youngest request
    is evicted and later resumes. Findings here are harness-level
    (a state the geometry cannot reach), not rule findings.
    """
    from rocket_tpu.serve.kv_pool import BlockAllocator
    from rocket_tpu.serve.scheduler import Request, Scheduler

    findings: list[Finding] = []
    sched = scheduler or Scheduler(
        engine, BlockAllocator(engine.spec.num_blocks)
    )
    chunk = engine.prefill_chunk
    block_len = engine.spec.block_len
    slots = engine.max_slots
    # Scheduler.submit enforces BOTH the per-slot block context and the
    # model's max_seq_len — bound the harness by the tighter one, or a
    # non-block-multiple max_seq_len crashes the drive mid-audit.
    max_ctx = min(
        engine.max_blocks_per_seq * block_len,
        engine.model.config.max_seq_len,
    )

    def submit(plen, new, **kw):
        # Clamp BOTH knobs so prompt + new always fits the context —
        # the harness must adapt to any legal geometry, not crash on
        # one-block slots or small contexts.
        new = max(1, min(new, max_ctx - 1))
        plen = max(1, min(plen, max_ctx - new))
        req = Request(
            prompt=np.arange(plen, dtype=np.int32) % 7,
            max_new_tokens=new, **kw,
        )
        return sched.submit(req)

    def tick(state: str) -> None:
        engine.state = state
        sched.tick()

    # Generation lengths are sized in BLOCKS, not ticks: every request
    # outlives the whole drive unless finished deliberately (force_eos)
    # — the pipelined scheduler harvests one dispatch behind and scans
    # k waves per dispatch, so a tick-counted workload would drain
    # early on a large ``waves_per_dispatch`` and leave full-occupancy/
    # eviction states unreachable (a vacuous proof).
    long_gen = 2 * block_len + 2

    # 1. empty -> first admission. The prompt spans several prefill
    # chunks and its tail chunk is PARTIAL (P-1 = 2.5 chunks).
    long_prompt = min(2 * chunk + max(chunk // 2, 1) + 1, max_ctx - 4)
    submit(long_prompt, long_gen, temperature=0.7, top_k=3, eos_token_id=5)
    tick("first_admit")
    while not sched.idle and any(
        st is not None and not st.prefill_done for st in sched.slots
    ):
        # Label chunks: the LAST pending chunk is the partial tail.
        st = next(s for s in sched.slots if s is not None)
        remaining = (len(st.ctx) - 1) - st.prefill_pos
        tick("final_partial_chunk" if remaining <= chunk
             else "multi_chunk_prefill")
    tick("partial_slots")

    # 2. fill every slot (mixed sampling knobs — runtime values only).
    for i in range(slots - 1):
        submit(1 + i % 3, long_gen + i, temperature=float(i % 2),
               top_p=0.9 if i % 2 else None,
               eos_token_id=None if i % 2 else 5)
    for _ in range(2 * slots):
        if all(st is not None for st in sched.slots):
            break
        tick("partial_slots")
    if all(st is not None for st in sched.slots):
        tick("full_slots")
    else:
        findings.append(Finding(
            "RKT601", "<serve:lattice>", 0,
            "serve-retrace-surface: lattice harness could not reach "
            "full_slots with this geometry — the proof is vacuous for "
            "full occupancy; widen the pool or shrink max_slots",
        ))

    # 3. EOS mid-wave: finish the first slot early while others run.
    live = [i for i, st in enumerate(sched.slots) if st is not None]
    if live:
        engine.force_eos[live[0]] = 1
        tick("eos_mid_wave")

    # 4. refill the freed slot from the queue — sized to CROSS a block
    # boundary mid-generation (plen 2 starts with one block; the +4
    # tokens past block_len force a table growth), which is what the
    # eviction phase below starves. Two ticks: the EOS finish above is
    # harvested one tick behind its dispatch (pipelining), so the first
    # refill tick discovers the freed slot and the second re-admits
    # into it.
    submit(2, block_len + 4, temperature=0.3)
    tick("refill")
    tick("refill")

    # 5. eviction: hold every free block (re-grabbing any that finishing
    # requests return) so the live slots' table growth exhausts the
    # pool and the youngest active request preempts. Every request was
    # sized to keep generating past several block boundaries, so growth
    # demand keeps arriving no matter how the harvest lag interleaves
    # block frees with the grow phase.
    hold: list[int] = []
    before = sched.preemptions
    for _ in range(8 * block_len):
        if sched.preemptions > before:
            break
        got = sched.allocator.alloc(sched.allocator.num_free)
        if got:
            hold.extend(got)
        tick("eviction")
    if sched.preemptions == before:
        findings.append(Finding(
            "RKT601", "<serve:lattice>", 0,
            "serve-retrace-surface: lattice harness could not trigger an "
            "eviction with this geometry — the proof is vacuous for "
            "preemption; shrink num_blocks or lengthen the workload",
        ))
    if hold:
        sched.allocator.free(hold)

    # 6. the evicted request re-admits and resumes.
    for _ in range(4 * max_ctx):
        if sched.idle:
            break
        tick("post_evict_resume")
    if not sched.idle:
        findings.append(Finding(
            "RKT601", "<serve:lattice>", 0,
            "serve-retrace-surface: lattice harness did not drain — the "
            "post-eviction resume path was not fully observed",
        ))

    states_seen = {obs.state for obs in engine.observations}
    # Backstop: ANY required state the drive never observed leaves the
    # proof vacuous there — a finding, never a silent false-clean.
    # full_slots is excluded because its targeted check above fires
    # exactly when the state is missing (with the remedy attached).
    for missing in sorted(REQUIRED_LATTICE_STATES - states_seen
                          - {"full_slots"}):
        findings.append(Finding(
            "RKT601", "<serve:lattice>", 0,
            "serve-retrace-surface: lattice harness never observed "
            f"required state {missing!r} with this geometry — the "
            "retrace proof is vacuous for that state; adjust "
            "slots/blocks/chunk so the drive can reach it",
        ))
    return engine.observations, findings, states_seen


# -- AOT compilation + facts -------------------------------------------------


@dataclass
class CompiledServeProgram:
    """One AOT-compiled serving program plus the facts the rules consume.

    ``wave_time_us`` / ``wave_hbm_bytes`` are the program's WAVE-LEVEL
    roofline: unique bytes the wave streams (arguments read once +
    outputs written once + temps written-and-read, from the compiled
    module's own memory accounting) against the device's HBM bandwidth,
    vs the module's MXU FLOPs against peak. The per-op schedule record
    (``record``, :func:`~rocket_tpu.analysis.sched_audit.predict_compiled`)
    stays as ATTRIBUTION — its operand+result counting re-reads every
    shared buffer per consumer, which is the right conservatism for
    ranking train-step schedules but overstates one serving wave whose
    params/pool thread through many sequential ops.
    """

    name: str                  # "decode" | "prefill"
    record: dict               # predict_compiled record (attribution)
    wave_time_us: float        # wave-level roofline time
    wave_hbm_bytes: int        # unique bytes one wave streams
    aliased_bytes: int         # input->output aliased bytes (donation)
    non_aliased_output_bytes: int
    temp_bytes: int
    abstract_signature: Tuple
    hlo_text: str = ""


def _compile_program(name, fn, abs_args, donate, device_kind) -> tuple:
    """(CompiledServeProgram | None, findings)."""
    device = device_spec(device_kind)
    try:
        compiled = (
            jax.jit(fn, donate_argnums=tuple(donate))
            .lower(*abs_args)
            .compile()
        )
    except (ValueError, RuntimeError) as exc:
        return None, [Finding(
            "RKT601", "<serve:compile>", 0,
            f"serve-retrace-surface: the {name} program failed to "
            f"AOT-compile: {str(exc).splitlines()[0][:300]}",
        )]
    text = compiled.as_text()
    _scheduled, _ideal, record = predict_compiled(text, device_kind)
    aliased = output = temp = arg = 0
    try:
        stats = compiled.memory_analysis()
        aliased = int(getattr(stats, "alias_size_in_bytes", 0) or 0)
        output = int(getattr(stats, "output_size_in_bytes", 0) or 0)
        temp = int(getattr(stats, "temp_size_in_bytes", 0) or 0)
        arg = int(getattr(stats, "argument_size_in_bytes", 0) or 0)
    except Exception:  # backend without memory analysis
        pass
    if arg or output or temp:
        # Unique traffic: every argument read once, every non-aliased
        # output written once, every temp written and read back.
        wave_bytes = arg + max(0, output - aliased) + 2 * temp
    else:
        wave_bytes = int(record["hbm_bytes_per_step"])
    wave_time_s = max(
        record["flops_per_step"] / device.flops_bf16,
        wave_bytes / device.hbm_bw,
    )
    return CompiledServeProgram(
        name=name, record=record,
        wave_time_us=round(wave_time_s * 1e6, 3),
        wave_hbm_bytes=int(wave_bytes),
        aliased_bytes=aliased,
        non_aliased_output_bytes=max(0, output - aliased),
        temp_bytes=temp,
        abstract_signature=_abstract_signature(abs_args),
        hlo_text=text,
    ), []


def compile_serve_programs(
    model,
    spec,
    *,
    max_slots: int,
    max_blocks_per_seq: int,
    prefill_chunk: int,
    waves_per_dispatch: int = 1,
    device_kind: str = DEFAULT_DEVICE_KIND,
    donate: bool = True,
    abs_inputs=None,
) -> tuple[list[CompiledServeProgram], list[Finding]]:
    """AOT-compile the REAL serving programs from abstract inputs and
    price them with the roofline. Three programs when the target scans
    k > 1 waves per dispatch: ``decode`` (the REAL k-wave scan — the
    retrace/donation/host-transfer facts audit what actually runs),
    ``decode_wave`` (a single-wave compile of the same body — the
    per-wave attribution the roofline prices, free of while-loop
    body-counting ambiguity), and ``prefill``. At k=1 ``decode`` IS the
    single wave and ``decode_wave`` is omitted. ``donate=False``
    compiles without pool donation (the seeded-bad demo — RKT604's true
    positive). ``abs_inputs`` takes a precomputed
    :func:`~rocket_tpu.serve.engine.abstract_wave_inputs` pair so a
    caller that also needs the cast param avals evaluates them once."""
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    if abs_inputs is None:
        abs_inputs = abstract_wave_inputs(
            model, spec, max_slots=max_slots,
            max_blocks_per_seq=max_blocks_per_seq,
            prefill_chunk=prefill_chunk,
        )
    decode_args, prefill_args = abs_inputs
    k = int(waves_per_dispatch)
    to_compile = [
        ("decode", build_decode_wave(model, waves=k), decode_args,
         DECODE_DONATE),
        ("prefill", build_prefill_step(model), prefill_args,
         PREFILL_DONATE),
    ]
    if k > 1:
        to_compile.insert(1, (
            "decode_wave", build_decode_wave(model, waves=1), decode_args,
            DECODE_DONATE,
        ))
    programs: list[CompiledServeProgram] = []
    findings: list[Finding] = []
    for name, fn, args, donate_argnums in to_compile:
        prog, prog_findings = _compile_program(
            name, fn, args, donate_argnums if donate else (), device_kind
        )
        findings.extend(prog_findings)
        if prog is not None:
            programs.append(prog)
    return programs, findings


# -- roofline / HBM math -----------------------------------------------------


def _tree_bytes(tree) -> int:
    """Total bytes of a pytree of avals/arrays."""
    return int(sum(
        int(np.prod(leaf.shape or (1,))) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    ))


def decode_floor_bytes(
    spec,
    params_bytes: int,
    *,
    max_slots: int,
    max_blocks_per_seq: int,
) -> int:
    """Analytic HBM floor of ONE decode wave: master params (read) +
    the active-KV gather (every slot's mapped blocks, of every pool
    array: K and V, or the one latent) + the one-new-row-per-slot pool
    scatter + every slot's per-slot state read and written (a model with
    state layers; ``KVPoolSpec.state_bytes``). What a perfectly fused
    wave streams — the RKT602 denominator."""
    row = sum(spec.lanes) * np.dtype(spec.dtype).itemsize
    kv_gather = (
        spec.num_layers * max_slots * max_blocks_per_seq
        * spec.block_len * row
    )
    scatter = spec.num_layers * max_slots * row
    return int(params_bytes + kv_gather + scatter + 2 * spec.state_bytes)


def fused_decode_bytes(
    spec,
    params_bytes: int,
    *,
    max_slots: int,
    max_blocks_per_seq: int,
    vocab_size: int,
) -> int:
    """The fused-kernel byte model of ONE decode wave: the analytic
    floor (params + active-pages-only gather + per-slot scatter — the
    pallas paged-decode kernel streams exactly the mapped pages, no
    transient ``(S, MB*BL, Hkv*D)`` context) plus the wave's real
    activation traffic: the ``(S, V)`` logits written by the head and
    re-read (several times — sort-based top-k/top-p filtering is always
    compiled in, the knobs being runtime arrays) by the sampling core,
    in f32. This is what the compiled wave moves on a TPU where the
    kernel engages — the RKT602 re-pricing of ISSUE 11."""
    floor = decode_floor_bytes(
        spec, params_bytes, max_slots=max_slots,
        max_blocks_per_seq=max_blocks_per_seq,
    )
    logits = 4 * max_slots * vocab_size * 4  # f32, head write + ~3 reads
    return int(floor + logits)


def estimate_serve_hbm(
    spec,
    params_bytes: int,
    programs: Sequence[CompiledServeProgram],
    device,
    *,
    max_blocks_per_seq: int,
) -> dict:
    """The engine's steady-state HBM record + the (slots, blocks)
    frontier that WOULD fit the device kind — RKT603's fact.

    Steady state holds the pool, the master-cast params and the larger
    of the two programs' temp buffers (the programs never run
    concurrently — the engine is a serial tick loop).
    """
    temp = max((p.temp_bytes for p in programs), default=0)
    total = spec.pool_bytes + params_bytes + temp
    capacity = int(device.hbm_bytes) if device is not None else 0
    # The per-slot state is held whatever the blocks: it comes off the
    # room the blocks could fill.
    headroom = capacity - params_bytes - temp - spec.state_bytes
    max_blocks = max(0, headroom // spec.block_bytes) if capacity else 0
    frontier = {
        "max_num_blocks": int(max_blocks),
        # Full-context slots: each needs max_blocks_per_seq blocks, and
        # block 0 stays reserved.
        "max_full_context_slots": int(
            max(0, (max_blocks - 1) // max(max_blocks_per_seq, 1))
        ),
    }
    return {
        "pool_bytes": int(spec.pool_bytes),
        "params_bytes": int(params_bytes),
        "temp_bytes": int(temp),
        "total_bytes": int(total),
        "capacity_bytes": capacity,
        "device_kind": getattr(device, "kind", None),
        "fit_fraction": round(total / capacity, 4) if capacity else None,
        "frontier": frontier,
    }


# -- the orchestrator --------------------------------------------------------


@dataclass
class ServeAuditReport:
    """Findings plus the record the budget gate (and BENCH emission)
    consumes."""

    label: str
    findings: list = field(default_factory=list)
    observations: list = field(default_factory=list)
    states_seen: set = field(default_factory=set)
    programs: list = field(default_factory=list)
    record: dict = field(default_factory=dict)

    @property
    def clean(self) -> bool:
        return not self.findings


def audit_serving(
    model,
    serve_config,
    *,
    device_kind: str = DEFAULT_DEVICE_KIND,
    ref_prompt_len: int = 64,
    itl_ceiling_us: float = 0.0,
    ttft_ceiling_us: float = 0.0,
    overfetch_ratio: float = 16.0,
    host_bytes_max: int = 64 << 10,
    donate: bool = True,
    engine_factory: Optional[Callable] = None,
    label: str = "serve",
) -> ServeAuditReport:
    """Audit ``ServeEngine(model, params, serve_config)``'s serving path
    without building an engine or materializing params.

    ``serve_config`` is a :class:`~rocket_tpu.serve.api.ServeConfig`;
    the pool/slot sizing resolves through the SAME
    ``ServeConfig.resolve`` the live engine uses. ``ref_prompt_len``
    anchors the TTFT prediction (TTFT depends on prompt length; the
    budget record pins one reference). ``engine_factory`` overrides the
    lattice's recording engine (the seeded-bad demo injects its
    python-leaking variant). Pure abstract evaluation + XLA compilation
    — no FLOPs run, no pool allocates, no TPU required.
    """
    device = device_spec(device_kind)
    if device is None:
        raise ValueError(
            f"serve_audit: unknown device kind {device_kind!r} — add it "
            "to rocket_tpu.utils.perf.DEVICE_SPECS"
        )
    spec, mb, _num_blocks, waves = serve_config.resolve(model.config)
    report = ServeAuditReport(label=label)
    findings: list[Finding] = []

    # 1/5. the compiled programs + donation/alias facts — the REAL
    # k-wave scan the engine dispatches, plus a single-wave compile for
    # per-wave attribution when k > 1. The abstract inputs are evaluated
    # ONCE here: the compile harness consumes them, and their cast param
    # avals (decode arg 0) are the params-bytes fact the roofline floor
    # reads below.
    from rocket_tpu.serve.engine import abstract_wave_inputs

    abs_inputs = abstract_wave_inputs(
        model, spec, max_slots=serve_config.max_slots,
        max_blocks_per_seq=mb, prefill_chunk=serve_config.prefill_chunk,
    )
    programs, compile_findings = compile_serve_programs(
        model, spec,
        max_slots=serve_config.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=serve_config.prefill_chunk,
        waves_per_dispatch=waves,
        device_kind=device_kind, donate=donate, abs_inputs=abs_inputs,
    )
    findings.extend(compile_findings)
    report.programs = programs
    by_name = {p.name: p for p in programs}

    # 2. the admission-state lattice against the REAL scheduler.
    factory = engine_factory or RecordingEngine
    engine = factory(
        spec, max_slots=serve_config.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=serve_config.prefill_chunk,
        max_seq_len=model.config.max_seq_len,
        waves_per_dispatch=waves,
    )
    observations, lattice_findings, states_seen = \
        enumerate_admission_lattice(engine)
    report.observations = observations
    report.states_seen = states_seen
    findings.extend(lattice_findings)
    findings.extend(check_retrace_surface(observations, label=label))

    # The scheduler's recorded wave signature must equal the compiled
    # program's abstract signature over the scheduler-supplied inputs
    # (decode args after params/pools/key) — host mirrors and compiled
    # avals drifting apart IS a retrace.
    decode = by_name.get("decode")
    if decode is not None and observations:
        sched_sigs = {
            obs.signature for obs in observations if obs.program == "decode"
        }
        # abstract decode args: params(pytree), k, v, <the scheduler
        # mirrors, SCHEDULER_WAVE_ARGS order>, key — compare the mirror
        # slice only. Signatures carrying non-array leaves are the
        # python-leak case check_retrace_surface already flagged above;
        # a pure-array signature of ANY other arity is mirror drift.
        n_sched = len(SCHEDULER_WAVE_ARGS)
        abs_tail = decode.abstract_signature[-(n_sched + 1):-1]
        for sig in sorted(sched_sigs):
            if any(leaf[0] != "array" for leaf in sig):
                continue
            if tuple(sig) != tuple(abs_tail):
                findings.append(Finding(
                    "RKT601", f"<serve:{label}>", 0,
                    "serve-retrace-surface: the scheduler's host mirrors "
                    f"({sig}) do not match the compiled decode wave's "
                    f"input avals ({abs_tail}) — the first wave would "
                    "retrace the engine's compiled program",
                ))

    # 3. latency roofline. Per-wave attribution comes from the
    # single-wave compile ("decode_wave" at k > 1, else "decode"
    # itself); the REAL k-wave program keeps the donation/signature/
    # host-transfer facts. Predicted ITL is per TOKEN — the k-wave scan
    # amortizes the host dispatch, it does not change per-wave device
    # time — priced under the FUSED-KERNEL byte model (active-pages-only
    # gather + logits/sampling traffic) wherever the pallas paged-decode
    # kernel engages on the audited device kind, and under the compiled
    # XLA program's unique-bytes model otherwise.
    from rocket_tpu.ops.paged_attention import paged_decode_supported

    params_bytes = _tree_bytes(abs_inputs[0][0])
    floor = decode_floor_bytes(
        spec, params_bytes,
        max_slots=serve_config.max_slots, max_blocks_per_seq=mb,
    )
    wave = by_name.get("decode_wave") or decode
    kernel_engages = paged_decode_supported(
        spec.block_len, spec.head_dim, np.dtype(spec.dtype).itemsize,
        lanes=spec.lanes[0],
    )
    fused = fused_decode_bytes(
        spec, params_bytes,
        max_slots=serve_config.max_slots, max_blocks_per_seq=mb,
        vocab_size=int(model.config.vocab_size),
    )
    itl_us = None
    priced_bytes = None
    if wave is not None:
        if kernel_engages:
            wave_s = max(
                wave.record["flops_per_step"] / device.flops_bf16,
                fused / device.hbm_bw,
            )
            itl_us = round(wave_s * 1e6, 3)
            priced_bytes = fused
        else:
            itl_us = wave.wave_time_us
            priced_bytes = wave.wave_hbm_bytes
    prefill = by_name.get("prefill")
    chunk_us = prefill.wave_time_us if prefill else None
    ttft_us = None
    if itl_us is not None and chunk_us is not None:
        # The first token is PRODUCED after one wave but only OBSERVED
        # after the whole first k-wave dispatch returns — raising k
        # trades TTFT for dispatch amortization.
        chunk = serve_config.prefill_chunk
        n_chunks = max(0, -(-(ref_prompt_len - 1) // chunk))
        ttft_us = round(n_chunks * chunk_us + waves * itl_us, 3)
    record: dict[str, Any] = {
        "device_kind": device.kind,
        "model_family": label,
        "max_slots": int(serve_config.max_slots),
        "num_blocks": int(spec.num_blocks),
        "block_len": int(spec.block_len),
        "prefill_chunk": int(serve_config.prefill_chunk),
        "waves_per_dispatch": int(waves),
        "ref_prompt_len": int(ref_prompt_len),
        "predicted_itl_us": itl_us,
        "prefill_chunk_us": chunk_us,
        "predicted_ttft_us": ttft_us,
        "itl_floor_us": round(floor / device.hbm_bw * 1e6, 3),
        "decode_floor_bytes": int(floor),
        "byte_model": "fused-paged" if kernel_engages else "compiled-xla",
        "decode_traffic_bytes": (
            int(priced_bytes) if priced_bytes else None
        ),
        "fused_decode_bytes": int(fused),
        "xla_traffic_bytes": (
            wave.wave_hbm_bytes if wave else None
        ),
        "overfetch_ratio": (
            round(wave.wave_hbm_bytes / floor, 2)
            if wave and floor else None
        ),
        # The one device_get fetches the whole k-wave dispatch's output;
        # per-wave is the k-normalized figure so the metric stays
        # comparable across targets with different k.
        "host_bytes_per_dispatch": (
            decode.non_aliased_output_bytes if decode else None
        ),
        "host_bytes_per_wave": (
            round(decode.non_aliased_output_bytes / waves, 1)
            if decode else None
        ),
        "programs": {
            p.name: {
                "wave_time_us": p.wave_time_us,
                "wave_hbm_bytes": p.wave_hbm_bytes,
                "scheduled_time_us": p.record["predicted_step_time_us"],
                "flops": p.record["flops_per_step"],
                "bound": p.record["bound"],
                "n_ops": p.record["n_ops"],
            }
            for p in programs
        },
        "lattice": {
            "decode_signatures": len({
                o.signature for o in observations if o.program == "decode"
            }),
            "prefill_signatures": len({
                o.signature for o in observations if o.program == "prefill"
            }),
            "states": sorted(states_seen),
            "waves": sum(1 for o in observations if o.program == "decode"),
            "chunks": sum(1 for o in observations if o.program == "prefill"),
        },
    }
    if wave is not None:
        # RKT602 audits the COMPILED single-wave program's traffic — the
        # XLA gather path every backend can fall back to. The fused
        # kernel's modeled bytes sit near the floor by construction;
        # what can regress (lost fusion, a widened transient, a fat pool
        # dtype) shows up in the compiled program.
        findings.extend(check_decode_roofline(
            wave.wave_hbm_bytes, floor, overfetch_ratio=overfetch_ratio,
            label=label,
        ))

    # 4. HBM fit + frontier.
    hbm = estimate_serve_hbm(
        spec, params_bytes, programs, device, max_blocks_per_seq=mb,
    )
    record["hbm"] = hbm
    record["hbm_total_bytes"] = hbm["total_bytes"]
    findings.extend(check_hbm_fit(hbm, label=label))

    # 5. donation / host-transfer.
    findings.extend(check_serve_donation(
        programs, spec.pool_bytes, host_bytes_max=host_bytes_max,
        label=label,
    ))

    # RKT605 ceilings.
    findings.extend(check_latency_ceilings(
        record, itl_ceiling_us=itl_ceiling_us,
        ttft_ceiling_us=ttft_ceiling_us, label=label,
    ))

    report.findings = findings
    report.record = record
    return report


# -- builtin targets ---------------------------------------------------------


@dataclass(frozen=True)
class ServeTarget:
    """One self-gate serve configuration the CLI audits."""

    name: str
    #: () -> (model, ServeConfig)
    build: Callable[[], tuple]
    device_kind: str = DEFAULT_DEVICE_KIND
    ref_prompt_len: int = 64
    #: RKT605 ceilings (us; 0 disables) — predictions with headroom, so
    #: only a structural regression fails CI while the RKT606 budget
    #: tracks drift at 10%.
    itl_ceiling_us: float = 0.0
    ttft_ceiling_us: float = 0.0
    overrides: Mapping[str, Any] = field(default_factory=dict)
    demo: bool = False


def _tiny_serve_parts():
    """The `python -m rocket_tpu.serve --config tiny` pairing."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve.api import ServeConfig

    config = TransformerConfig(
        vocab_size=128, max_seq_len=128, dim=64, num_layers=2,
        num_heads=4, dropout=0.0,
    )
    return TransformerLM(config), ServeConfig(
        max_slots=4, block_len=16, prefill_chunk=16,
    )


def _charlm_serve_parts():
    """EXACTLY bench.py's serve_summary config (charlm_256) so the
    BENCH calibration leg compares the prediction against the measured
    serve record of the same engine."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve.api import ServeConfig

    config = TransformerConfig(
        vocab_size=128, max_seq_len=256, dim=256, num_layers=6,
        num_heads=4, dropout=0.0, activation_dtype="bfloat16",
    )
    return TransformerLM(config), ServeConfig(
        max_slots=8, block_len=16, prefill_chunk=32, max_model_len=256,
        decode_waves_per_dispatch=4,
    )


def _gpt2_geom_serve_parts():
    """GPT-2 geometry at audit scale: 768-wide heads-of-64 with GQA
    (num_kv_heads < num_heads) and rope, 2 layers so the AOT compile
    stays in seconds — exercises the grouped-query gather path and a
    realistically wide vocab head."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve.api import ServeConfig

    config = TransformerConfig(
        vocab_size=8192, max_seq_len=512, dim=768, num_layers=2,
        num_heads=12, num_kv_heads=4, pos_embedding="rope",
        dropout=0.0, activation_dtype="bfloat16",
    )
    return TransformerLM(config), ServeConfig(
        max_slots=8, block_len=32, prefill_chunk=64, max_model_len=512,
        decode_waves_per_dispatch=4,
    )


def _badserve_parts():
    """Seeded-bad serve config for the true-positive fixtures: a pool
    sized past the device HBM (RKT603) on a tiny model, audited with
    donation disabled (RKT604), unreachable latency ceilings (RKT605)
    and a decode driver leaking the python active-count into the wave
    signature (RKT601 — the _PyLeakRecordingEngine)."""
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve.api import ServeConfig

    config = TransformerConfig(
        vocab_size=128, max_seq_len=128, dim=64, num_layers=2,
        num_heads=4, dropout=0.0,
    )
    # block_bytes = 2*L*BL*Hkv*D*4 = 2*2*16*4*16*4 = 32 KiB;
    # 1.2M blocks ≈ 37 GiB of pool — past any v5e (16 GiB).
    return TransformerLM(config), ServeConfig(
        max_slots=4, block_len=16, prefill_chunk=16,
        num_blocks=1_200_000,
    )


#: name -> target. The default sweep runs the non-demo entries.
#: Ceilings are the current roofline predictions with ~40% headroom —
#: a decode-path regression (lost fusion, widened pool traffic) blows
#: through; cost-model noise does not. Calibrated in
#: tests/test_serve_audit.py against the committed budgets.
SERVE_TARGETS: dict[str, ServeTarget] = {}


def _register_targets():
    for target in (
        # Ceilings = today's fused-byte-model roofline predictions
        # (tiny 1.2/6.9us, charlm 27/419us, gpt2_geom 58/414us on v5e)
        # + ~40-50% headroom: cost-model noise passes, a structural
        # decode-path regression (the kernel's active-pages byte model
        # widening back toward the XLA gather's transient) does not.
        ServeTarget(
            name="tiny",
            build=_tiny_serve_parts,
            ref_prompt_len=48,
            itl_ceiling_us=2.0,
            ttft_ceiling_us=11.0,
        ),
        ServeTarget(
            name="charlm",
            build=_charlm_serve_parts,
            ref_prompt_len=64,
            itl_ceiling_us=42.0,
            ttft_ceiling_us=600.0,
        ),
        ServeTarget(
            name="gpt2_geom",
            build=_gpt2_geom_serve_parts,
            ref_prompt_len=128,
            itl_ceiling_us=85.0,
            ttft_ceiling_us=600.0,
        ),
        ServeTarget(
            name="badserve",
            build=_badserve_parts,
            ref_prompt_len=48,
            itl_ceiling_us=1.0,
            ttft_ceiling_us=1.0,
            overrides={
                "donate": False,
                "engine_factory": _PyLeakRecordingEngine,
            },
            demo=True,
        ),
    ):
        SERVE_TARGETS[target.name] = target


_register_targets()


def run_serve_target(target: ServeTarget) -> ServeAuditReport:
    model, serve_config = target.build()
    return audit_serving(
        model, serve_config,
        device_kind=target.device_kind,
        ref_prompt_len=target.ref_prompt_len,
        itl_ceiling_us=target.itl_ceiling_us,
        ttft_ceiling_us=target.ttft_ceiling_us,
        label=target.name,
        **dict(target.overrides),
    )
