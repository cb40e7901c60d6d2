"""sched_audit — static roofline, HLO-schedule & comm-overlap audit.

``shard_audit`` prices collective *bytes*; this pass prices *time*. The
low-MFU configs (resnet50 0.27, charlm 0.28, moe 0.39 vs gpt2_350m at
0.60) are indistinguishable from the byte counts alone: compute-bound,
memory-bound and exposed-communication steps all show the same traffic.
Answering "where does the step time go" today costs a hardware run and a
profiler trace; this pass answers it **before any run**, on the same
fake-mesh AOT compile the SPMD auditor already does:

1. the real train/eval step is AOT-compiled under a fake CPU mesh
   (:func:`~rocket_tpu.analysis.shard_audit.aot_compile_step` — the
   shared harness);
2. the optimized HLO's instruction sequence (``is_scheduled=true`` —
   the text order IS the schedule) is parsed into a dependency DAG with
   per-op FLOPs, HBM bytes and collective bytes;
3. each op gets a roofline cost against the target device kind's peak
   tables (:func:`rocket_tpu.utils.perf.device_spec` — MXU FLOPs, HBM
   bandwidth, ICI bandwidth) and a two-stream simulation (compute
   stream + collective stream) attributes the predicted step time to
   compute-bound vs memory-bound vs exposed (non-overlapped)
   communication;
4. a second, ideal-overlap simulation of the same DAG separates
   *structural* exposure (a collective feeding the very next op) from
   *schedulable* exposure (independent compute existed to hide it) —
   the RKT501 signal;
5. pallas_call block shapes are collected from the traced jaxpr (the
   kernels trace abstractly on any backend, with the tuned-config
   lookup pinned to the TARGET device kind so table entries resolve as
   they would on the audited hardware) and checked against the device
   VMEM budget and tile alignment (RKT504).

The predicted numbers are a COST MODEL, not a clock: good enough to
rank schedules, attribute time, and gate regressions (RKT506 budgets,
``tests/fixtures/budgets/sched/``); ``bench.py`` folds the predicted vs
measured calibration error into BENCH_DETAIL.json so model/reality
drift is itself a tracked number.

CLI: ``python -m rocket_tpu.analysis sched`` audits the repo's own
canonical (model, rule-set, mesh) pairings (the self-gate CI runs via
``scripts/check.sh``). Library entries: :func:`audit_schedule` for user
steps, :func:`predict_compiled` for an already-compiled step.
docs/analysis.md has the cost model and the rule table.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from rocket_tpu.analysis.findings import Finding
from rocket_tpu.analysis.rules.sched_rules import (
    check_convoys,
    check_exposed_comm,
    check_memory_bound,
    check_mfu_floor,
    check_pallas,
)
from rocket_tpu.analysis.shard_audit import (
    COLLECTIVE_KINDS,
    _DTYPE_BYTES,
    _GROUPS_IOTA_RE,
    _GROUPS_LIST_RE,
    _SHAPE_RE,
    _lm_config,
    _lm_parts,
    _mesh_from_shape,
    _ring_bytes,
    aot_compile_step,
    resolve_placement,
)
from rocket_tpu.utils.perf import DeviceSpec, device_spec

__all__ = [
    "HloInstr",
    "OpCost",
    "SimResult",
    "PallasFact",
    "parse_hlo_module",
    "cost_ops",
    "simulate",
    "collect_pallas_facts",
    "predict_compiled",
    "audit_schedule",
    "SchedAuditReport",
    "SCHED_TARGETS",
    "run_sched_target",
]

#: Fixed per-collective launch/sync latency (seconds) added on top of the
#: bytes/bandwidth term. This is what makes convoys of tiny collectives
#: expensive in the model, as they are on hardware.
COLLECTIVE_LATENCY_S = 1e-6

#: Reference device kind the CI self-gate prices against (the bench
#: fleet's v5e). The CLI/targets can override per audit.
DEFAULT_DEVICE_KIND = "TPU v5 lite"

#: Opcodes that cost nothing in the model: metadata plumbing and
#: layout-free aliasing.
_FREE_OPS = frozenset({
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element",
    "partition-id", "replica-id", "after-all", "iota",
    "rng-get-and-update-state", "get-dimension-size",
})

_ASYNC_SUFFIXES = ("-start", "-done")


# -- HLO text -> instruction DAG ---------------------------------------------


@dataclass
class HloInstr:
    """One instruction parsed from the HLO text dump."""

    name: str
    opcode: str
    dtype: str                  # first result element's dtype
    shape: Tuple[int, ...]      # first result element's per-device shape
    result_bytes: int           # all result elements
    #: per result element: (dtype, dims, nbytes) — async starts cost
    #: only the last element (the actual result; the head aliases the
    #: operand), matching shard_audit.parse_collectives.
    shapes: Tuple[Tuple[str, Tuple[int, ...], int], ...]
    operands: Tuple[str, ...]   # operand instruction names (same computation)
    called: Tuple[str, ...]     # called computation names (fusion/call/while)
    attrs: str                  # raw attr tail (dims, groups, metadata)
    where: str = ""             # op_name + source, for messages


_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op>[^"]*)"'
    r'(?:[^}]*?source_file="(?P<file>[^"]*)")?'
    r"(?:[^}]*?source_line=(?P<line>\d+))?"
)
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|condition|body|branch_computations)="
    r"\{?%([\w\.\-]+)"
)
_OPERAND_NAME_RE = re.compile(r"%([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONV_DIMS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")


def _matched_paren_span(text: str, start: int) -> int:
    """Index just past the ``)`` matching the ``(`` at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _shorten_where(match) -> str:
    if match is None:
        return ""
    op = (match.group("op") or "").split("/")[-1]
    file = match.group("file") or ""
    line = match.group("line") or ""
    loc = f"{file.rsplit('/', 1)[-1]}:{line}" if file else ""
    return f"{op} {loc}".strip()


def _parse_instr(line: str) -> Optional[HloInstr]:
    stripped = line.strip()
    if stripped.startswith("ROOT "):
        stripped = stripped[5:]
    if not stripped.startswith("%") or " = " not in stripped:
        return None
    name, rest = stripped[1:].split(" = ", 1)
    if rest.startswith("("):
        end = _matched_paren_span(rest, 0)
        type_seg, rest = rest[:end], rest[end:].lstrip()
    else:
        parts = rest.split(" ", 1)
        if len(parts) != 2:
            return None
        type_seg, rest = parts[0], parts[1].lstrip()
    paren = rest.find("(")
    if paren <= 0:
        return None
    opcode = rest[:paren]
    end = _matched_paren_span(rest, paren)
    operand_seg = rest[paren + 1:end - 1]
    attrs = rest[end:]

    shapes = []
    for m in _SHAPE_RE.finditer(type_seg):
        dims = tuple(int(x) for x in m.group("dims").split(",") if x)
        n = 1
        for d in dims:
            n *= d
        shapes.append((m.group("dtype"), dims,
                       n * _DTYPE_BYTES.get(m.group("dtype"), 4)))
    if not shapes:
        shapes = [("pred", (), 0)]
    operands = tuple(_OPERAND_NAME_RE.findall(operand_seg))
    called = tuple(_CALLED_RE.findall(attrs))
    return HloInstr(
        name=name.strip(), opcode=opcode, dtype=shapes[0][0],
        shape=shapes[0][1],
        result_bytes=sum(b for _d, _s, b in shapes),
        shapes=tuple(shapes),
        operands=operands, called=called, attrs=attrs,
        where=_shorten_where(_METADATA_RE.search(attrs)),
    )


def parse_hlo_module(hlo_text: str) -> tuple[list[HloInstr], dict]:
    """Parse every computation out of an HLO text dump.

    Returns ``(entry_instrs, computations)`` where ``entry_instrs`` is
    the ENTRY computation's instruction sequence in schedule order
    (SPMD-compiled modules dump with ``is_scheduled=true``) and
    ``computations`` maps every computation name to its instruction
    list (fusion bodies, called subcomputations).
    """
    computations: dict[str, list[HloInstr]] = {}
    entry_name = None
    current: Optional[list[HloInstr]] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{") \
                and "%" in line:
            head = line.strip()
            is_entry = head.startswith("ENTRY")
            if is_entry:
                head = head[len("ENTRY"):].strip()
            if not head.startswith("%"):
                continue
            name = head[1:].split(" ", 1)[0].split("(", 1)[0]
            current = computations.setdefault(name, [])
            if is_entry:
                entry_name = name
            continue
        if line.startswith("}") or line.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        instr = _parse_instr(line)
        if instr is not None:
            current.append(instr)
    entry = computations.get(entry_name, []) if entry_name else []
    return entry, computations


# -- per-op roofline costs ---------------------------------------------------


def _numel(shape) -> int:
    n = 1
    for d in shape or ():
        n *= int(d)
    return n


def _conv_flops(out_numel: int, kernel_numel: int, out_features: int) -> float:
    # per output element: one MAC per kernel element of its input patch
    # (= kernel elems / output-feature count), times 2 for mul+add.
    if out_features <= 0:
        out_features = 1
    return 2.0 * out_numel * (kernel_numel / out_features)


def _computation_flops(
    name: str,
    computations: Mapping[str, list[HloInstr]],
    memo: dict,
) -> float:
    """MXU (dot/conv) FLOPs inside a called computation, recursively."""
    if name in memo:
        return memo[name]
    memo[name] = 0.0  # cycle guard
    total = 0.0
    for instr in computations.get(name, ()):
        if instr.opcode == "dot":
            total += _dot_flops_from(instr, computations)
        elif instr.opcode == "convolution":
            total += _conv_flops_from(instr, computations)
        else:
            for called in instr.called:
                total += _computation_flops(called, computations, memo)
    memo[name] = total
    return total


def _dot_flops_from(instr: HloInstr, computations) -> float:
    m = _LHS_CONTRACT_RE.search(instr.attrs)
    contract = 1
    if m is not None and instr.operands:
        lhs = _shape_of_operand(instr, 0, computations)
        if lhs is not None:
            for idx in (int(x) for x in m.group(1).split(",") if x):
                if idx < len(lhs):
                    contract *= int(lhs[idx])
    return 2.0 * _numel(instr.shape) * contract


def _conv_flops_from(instr: HloInstr, computations) -> float:
    kernel = _shape_of_operand(instr, 1, computations)
    if kernel is None:
        return 2.0 * _numel(instr.shape)
    m = _CONV_DIMS_RE.search(instr.attrs)
    out_features = 1
    if m is not None:
        rhs_labels = m.group(2)
        o_pos = rhs_labels.find("o")
        if 0 <= o_pos < len(kernel):
            out_features = int(kernel[o_pos])
    else:
        out_features = int(kernel[-1]) if kernel else 1
    return _conv_flops(_numel(instr.shape), _numel(kernel), out_features)


_OPERAND_TYPE_RE = re.compile(
    r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\](?:\{[\d,]*\})?\s+"
    r"%(?P<name>[\w\.\-]+)"
)


def _shape_of_operand(instr: HloInstr, index: int, computations):
    """Operand shapes resolve through the instruction map; falls back to
    None (callers then degrade to an output-numel estimate)."""
    if index >= len(instr.operands):
        return None
    target = instr.operands[index]
    by_name = computations.get("__by_name__")
    if by_name is None:
        by_name = {}
        for instrs in computations.values():
            if isinstance(instrs, list):
                for i in instrs:
                    by_name[i.name] = i
        computations["__by_name__"] = by_name  # type: ignore[index]
    found = by_name.get(target)
    return tuple(found.shape) if found is not None else None


@dataclass
class OpCost:
    """One scheduled op with its roofline cost attribution."""

    name: str
    opcode: str
    kind: str            # "compute" | "memory" | "comm" | "free"
    time_s: float
    flops: float
    hbm_bytes: int
    comm_bytes: int      # ring-model bytes for collectives, else 0
    is_comm: bool
    operands: Tuple[str, ...]
    where: str = ""
    is_dcn: bool = False  # replica group spans a slice boundary (DCN-priced)

    @property
    def intensity(self) -> float:
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0


def _comm_base_kind(opcode: str) -> Optional[str]:
    base = opcode
    for suffix in _ASYNC_SUFFIXES:
        if base.endswith(suffix):
            base = base[: -len(suffix)]
    return base if base in COLLECTIVE_KINDS else None


def _group_size(instr: HloInstr) -> int:
    grp = _GROUPS_LIST_RE.search(instr.attrs)
    if grp is not None:
        return len(grp.group(1).split(","))
    grp = _GROUPS_IOTA_RE.search(instr.attrs)
    if grp is not None:
        return int(grp.group(2))
    if "source_target_pairs" in instr.attrs:
        return 2
    return 1


_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def _crosses_slice(instr: HloInstr, slice_size: int) -> bool:
    """Whether the collective's communicating devices span a slice
    boundary on a slice-major fake mesh (device ``d`` lives on slice
    ``d // slice_size``). List-form replica groups are checked member by
    member; iota-form groups are contiguous-major, so a group wider than
    a slice must cross; a collective-permute crosses when any
    source/target pair does."""
    if slice_size <= 0:
        return False
    grp = _GROUPS_LIST_RE.search(instr.attrs)
    if grp is not None:
        members = [int(m) for m in grp.group(1).split(",")]
        return len({m // slice_size for m in members}) > 1
    grp = _GROUPS_IOTA_RE.search(instr.attrs)
    if grp is not None:
        return int(grp.group(2)) > slice_size
    pairs = _PAIRS_RE.search(instr.attrs)
    if pairs is not None:
        return any(
            int(a) // slice_size != int(b) // slice_size
            for a, b in _PAIR_RE.findall(pairs.group(1))
        )
    return False


def cost_ops(
    entry: Sequence[HloInstr],
    computations: Mapping[str, list[HloInstr]],
    spec: DeviceSpec,
    *,
    slice_size: int = 0,
) -> list[OpCost]:
    """Roofline-cost every scheduled op of the entry computation.

    Compute ops: ``max(flops / peak, bytes / hbm_bw)`` with the binding
    resource deciding compute- vs memory-bound (f32 dots run at half the
    bf16 MXU peak). Collectives: ring-model bytes over ICI bandwidth
    plus a fixed :data:`COLLECTIVE_LATENCY_S`; ``-done`` halves are free
    join markers so sync and async forms of one op cost the same. FLOPs
    inside fusions/calls come from their called computations (dots and
    convolutions found recursively).

    ``slice_size`` > 0 declares a multi-slice topology (``slice_size``
    devices per ICI domain, slice-major device order): any collective
    whose replica group spans a slice boundary is priced at the
    data-center network column ``spec.dcn_bw`` instead of ``ici_bw`` —
    cross-slice bytes are 10-40x slower per the spec table, which is
    the whole reason the audit has to see them.
    """
    memo: dict = {}
    computations = dict(computations)
    by_name = {i.name: i for i in entry}
    ops: list[OpCost] = []
    for instr in entry:
        operand_bytes = sum(
            by_name[o].result_bytes for o in sorted(set(instr.operands))
            if o in by_name
        )
        hbm_bytes = operand_bytes + instr.result_bytes
        comm_kind = _comm_base_kind(instr.opcode)

        if instr.opcode in _FREE_OPS:
            ops.append(OpCost(
                name=instr.name, opcode=instr.opcode, kind="free",
                time_s=0.0, flops=0.0, hbm_bytes=0, comm_bytes=0,
                is_comm=False, operands=instr.operands, where=instr.where,
            ))
            continue

        if comm_kind is not None:
            if instr.opcode.endswith("-done"):
                ops.append(OpCost(
                    name=instr.name, opcode=instr.opcode, kind="comm",
                    time_s=0.0, flops=0.0, hbm_bytes=0, comm_bytes=0,
                    is_comm=True, operands=instr.operands,
                    where=instr.where,
                ))
                continue
            group = _group_size(instr)
            result_bytes = instr.result_bytes
            if instr.opcode.endswith("-start") and len(instr.shapes) > 1:
                # An async start's tuple is (operand alias, result): cost
                # only the final element so sync and async forms agree.
                result_bytes = instr.shapes[-1][2]
            bytes_moved = _ring_bytes(comm_kind, result_bytes, group)
            # Bulk collectives run XLA's multi-dimensional rings and
            # drive every ICI link at once (aggregate bandwidth); an
            # explicit collective-permute hop moves its chunk over ONE
            # link — priced hop-by-hop at the per-link column, which is
            # what makes a ppermute ring honest against a bulk
            # all-gather of the same bytes. A group that spans a slice
            # boundary leaves ICI entirely: the slowest hop (DCN) sets
            # the collective's rate.
            dcn = _crosses_slice(instr, slice_size)
            if dcn:
                bw = spec.dcn_bw
            elif comm_kind == "collective-permute":
                bw = spec.ici_link_bw
            else:
                bw = spec.ici_bw
            time_s = bytes_moved / bw + COLLECTIVE_LATENCY_S
            ops.append(OpCost(
                name=instr.name, opcode=instr.opcode, kind="comm",
                time_s=time_s, flops=0.0, hbm_bytes=hbm_bytes,
                comm_bytes=bytes_moved, is_comm=True,
                operands=instr.operands, where=instr.where,
                is_dcn=dcn,
            ))
            continue

        if instr.opcode == "dot":
            flops = _dot_flops_from(instr, computations)
        elif instr.opcode == "convolution":
            flops = _conv_flops_from(instr, computations)
        elif instr.called:
            flops = sum(
                _computation_flops(c, computations, memo)
                for c in instr.called
            )
            if flops == 0.0:
                flops = float(_numel(instr.shape))
        else:
            flops = float(_numel(instr.shape))

        peak = spec.flops_bf16
        if instr.opcode in ("dot", "convolution") and instr.dtype == "f32":
            peak *= 0.5
        t_flops = flops / peak
        t_mem = hbm_bytes / spec.hbm_bw
        kind = "compute" if t_flops >= t_mem else "memory"
        ops.append(OpCost(
            name=instr.name, opcode=instr.opcode, kind=kind,
            time_s=max(t_flops, t_mem), flops=flops,
            hbm_bytes=hbm_bytes, comm_bytes=0, is_comm=False,
            operands=instr.operands, where=instr.where,
        ))
    return ops


# -- the two-stream schedule simulation --------------------------------------


@dataclass
class SimResult:
    """One simulation pass over the scheduled ops."""

    makespan_s: float
    compute_bound_s: float   # compute-stream time on MXU-bound ops
    memory_bound_s: float    # compute-stream time on HBM-bound ops
    comm_total_s: float      # total collective time (both passes agree)
    exposed_comm_s: float    # collective time with the compute stream idle
    stall_s: float           # compute idle not explained by communication
    ops: list = field(default_factory=list)


def _interval_overlap(a: list, b: list) -> float:
    """Total overlap between two sorted, non-overlapping interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def simulate(ops: Sequence[OpCost], *, overlap: bool) -> SimResult:
    """Simulate the schedule on a compute stream + a collective stream.

    ``overlap=False`` prices the module as compiled: ops run in schedule
    order and a synchronous collective blocks the compute stream until
    it completes (the TPU TensorCore sequencer semantics for non-async
    collective HLO); async ``-start``/``-done`` pairs overlap. Makespan
    decomposes exactly into compute-bound + memory-bound + exposed-comm
    + stall.

    ``overlap=True`` prices the ideal: greedy dataflow list scheduling —
    collectives run (in order) on their own stream, the compute stream
    picks the earliest-ready op regardless of schedule position. The
    difference between the two passes is communication that independent
    compute COULD hide with a better schedule or async collectives.
    """
    if overlap:
        return _simulate_dataflow(ops)
    finish: dict[str, float] = {}
    compute_clock = 0.0
    comm_clock = 0.0
    comm_busy: list = []
    compute_idle: list = []
    compute_bound = memory_bound = comm_total = 0.0

    for op in ops:
        dep_t = max(
            (finish[d] for d in op.operands if d in finish), default=0.0
        )
        if op.kind == "free":
            finish[op.name] = dep_t
            continue
        if op.is_comm:
            if op.opcode.endswith("-done"):
                finish[op.name] = dep_t
                continue
            # collective-permute is a point-to-point DMA on TPU — the
            # sequencer issues the send and runs on; XLA lowers it to
            # -start/-done pairs there. The CPU fake-mesh dump keeps the
            # sync spelling, so the simulator restores the async
            # semantics by opcode: a permute floats to its dependency
            # time and only its CONSUMERS wait.
            sync = not (
                op.opcode.endswith("-start")
                or op.opcode.startswith("collective-permute")
            )
            # A sync collective is issued by the in-order sequencer: it
            # cannot start before the compute stream reaches it. Only
            # async -start ops float back to their dependency time.
            start = max(comm_clock, dep_t, compute_clock if sync else 0.0)
            end = start + op.time_s
            comm_clock = end
            comm_total += op.time_s
            if op.time_s > 0:
                comm_busy.append((start, end))
            finish[op.name] = end
            if sync and end > compute_clock:
                compute_idle.append((compute_clock, end))
                compute_clock = end
            continue
        start = max(compute_clock, dep_t)
        if start > compute_clock:
            compute_idle.append((compute_clock, start))
        end = start + op.time_s
        if op.kind == "compute":
            compute_bound += op.time_s
        else:
            memory_bound += op.time_s
        compute_clock = end
        finish[op.name] = end

    makespan = max(
        [compute_clock, comm_clock] + list(finish.values()) or [0.0]
    )
    if makespan > compute_clock:
        compute_idle.append((compute_clock, makespan))
    exposed = _interval_overlap(comm_busy, compute_idle)
    idle_total = sum(hi - lo for lo, hi in compute_idle)
    return SimResult(
        makespan_s=makespan,
        compute_bound_s=compute_bound,
        memory_bound_s=memory_bound,
        comm_total_s=comm_total,
        exposed_comm_s=exposed,
        stall_s=max(0.0, idle_total - exposed),
        ops=list(ops),
    )


def _simulate_dataflow(ops: Sequence[OpCost]) -> SimResult:
    """Greedy two-stream dataflow schedule (the ideal-overlap pass).

    The collective stream keeps schedule order (in-order DMA queue);
    the compute stream repeatedly runs the first op in schedule order
    whose dependencies have finished, advancing time only when nothing
    is ready. O(n^2) worst case — entry computations are a few hundred
    ops."""
    finish: dict[str, float] = {}
    done: list[bool] = [False] * len(ops)
    # Dependencies resolve against ops in THIS computation only; outside
    # names (never produced here) resolve to t=0.
    produced = {op.name for op in ops}

    def dep_t(op) -> Optional[float]:
        t = 0.0
        for d in op.operands:
            if d in finish:
                t = max(t, finish[d])
            elif d in produced:
                return None  # dependency not yet scheduled
        return t

    compute_clock = comm_clock = 0.0
    comm_busy: list = []
    compute_busy: list = []
    compute_bound = memory_bound = comm_total = 0.0
    comm_idx = [i for i, op in enumerate(ops) if op.is_comm]
    comm_pos = 0

    remaining = len(ops)
    while remaining:
        progressed = False
        # Drain every free/instant op that is ready (zero cost, any stream).
        for i, op in enumerate(ops):
            if done[i] or not (
                op.kind == "free"
                or (op.is_comm and op.opcode.endswith("-done"))
            ):
                continue
            t = dep_t(op)
            if t is None:
                continue
            finish[op.name] = t
            done[i] = True
            remaining -= 1
            progressed = True
        # Head-of-line collective.
        while comm_pos < len(comm_idx) and done[comm_idx[comm_pos]]:
            comm_pos += 1
        comm_candidate = None
        if comm_pos < len(comm_idx):
            op = ops[comm_idx[comm_pos]]
            t = dep_t(op)
            if t is not None:
                comm_candidate = (max(comm_clock, t), comm_idx[comm_pos])
        # First ready compute op in schedule order.
        compute_candidate = None
        for i, op in enumerate(ops):
            if done[i] or op.is_comm or op.kind == "free":
                continue
            t = dep_t(op)
            if t is None:
                continue
            compute_candidate = (max(compute_clock, t), i)
            break
        if comm_candidate is None and compute_candidate is None:
            if progressed:
                continue
            break  # cyclic/unresolvable (malformed dump): stop cleanly
        # Run whichever stream can start earlier (tie -> compute).
        if compute_candidate is not None and (
            comm_candidate is None
            or compute_candidate[0] <= comm_candidate[0]
        ):
            start, i = compute_candidate
            op = ops[i]
            end = start + op.time_s
            if op.time_s > 0:
                compute_busy.append((start, end))
            if op.kind == "compute":
                compute_bound += op.time_s
            else:
                memory_bound += op.time_s
            compute_clock = max(compute_clock, end)
        else:
            start, i = comm_candidate
            op = ops[i]
            end = start + op.time_s
            comm_total += op.time_s
            if op.time_s > 0:
                comm_busy.append((start, end))
            comm_clock = max(comm_clock, end)
        finish[op.name] = end
        done[i] = True
        remaining -= 1

    makespan = max(finish.values(), default=0.0)
    compute_busy.sort()
    idle: list = []
    cursor = 0.0
    for lo, hi in compute_busy:
        if lo > cursor:
            idle.append((cursor, lo))
        cursor = max(cursor, hi)
    if makespan > cursor:
        idle.append((cursor, makespan))
    comm_busy.sort()
    exposed = _interval_overlap(comm_busy, idle)
    idle_total = sum(hi - lo for lo, hi in idle)
    return SimResult(
        makespan_s=makespan,
        compute_bound_s=compute_bound,
        memory_bound_s=memory_bound,
        comm_total_s=comm_total,
        exposed_comm_s=exposed,
        stall_s=max(0.0, idle_total - exposed),
        ops=list(ops),
    )


# -- pallas facts from the traced jaxpr --------------------------------------


@dataclass(frozen=True)
class PallasFact:
    """One ``pallas_call`` found in the traced step."""

    name: str
    grid: Tuple[int, ...]
    #: ((block_shape, dtype_str), ...) across inputs and outputs
    blocks: Tuple[Tuple[Tuple, str], ...]
    #: (block_shape, dtype_str) -> full array shape (for full-dim waivers)
    full_shapes: Mapping
    vmem_bytes_est: int


def _pallas_fact(eqn) -> PallasFact:
    gm = eqn.params["grid_mapping"]
    # The call's explicit ``name=``, else the kernel function's own name
    # (the kernel jaxpr's debug info carries it).
    name = eqn.params["name"] \
        or eqn.params["jaxpr"].debug_info.func_name or "pallas_call"
    blocks = []
    full_shapes = {}
    vmem = 0
    for bm in gm.block_mappings:
        # Block-shape entries are pallas BlockDim objects: Blocked /
        # Element / BoundedSlice carry ``block_size``; a Squeezed axis
        # holds one row.
        shape = tuple(int(getattr(d, "block_size", 1)) for d in bm.block_shape)
        dtype = str(bm.array_aval.dtype)
        memspace = str(bm.block_aval.memory_space).lower()
        if not (memspace.endswith("any") or memspace.endswith("hbm")):
            # ANY/HBM operands are NOT pipelined into VMEM — the kernel
            # DMAs the slices it needs (e.g. gather_gmm's token array);
            # counting their full shape as a double-buffered block would
            # flag every HBM-resident operand as a VMEM overflow.
            vmem += 2 * _numel(shape) * np.dtype(dtype).itemsize
        key = (shape, dtype)
        blocks.append(key)
        full_shapes[key] = tuple(bm.array_aval.shape)
    return PallasFact(
        name=name, grid=tuple(int(g) for g in gm.grid), blocks=tuple(blocks),
        full_shapes=full_shapes, vmem_bytes_est=int(vmem),
    )


def collect_pallas_facts(step_fn: Callable, variables, batch) -> list:
    """Trace ``step_fn`` abstractly and collect every ``pallas_call``'s
    block/grid facts (the kernels trace on any backend — no TPU, no
    compile)."""
    closed = jax.make_jaxpr(step_fn)(variables, batch)
    facts: list[PallasFact] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                facts.append(_pallas_fact(eqn))
            for value in eqn.params.values():
                for sub in _subjaxprs(value):
                    walk(sub)

    def _subjaxprs(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield value.jaxpr
        elif isinstance(value, (list, tuple)):
            for item in value:
                if hasattr(item, "eqns"):
                    yield item
                elif hasattr(item, "jaxpr"):
                    yield item.jaxpr

    walk(closed.jaxpr)
    return facts


# -- prediction + report -----------------------------------------------------


def predict_compiled(
    hlo_text: str,
    device_kind: str = DEFAULT_DEVICE_KIND,
    slice_size: int = 0,
) -> tuple[SimResult, SimResult, dict]:
    """Roofline-simulate an optimized HLO dump for ``device_kind``.

    Returns ``(scheduled, ideal, record)``: the as-compiled simulation,
    the ideal-overlap simulation, and the budget/BENCH record. Raises
    ``ValueError`` for an unknown device kind (price against a known
    machine or not at all). ``slice_size`` > 0 prices cross-slice
    collectives at DCN bandwidth (see :func:`cost_ops`) and adds
    ``n_dcn_collectives`` / ``dcn_bytes_per_step`` to the record.
    """
    spec = device_spec(device_kind)
    if spec is None:
        raise ValueError(
            f"sched_audit: unknown device kind {device_kind!r} — add it "
            "to rocket_tpu.utils.perf.DEVICE_SPECS"
        )
    entry, computations = parse_hlo_module(hlo_text)
    ops = cost_ops(entry, computations, spec, slice_size=slice_size)
    scheduled = simulate(ops, overlap=False)
    ideal = simulate(ops, overlap=True)

    # MFU numerator: everything the cost model counted — dots/convs at
    # top level plus fusion-internal dots; the 1-FLOP/element estimates
    # for pure elementwise fusions are noise next to them.
    flops = sum(op.flops for op in ops if op.kind in ("compute", "memory"))
    hbm_bytes = sum(op.hbm_bytes for op in ops if not op.is_comm)
    step = max(scheduled.makespan_s, 1e-12)
    predicted_mfu = flops / (step * spec.flops_bf16)
    record = {
        "device_kind": spec.kind,
        "predicted_step_time_us": round(scheduled.makespan_s * 1e6, 3),
        "compute_us": round(scheduled.compute_bound_s * 1e6, 3),
        "memory_us": round(scheduled.memory_bound_s * 1e6, 3),
        "exposed_comm_us": round(scheduled.exposed_comm_s * 1e6, 3),
        "stall_us": round(scheduled.stall_s * 1e6, 3),
        "comm_total_us": round(scheduled.comm_total_s * 1e6, 3),
        "overlap_headroom_us": round(
            max(0.0, scheduled.makespan_s - ideal.makespan_s) * 1e6, 3
        ),
        "overlap_fraction": round(
            1.0 - scheduled.exposed_comm_s / scheduled.comm_total_s, 4
        ) if scheduled.comm_total_s > 0 else 1.0,
        "fractions": {
            "compute": round(scheduled.compute_bound_s / step, 4),
            "memory": round(scheduled.memory_bound_s / step, 4),
            "exposed_comm": round(scheduled.exposed_comm_s / step, 4),
            "stall": round(scheduled.stall_s / step, 4),
        },
        "bound": max(
            ("compute", scheduled.compute_bound_s),
            ("memory", scheduled.memory_bound_s),
            ("comm", scheduled.exposed_comm_s),
            key=lambda kv: kv[1],
        )[0],
        "flops_per_step": float(flops),
        "hbm_bytes_per_step": int(hbm_bytes),
        "predicted_mfu": round(predicted_mfu, 4),
        "n_ops": len([op for op in ops if op.kind != "free"]),
        "n_collectives": len([
            op for op in ops
            if op.is_comm and not op.opcode.endswith("-done")
        ]),
    }
    if slice_size > 0:
        dcn_ops = [
            op for op in ops
            if op.is_dcn and not op.opcode.endswith("-done")
        ]
        record["n_dcn_collectives"] = len(dcn_ops)
        record["dcn_bytes_per_step"] = int(
            sum(op.comm_bytes for op in dcn_ops)
        )
    return scheduled, ideal, record


@dataclass
class SchedAuditReport:
    """Findings plus the schedule record the budget gate (and BENCH
    emission) consumes."""

    label: str
    findings: list = field(default_factory=list)
    scheduled: Optional[SimResult] = None
    ideal: Optional[SimResult] = None
    pallas: list = field(default_factory=list)
    record: dict = field(default_factory=dict)

    @property
    def clean(self) -> bool:
        return not self.findings


def audit_schedule(
    step_fn: Callable,
    variables,
    batch,
    *,
    rules=None,
    mesh_shape: Optional[Mapping[str, int]] = None,
    mesh=None,
    data_axes: Tuple[str, ...] = ("data",),
    device_kind: str = DEFAULT_DEVICE_KIND,
    donate_argnums: Sequence[int] = (),
    compile_hlo: bool = True,
    mfu_floor: float = 0.0,
    exposed_frac_min: float = 0.15,
    exposed_min_s: float = 20e-6,
    convoy_min: int = 6,
    bucket_bytes: int = 4 << 20,
    memory_frac_max: float = 0.6,
    memory_min_bytes: int = 1 << 20,
    slice_size: int = 0,
    label: str = "step",
) -> SchedAuditReport:
    """Audit the compiled schedule of ``step_fn(variables, batch)``.

    With ``compile_hlo=True`` (default) the step is AOT-compiled on the
    fake mesh under ``rules`` (the shard_audit harness) and the RKT501/
    502/503/505 schedule checks run over the roofline simulation;
    pallas facts (RKT504) come from the abstract trace either way.
    ``compile_hlo=False`` audits only the jaxpr side — for steps whose
    kernels cannot compile on the host backend (pallas without
    interpret mode). Pure abstract evaluation + XLA compilation — no
    FLOPs run, no params materialize, no TPU required.
    """
    spec = device_spec(device_kind)
    if spec is None:
        raise ValueError(
            f"sched_audit: unknown device kind {device_kind!r} — add it "
            "to rocket_tpu.utils.perf.DEVICE_SPECS"
        )
    findings: list[Finding] = []
    report = SchedAuditReport(label=label)

    # Trace under the audited target's device kind so the tuned-config
    # lookup (`rocket_tpu.tune.get_config`) resolves the block shapes
    # that would ACTUALLY run there — RKT504 then audits the tuned
    # table's configs, not the hand-picked defaults the audit host (a
    # CPU with no table entries) would fall back to.
    from rocket_tpu.tune import priced_device_kind

    with priced_device_kind(device_kind):
        report.pallas = collect_pallas_facts(step_fn, variables, batch)
    findings.extend(check_pallas(
        report.pallas, spec.vmem_bytes, label=label
    ))

    if compile_hlo:
        if mesh is None:
            mesh = _mesh_from_shape(mesh_shape or {})
        if rules is None:
            def rules(path, leaf):  # replicate everything
                return None
        abs_variables, abs_batch, _specs, placement_findings = \
            resolve_placement(
                variables, batch, rules=rules, mesh=mesh,
                data_axes=data_axes, label=label,
            )
        # Placement findings are the SPMD auditor's to report; here the
        # placement only needs to compile, so only fatal ones surface.
        compiled, compile_findings = aot_compile_step(
            step_fn, abs_variables, abs_batch, mesh=mesh,
            donate_argnums=donate_argnums, label=label,
        )
        del placement_findings
        findings.extend(compile_findings)
        if compiled is not None:
            scheduled, ideal, record = predict_compiled(
                compiled.as_text(), device_kind, slice_size=slice_size
            )
            report.scheduled, report.ideal = scheduled, ideal
            report.record = dict(record, mesh=dict(
                zip(mesh.axis_names, mesh.devices.shape)
            ))
            findings.extend(check_exposed_comm(
                scheduled, ideal, exposed_frac_min=exposed_frac_min,
                exposed_min_s=exposed_min_s, label=label,
            ))
            findings.extend(check_convoys(
                scheduled.ops, convoy_min=convoy_min,
                bucket_bytes=bucket_bytes, label=label,
            ))
            findings.extend(check_memory_bound(
                scheduled.ops, scheduled.makespan_s, spec.ridge,
                memory_frac_max=memory_frac_max,
                min_bytes=memory_min_bytes, label=label,
            ))
            findings.extend(check_mfu_floor(
                record.get("predicted_mfu"), mfu_floor, label=label,
            ))

    report.findings = findings
    return report


# -- builtin targets ---------------------------------------------------------


@dataclass(frozen=True)
class SchedTarget:
    """One self-gate configuration the CLI audits.

    Names pair with the SPMD audit targets (same model/rule-set/mesh
    pairings, same fake-mesh compile); each carries the device kind it
    prices against, a predicted-MFU floor (RKT505 — 0 disables) and
    threshold overrides where the defaults would mis-scale for the
    target's size.
    """

    name: str
    mesh_shape: Mapping[str, int]
    #: () -> (step_fn, variables, batch, rules, donate_argnums)
    build: Callable[[], tuple]
    device_kind: str = DEFAULT_DEVICE_KIND
    mfu_floor: float = 0.0
    compile_hlo: bool = True
    overrides: Mapping[str, Any] = field(default_factory=dict)
    demo: bool = False


def _tp_sched_parts():
    from rocket_tpu.analysis.shard_audit import _tp_parts

    return _tp_parts()


def _tp_2x4_sched_parts():
    from rocket_tpu.analysis.shard_audit import _tp_2x4_parts

    return _tp_2x4_parts()


def _tp_eval_sched_parts():
    from rocket_tpu.analysis.shard_audit import _tp_eval_parts

    return _tp_eval_parts()


def _fsdp_sched_parts():
    from rocket_tpu.analysis.shard_audit import _fsdp_parts

    return _fsdp_parts()


def _dp_2slice_parts():
    """Two-slice data parallelism: params FSDP-sharded inside each
    slice, batch split across both mesh axes. The gradient reduction
    then factors into an intra-slice reduce-scatter (ICI) and a
    cross-slice all-reduce whose replica groups span the slice boundary
    — the target's ``slice_size`` override makes the cost model price
    those at ``DeviceSpec.dcn_bw``. Plain GSPMD step (no overlap
    machinery): the point here is the DCN pricing, not the overlap."""
    from rocket_tpu.parallel.sharding import fsdp_rules

    return _lm_parts(fsdp_rules(axis="data", min_size=4096))


def _resnet_parts(batch_size: int = 64):
    """ResNet-18 (CIFAR stem) train step on a pure data mesh — the conv
    family's representative: exercises the convolution FLOP model and
    the sync-batchnorm cross-replica reductions. ``batch_size`` lets
    bench.py's calibration leg rebuild at the bench config's batch."""
    import jax.numpy as jnp
    import optax

    from rocket_tpu.models.resnet import resnet18

    model = resnet18(num_classes=10, stem="cifar")
    variables = jax.eval_shape(model.init, jax.random.key(0))
    batch = {
        "image": jax.ShapeDtypeStruct((batch_size, 32, 32, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((batch_size,), jnp.int32),
    }

    def loss_fn(variables, batch):
        out, state = model.apply(variables, dict(batch), mode="train")
        loss = optax.softmax_cross_entropy_with_integer_labels(
            out["logits"].astype(jnp.float32), out["label"]
        ).mean()
        return loss, state

    def train_step(variables, batch):
        (loss, state), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(variables, batch)
        params = jax.tree.map(
            lambda p, g: (p - 1e-3 * g).astype(p.dtype),
            variables["params"], grads["params"],
        )
        return {"params": params, "state": state}, loss

    return train_step, variables, batch, None, (0,)


def _flash_parts():
    """The flash-attention step traced (not compiled): audits the REAL
    pallas kernels' block shapes against the device tile/VMEM budget
    (RKT504). seq 256 so the kernel's block resolution engages."""
    config = _lm_config(attention_impl="flash", max_seq_len=256)
    step_fn, variables, batch, _rules, donate = _lm_parts(
        None, config=config
    )
    return step_fn, variables, batch, None, donate


def _fused_kernels_parts():
    """The structural kernel candidates (ISSUE 14) traced with their
    pallas variants PINNED — RKT504 prices the fused programs' blocks
    against the device tile/VMEM budget like any other pallas kernel,
    independent of the tune tables (which default them off). Shapes are
    the soft-spot bench geometries: the resnet18 stem epilogue, the
    charlm block, a bench-slice gather-gmm. compile_hlo=False — the
    kernels trace on any backend; Mosaic compilation is hardware's."""
    import jax.numpy as jnp

    from rocket_tpu.ops.fused_block import block_attn_half
    from rocket_tpu.ops.fused_conv import fused_bn_act
    from rocket_tpu.ops.gather_gmm import gather_gmm

    d_blk, h_blk, t_blk = 256, 4, 256
    n_conv, c_conv = 256 * 32 * 32, 64
    m_gmm, k_gmm, n_gmm, e_gmm = 2048, 768, 3072, 4
    bf16 = jnp.bfloat16
    variables = {
        "params": {
            "bn_scale": jax.ShapeDtypeStruct((c_conv,), jnp.float32),
            "bn_bias": jax.ShapeDtypeStruct((c_conv,), jnp.float32),
            "ln_scale": jax.ShapeDtypeStruct((d_blk,), jnp.float32),
            "ln_bias": jax.ShapeDtypeStruct((d_blk,), jnp.float32),
            "wqkv": jax.ShapeDtypeStruct((d_blk, 3 * d_blk), jnp.float32),
            "bqkv": jax.ShapeDtypeStruct((3 * d_blk,), jnp.float32),
            "wproj": jax.ShapeDtypeStruct((d_blk, d_blk), jnp.float32),
            "bproj": jax.ShapeDtypeStruct((d_blk,), jnp.float32),
            "experts": jax.ShapeDtypeStruct((e_gmm, k_gmm, n_gmm), bf16),
        },
        "state": {},
    }
    batch = {
        "x_conv": jax.ShapeDtypeStruct((n_conv, c_conv), bf16),
        "x_blk": jax.ShapeDtypeStruct((64, t_blk, d_blk), bf16),
        "x_tok": jax.ShapeDtypeStruct((m_gmm, k_gmm), bf16),
        "row_ids": jax.ShapeDtypeStruct((m_gmm,), jnp.int32),
        "group_sizes": jax.ShapeDtypeStruct((e_gmm,), jnp.int32),
    }

    def step(variables, batch):
        p = variables["params"]
        y1, stats = fused_bn_act(
            batch["x_conv"], p["bn_scale"], p["bn_bias"],
            act=True, schedule="twopass", block_rows=512,
        )
        y2 = block_attn_half(
            batch["x_blk"], p["ln_scale"], p["ln_bias"], p["wqkv"],
            p["bqkv"], p["wproj"], p["bproj"],
            num_heads=h_blk, epilogue="fused", block_b=1,
        )
        y3 = gather_gmm(
            batch["x_tok"], p["experts"], batch["row_ids"],
            batch["group_sizes"], tile_m=512, tile_n=512,
        )
        total = (
            y1.astype(jnp.float32).sum() + stats.sum()
            + y2.astype(jnp.float32).sum() + y3.astype(jnp.float32).sum()
        )
        return variables, total

    return step, variables, batch, None, ()


def _badsched_parts():
    """Seeded-bad step for the true-positive fixture tests: a big
    all-gather whose result is consumed only at the end while an
    independent matmul chain sits after it (RKT501), a chained convoy of
    tiny psums (RKT502), and a large elementwise chain at arithmetic
    intensity ~0 that dominates the step (RKT503). The target also sets
    an unreachable MFU floor (RKT505)."""
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_from_shape({"data": 8})

    variables = {
        "params": {"w": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)},
        "state": {},
    }
    batch = {"x": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)}

    def body(w, x):
        # RKT502: a dependency-chained convoy of tiny collectives.
        v = x[0, :128]
        for _ in range(8):
            v = jax.lax.psum(v, "data") * 0.125
        # RKT501: a big collective with independent compute after it.
        g = jax.lax.all_gather(x, "data")      # (8, 128, 1024) = 4 MiB
        h = jnp.tanh(x @ w) @ w                # independent of g
        # RKT503: big memory-bound elementwise chain on the gathered
        # buffer (AI << ridge).
        m = jnp.tanh(g * 1.0001) + jnp.log1p(jnp.abs(g))
        # psum so the P() out_spec's replication is statically provable.
        return jax.lax.psum(h.sum() + m.sum() + v.sum(), "data")

    def bad_step(variables, batch):
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P("data")), out_specs=P(),
        )
        return variables, fn(variables["params"]["w"], batch["x"])

    return bad_step, variables, batch, None, ()


def _badoverlap_parts():
    """Seeded-bad data-parallel step for the overlap true-positive
    fixtures — the exact shape the overlapped paths exist to kill:

    * an UNBUCKETED per-parameter gradient all-reduce convoy (one tiny
      fp32 ``psum`` per leaf, dependency-chained so nothing can hide
      them — RKT502, and the latency sum shows up as RKT501 exposure);
    * a synchronous full-batch ``all_gather`` whose result is consumed
      only at the END of the step while the first matmul — independent
      of it — sits behind it in program order (RKT501: the dataflow
      pass hides it entirely, the as-compiled schedule cannot).

    A regression that reintroduces this shape in the real paths fails
    the budget gates; this demo proves the RULES would also still name
    it."""
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_from_shape({"data": 8})

    n_leaves = 12
    variables = {
        "params": {
            f"w{i}": jax.ShapeDtypeStruct((512, 512), jnp.float32)
            for i in range(n_leaves)
        },
        "state": {},
    }
    batch = {"x": jax.ShapeDtypeStruct((2048, 512), jnp.float32)}

    def body(x, *ws):
        # Sync all-gather of the whole batch issued FIRST, consumed only
        # at the very END — the layer chain below is independent of it,
        # so the dataflow pass hides it entirely while the as-compiled
        # schedule blocks on it (RKT501).
        gathered = jax.lax.all_gather(x, "data", axis=0, tiled=True)
        h = x
        sums = []
        for w in ws:
            h = jnp.tanh(h @ w)                     # (B/8, 512)
            s = jnp.sum(h, axis=0)                  # (512,) local "grad"
            sums.append(s)
            # The next layer consumes the local sum, pinning it into
            # the compute phase (as backward-produced grads are).
            h = h + s * 0.0
        # Unbucketed per-param "grad" reduction: a dependency-chained
        # convoy of tiny fp32 psums (RKT502) — the exact anti-pattern
        # grad_sync's buckets amortize. The local sums are hoisted so
        # the psums sit back-to-back in the schedule, as per-param grad
        # reductions do at a real step's tail.
        # Every "grad" reduction waits for the chain's end (the tail
        # salt), exactly like real per-param reductions at a step's
        # tail — so the psums sit back-to-back.
        tail_salt = jnp.sum(h) * 0.0
        total = jnp.zeros((512,), jnp.float32)
        for s in sums:
            total = total + jax.lax.psum(
                s + tail_salt + total * 0.0, "data"
            )
        total = jnp.sum(total)
        return jax.lax.psum(
            h.sum() + gathered[-1].sum() * 1e-6 + total, "data"
        )

    def bad_step(variables, batch):
        ws = tuple(variables["params"].values())
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P("data"),) + (P(),) * len(ws), out_specs=P(),
        )
        return variables, fn(batch["x"], *ws)

    return bad_step, variables, batch, None, ()


def _badpallas_parts():
    """Seeded-bad pallas_call for the RKT504 fixtures: blocks misaligned
    with the (8, 128) f32 tile and a VMEM-overflowing block, traced only
    (compile_hlo=False)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    variables = {
        "params": {"w": jax.ShapeDtypeStruct((512, 4096), jnp.float32)},
        "state": {},
    }
    batch = {"x": jax.ShapeDtypeStruct((4096, 4096), jnp.float32)}

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def bad_step(variables, batch):
        x = batch["x"]
        # Misaligned: 100 % 128 lanes, 7 % 8 sublanes.
        y = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(4,),
            in_specs=[pl.BlockSpec((7, 100), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((7, 100), lambda i: (i, 0)),
        )(x)
        # Over-VMEM: one (4096, 4096) f32 block is 64 MiB before double
        # buffering.
        z = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(x.shape, lambda: (0, 0))],
            out_specs=pl.BlockSpec(x.shape, lambda: (0, 0)),
        )(x)
        return variables, (y.sum() + z.sum())

    return bad_step, variables, batch, None, ()


#: name -> target. The default sweep runs the non-demo entries. MFU
#: floors are the roofline predictions with ~35% headroom — a schedule
#: regression (lost fusion, new reshards) blows through; tiny-model
#: noise does not.
SCHED_TARGETS: dict[str, SchedTarget] = {}


def _register_targets():
    for target in (
        SchedTarget(
            name="tp_2x4",
            mesh_shape={"data": 2, "model": 4},
            build=_tp_2x4_sched_parts,
            mfu_floor=0.007,
            # The overlapped collective paths (PR 12) brought the
            # hideable exposure under the DEFAULT RKT501 gate (0.15) —
            # no override: a regression back toward unoverlapped comm
            # trips the rule as well as the exposed_comm_us budget.
        ),
        SchedTarget(
            name="tp_1x8",
            mesh_shape={"data": 1, "model": 8},
            build=_tp_sched_parts,
            mfu_floor=0.005,
        ),
        SchedTarget(
            name="fsdp_1x8",
            mesh_shape={"data": 8},
            build=_fsdp_sched_parts,
            mfu_floor=0.012,
        ),
        SchedTarget(
            name="dp_2slice",
            mesh_shape={"slice": 2, "data": 4},
            build=_dp_2slice_parts,
            # Cross-slice gradient all-reduce at DCN bandwidth dominates
            # the predicted step; measured predicted_mfu 0.0143 — the
            # floor sits under it with the usual headroom.
            mfu_floor=0.009,
            overrides={"data_axes": ("slice", "data"), "slice_size": 4,
                       # DCN exposure is structural for an unoverlapped
                       # 2-slice program: the exposed_comm_us budget
                       # tracks it; RKT501 gates only gross regressions.
                       "exposed_frac_min": 0.9},
        ),
        SchedTarget(
            name="tp_2x4_eval",
            mesh_shape={"data": 2, "model": 4},
            build=_tp_eval_sched_parts,
            mfu_floor=0.007,
        ),
        SchedTarget(
            name="dp_resnet_1x8",
            mesh_shape={"data": 8},
            build=_resnet_parts,
            mfu_floor=0.048,
            # CIFAR ResNet-18 at B=64 f32 is honestly memory-dominated
            # (~62% of the predicted step in >=1 MiB sub-ridge fusions);
            # the gate sits above that so only NEW memory-bound weight
            # fails CI, while the step-time budget catches growth.
            overrides={"memory_frac_max": 0.75},
        ),
        SchedTarget(
            name="tp_flash",
            mesh_shape={"data": 1, "model": 8},
            build=_flash_parts,
            compile_hlo=False,
        ),
        SchedTarget(
            name="fused_kernels",
            mesh_shape={"data": 1},
            build=_fused_kernels_parts,
            compile_hlo=False,
        ),
        SchedTarget(
            name="badsched",
            mesh_shape={"data": 8},
            build=_badsched_parts,
            mfu_floor=0.9,
            overrides={"convoy_min": 4, "bucket_bytes": 1 << 20,
                       "memory_frac_max": 0.2,
                       "exposed_frac_min": 0.05, "exposed_min_s": 1e-6},
            demo=True,
        ),
        SchedTarget(
            name="badoverlap",
            mesh_shape={"data": 8},
            build=_badoverlap_parts,
            overrides={"convoy_min": 6, "bucket_bytes": 1 << 20,
                       "exposed_frac_min": 0.05, "exposed_min_s": 1e-6},
            demo=True,
        ),
        SchedTarget(
            name="badpallas",
            mesh_shape={"data": 8},
            build=_badpallas_parts,
            compile_hlo=False,
            demo=True,
        ),
    ):
        SCHED_TARGETS[target.name] = target


_register_targets()


def run_sched_target(target: SchedTarget) -> SchedAuditReport:
    step_fn, variables, batch, rules, donate = target.build()
    return audit_schedule(
        step_fn, variables, batch,
        rules=rules, mesh_shape=target.mesh_shape,
        device_kind=target.device_kind,
        donate_argnums=donate, compile_hlo=target.compile_hlo,
        mfu_floor=target.mfu_floor, label=target.name,
        **dict(target.overrides),
    )
