"""Continuous-batching request scheduler — the host-side policy half.

Every ``tick()`` is one serving step, PIPELINED against the in-flight
device dispatch (dispatch-then-harvest):

1. **admit** queued requests into free slots while the block pool can
   cover their prompts (all-or-nothing — a request never half-admits);
   free slots were free at the previous dispatch, so admission never
   touches a slot with results in flight;
2. **prefill** one fixed-size chunk of the oldest still-prefilling slot
   (chunked prefill: long prompts trickle in a chunk per tick and never
   stall the decode latency of running requests). Prefill is
   fire-and-forget and still-prefilling slots are never in a decode
   wave, so the chunk dispatch OVERLAPS the in-flight decode — the pool
   buffers thread program-order through both, so dataflow serializes
   them on device without a host sync;
3. **harvest** the PREVIOUS tick's decode dispatch: one
   ``jax.device_get`` fetches its k waves of tokens; emitted tokens
   stream out, finished slots free their blocks and are refillable on
   the very next tick;
4. **grow** each decode-ready slot's block table to cover the next k
   tokens; when the pool is exhausted the YOUNGEST active request is
   evicted — its blocks return to the pool and it re-queues at the
   FRONT with its generated tokens folded into the prompt, so it
   resumes exactly where it stopped after re-prefill (back-pressure,
   never OOM). Eviction runs strictly AFTER harvest, so a preempted
   slot never has tokens in flight to lose;
5. **dispatch** the next k-wave decode over all decode-ready slots and
   return step 3's events — the caller detokenizes/streams them while
   the new dispatch runs on device.

The scheduler owns host-side numpy mirrors of every per-slot array the
compiled wave consumes (block table, lengths, sampling vectors, masks).
Admission/eviction mutate the mirrors only — shapes and dtypes are fixed
at construction, which is what keeps the engine's compiled-once guarantee
(asserted via the trace counters in ``serve/engine.py``). The pipelining
invariant: between a dispatch and its harvest, the only mutations are
admission into slots the dispatch did not run and prefill of slots the
dispatch did not run — every mirror a dispatch read was copied to device
at dispatch time, and harvest replays the device's own per-wave length
bookkeeping onto the mirrors before anything else can read them.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from rocket_tpu.models.sampling import SAMPLE_BRANCHES, sample_branch
from rocket_tpu.obs.spans import span, timed
from rocket_tpu.serve.engine import SlotEngine
from rocket_tpu.serve.kv_pool import BlockAllocator

__all__ = ["Request", "TickEvent", "Scheduler"]


@dataclass
class Request:
    """One generation request plus its lifecycle record."""

    prompt: np.ndarray                       # (P,) int32, P >= 1
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: Optional[int] = None              # None/0 = off
    top_p: Optional[float] = None            # None/1.0 = off
    eos_token_id: Optional[int] = None       # None = no EOS
    id: int = -1                             # assigned at submit()
    # -- runtime record (scheduler-owned) ----------------------------------
    tokens: list = field(default_factory=list)   # generated so far
    preemptions: int = 0
    submitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def finished(self) -> bool:
        return self.finished_at is not None


@dataclass(frozen=True)
class TickEvent:
    """One emitted token (``finished`` marks the request's last)."""

    request: Request
    token: int
    finished: bool


class _Slot:
    """Per-slot bookkeeping while a request occupies the wave."""

    __slots__ = ("req", "blocks", "ctx", "prefill_pos", "admit_order")

    def __init__(self, req: Request, blocks: list[int], ctx: np.ndarray,
                 admit_order: int) -> None:
        self.req = req
        self.blocks = blocks
        #: The context to (re-)prefill: original prompt + tokens generated
        #: before a preemption — resuming re-fills the pool and continues.
        self.ctx = ctx
        self.prefill_pos = 0
        self.admit_order = admit_order

    @property
    def prefill_done(self) -> bool:
        # Prefill covers [0, P-1); the LAST context token goes through the
        # decode wave itself (writes its KV row AND yields the next-token
        # logits) — admission is uniform for P == 1 prompts.
        return self.prefill_pos >= len(self.ctx) - 1


class Scheduler:
    def __init__(self, engine: SlotEngine, allocator: Optional[BlockAllocator] = None) -> None:
        self.engine = engine
        self.allocator = allocator or BlockAllocator(engine.spec.num_blocks)
        s = engine.max_slots
        mb = engine.max_blocks_per_seq
        self.block_len = engine.spec.block_len
        self.max_context = mb * self.block_len
        # Host mirrors of the wave inputs — fixed shape + dtype forever.
        self.block_table = np.zeros((s, mb), np.int32)
        self.lengths = np.zeros((s,), np.int32)
        self.last_tok = np.zeros((s,), np.int32)
        self.limits = np.zeros((s,), np.int32)
        self.temp = np.zeros((s,), np.float32)
        self.top_k = np.zeros((s,), np.int32)
        self.top_p = np.ones((s,), np.float32)
        self.eos = np.full((s,), -1, np.int32)
        self.seeds = np.zeros((s,), np.int32)
        self.slots: list[Optional[_Slot]] = [None] * s
        self.queue: deque[Request] = deque()
        #: The in-flight decode dispatch, harvested at the NEXT tick
        #: (dispatch-then-harvest pipelining).
        self.pending = None
        #: Optional :class:`~rocket_tpu.obs.reqtrace.RequestTracer` —
        #: every hook below is guarded, so a bare scheduler (tests,
        #: audits) pays nothing.
        self.tracer = None
        #: Ticks taken so far: the ``tick=`` of this tick's spans.
        self.ticks = 0
        self._next_id = 0
        self._admit_seq = 0
        # Aggregates for the report / gauges.
        self.submitted = 0
        self.completed = 0
        self.preemptions = 0
        self.tokens_generated = 0
        self.waves_idle = 0
        self.rejected = 0

    # -- intake ------------------------------------------------------------

    def submit(self, req: Request) -> int:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("Scheduler.submit: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("Scheduler.submit: max_new_tokens must be >= 1")
        if req.top_p is not None and not 0.0 < req.top_p <= 1.0:
            # Same guard as generate(): top_p <= 0 would mask EVERY token
            # to -inf and the slot would silently stream token 0 forever.
            raise ValueError(
                f"Scheduler.submit: top_p must be in (0, 1], got {req.top_p}"
            )
        total = prompt.size + req.max_new_tokens
        if total > self.max_context:
            raise ValueError(
                f"Scheduler.submit: prompt {prompt.size} + "
                f"{req.max_new_tokens} new tokens exceed the per-slot "
                f"context {self.max_context} (max_blocks_per_seq * block_len)"
            )
        max_len = self.engine.model.config.max_seq_len
        if total > max_len:
            raise ValueError(
                f"Scheduler.submit: request needs {total} positions > "
                f"model max_seq_len {max_len}"
            )
        need = -(-total // self.block_len)  # ceil
        if need > self.allocator.capacity:
            raise ValueError(
                f"Scheduler.submit: request needs {need} blocks but the "
                f"pool only has {self.allocator.capacity} — no eviction "
                "policy can make room for it"
            )
        req.prompt = prompt
        req.id = self._next_id
        self._next_id += 1
        req.submitted_at = time.perf_counter()
        self.queue.append(req)
        self.submitted += 1
        if self.tracer is not None:
            self.tracer.on_submit(
                req.id, req.submitted_at, prompt_len=prompt.size,
                max_new_tokens=req.max_new_tokens,
            )
        return req.id

    # -- the serving step --------------------------------------------------

    def tick(self) -> list[TickEvent]:
        """One scheduling round: admit / prefill one chunk / harvest the
        in-flight dispatch / grow tables (evicting on exhaustion) /
        dispatch the next k waves. Returns the tokens the HARVESTED
        dispatch emitted (one tick behind the device — the pipelining);
        an idle engine returns []."""
        # The engine dates its ``moe/expert_pairs`` records by this.
        self.engine.tick = self.ticks
        if self.queue:
            with span("serve/admit", tick=self.ticks) as sp:
                sp.set(admitted=self._admit())
        self._prefill_one()
        events = self._harvest_pending()
        with span("serve/grow") as sp:
            evicted = self.preemptions
            run = self._grow_tables()
            sp.set(evicted=self.preemptions - evicted)
        if run.any():
            # The counter serve/decode_pages: how much of the block table
            # this wave's running slots hold, which is all the decode
            # kernel walks (``live`` pages of ``table`` entries), and
            # ``sample``, the branch its sampling takes on the device.
            with span("serve/decode_pages", tick=self.ticks) as sp:
                if sp.on:
                    sp.set(
                        live=int((self.lengths[run] // self.block_len
                                  + 1).sum()),
                        table=int(self.block_table.size),
                        sample=SAMPLE_BRANCHES[int(sample_branch(
                            self.temp, self.top_k, self.top_p, run))],
                    )
            self.pending = self.engine.decode_dispatch(
                self.block_table, self.lengths, self.last_tok, run,
                self.limits, self.temp, self.top_k, self.top_p, self.eos,
                self.seeds,
            )
            if self.tracer is not None:
                # One shared wave record per dispatch (O(waves), not
                # O(waves x slots)) — harvested with `pending` next tick.
                self.tracer.on_dispatch(
                    occupancy=int(run.sum()),
                    t=self.engine.last_dispatch_at,
                    waves=self.engine.waves_per_dispatch,
                    seq=self.pending.seq,
                )
        elif self.pending is None and not events:
            self.waves_idle += 1
        self.ticks += 1
        return events

    @property
    def idle(self) -> bool:
        return (
            not self.queue
            and all(s is None for s in self.slots)
            and self.pending is None
        )

    def run_until_idle(self, max_ticks: int = 100_000) -> list[TickEvent]:
        events = []
        for _ in range(max_ticks):
            if self.idle:
                return events
            events.extend(self.tick())
        raise RuntimeError(
            f"Scheduler.run_until_idle: not idle after {max_ticks} ticks"
        )

    # -- phases ------------------------------------------------------------

    def _admit(self) -> int:
        """Queued requests into free slots while the pool covers them;
        returns how many."""
        admitted = 0
        free = [i for i, s in enumerate(self.slots) if s is None]
        while self.queue and free:
            req = self.queue[0]
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)]
            ) if req.tokens else req.prompt
            need = -(-len(ctx) // self.block_len)
            blocks = self.allocator.alloc(need)
            if blocks is None:
                break  # back-pressure: wait for running requests to free
            self.queue.popleft()
            slot = free.pop(0)
            st = _Slot(req, blocks, ctx, self._admit_seq)
            self._admit_seq += 1
            self.slots[slot] = st
            self.block_table[slot] = 0
            self.block_table[slot, :need] = blocks
            self.lengths[slot] = 0
            self.last_tok[slot] = ctx[-1]
            # Absolute row limit in ORIGINAL-prompt terms: rows written
            # when the g-th generated token lands = (P-1) + g.
            self.limits[slot] = len(req.prompt) - 1 + req.max_new_tokens
            self.temp[slot] = req.temperature
            self.top_k[slot] = req.top_k or 0
            self.top_p[slot] = 1.0 if req.top_p is None else req.top_p
            self.eos[slot] = -1 if req.eos_token_id is None else req.eos_token_id
            self.seeds[slot] = req.id % (2**31 - 1)
            admitted += 1
            if self.tracer is not None:
                # Read here whether or not spans are on: a traced and an
                # untraced run date an admission at the same place.
                self.tracer.on_admit(
                    req.id, time.perf_counter(), slot, ctx_len=len(ctx),
                    resumed=req.preemptions > 0,
                )
        return admitted

    def _prefill_one(self) -> None:
        """One chunk for the OLDEST still-prefilling slot (FIFO keeps TTFT
        fair); the chunk is fixed-shape, tail-padded and masked."""
        pending = [
            (st.admit_order, i) for i, st in enumerate(self.slots)
            if st is not None and not st.prefill_done
        ]
        if not pending:
            return
        _, slot = min(pending)
        st = self.slots[slot]
        c = self.engine.prefill_chunk
        start = st.prefill_pos
        chunk = st.ctx[start:min(start + c, len(st.ctx) - 1)]
        valid = len(chunk)
        if valid < c:
            chunk = np.pad(chunk, (0, c - valid))
        with timed("serve/prefill_enqueue", rid=st.req.id, start=start,
                   valid=valid) as sp:
            self.engine.prefill(
                self.block_table[slot:slot + 1],
                chunk[None, :].astype(np.int32),
                np.asarray([start], np.int32),
                np.asarray([valid], np.int32),
                slot,
            )
        st.prefill_pos = start + valid
        self.lengths[slot] = st.prefill_pos
        if self.tracer is not None:
            # The instant the chunk was ENQUEUED (never a device
            # completion): the span's end, read at the one place whether
            # or not spans are on.
            self.tracer.on_prefill(st.req.id, sp.end, start, valid)

    def _grow_tables(self) -> np.ndarray:
        """Cover every position the next dispatch may write — up to
        ``waves_per_dispatch`` tokens per decode-ready slot, capped at
        the slot's length limit — evicting the youngest active request
        on pool exhaustion. Returns the dispatch's run mask. Runs only
        with no dispatch in flight (tick() harvests first), so eviction
        never strands in-flight tokens."""
        k = self.engine.waves_per_dispatch
        run = np.zeros((self.engine.max_slots,), bool)
        for slot, st in enumerate(self.slots):
            if st is None or not st.prefill_done:
                continue
            # Highest row this dispatch can write: the k-th token lands
            # at lengths + k - 1, and the final token ever lands at
            # limits - 1 (see _admit's limit math).
            last_pos = min(
                int(self.lengths[slot]) + k - 1,
                max(int(self.limits[slot]) - 1, int(self.lengths[slot])),
            )
            need_idx = last_pos // self.block_len
            while need_idx >= len(st.blocks):
                got = self.allocator.alloc(1)
                if got is None:
                    victim = self._youngest_active()
                    self._evict(victim)
                    # The victim may already have been approved earlier in
                    # this sweep — it no longer runs this wave.
                    run[victim] = False
                    if victim == slot:
                        break
                    continue
                self.block_table[slot, len(st.blocks)] = got[0]
                st.blocks.extend(got)
            if self.slots[slot] is st:  # not evicted above
                run[slot] = True
        return run

    def _youngest_active(self) -> int:
        candidates = [
            (st.admit_order, i) for i, st in enumerate(self.slots)
            if st is not None
        ]
        return max(candidates)[1]

    def _evict(self, slot: int) -> None:
        """Preempt: blocks back to the pool, request to the FRONT of the
        queue with its progress folded into the context — it resumes (not
        restarts) once blocks free up."""
        st = self.slots[slot]
        self.allocator.free(st.blocks)
        st.req.preemptions += 1
        self.preemptions += 1
        self.queue.appendleft(st.req)
        if self.tracer is not None:
            self.tracer.on_evict(st.req.id, time.perf_counter())
        self._clear(slot)

    def _harvest_pending(self) -> list[TickEvent]:
        """Fetch the in-flight dispatch (ONE ``jax.device_get`` for its
        k waves) and replay it onto the host mirrors."""
        if self.pending is None:
            return []
        handle, self.pending = self.pending, None
        toks, done, emitted = self.engine.harvest(handle)
        with span("serve/replay", seq=handle.seq) as sp:
            # `now` is the instant the fetch returned, as harvest read it.
            events = self._replay(
                handle.seq, toks, done, emitted, self.engine.last_harvest_at
            )
            sp.set(tokens=len(events))
        return events

    def _replay(self, seq: int, toks, done, emitted,
                now: float) -> list[TickEvent]:
        """The device's per-wave bookkeeping replayed onto the host
        mirrors: every emitted token appends to its request and advances
        the slot's length; a slot whose ``done`` flag rose frees its
        blocks and is refillable next tick."""
        if self.tracer is not None:
            self.tracer.on_harvest(seq, now)
        emitted_by: dict[int, int] = {}
        finished_ids: list[int] = []
        events = []
        for wave in range(toks.shape[0]):
            for slot in np.nonzero(emitted[wave])[0]:
                st = self.slots[int(slot)]
                tok = int(toks[wave, slot])
                st.req.tokens.append(tok)
                if st.req.first_token_at is None:
                    st.req.first_token_at = now
                st.req.last_token_at = now
                self.tokens_generated += 1
                self.lengths[slot] += 1
                self.last_tok[slot] = tok
                finished = bool(done[wave, slot])
                emitted_by[st.req.id] = emitted_by.get(st.req.id, 0) + 1
                if finished:
                    st.req.finished_at = now
                    self.completed += 1
                    self.allocator.free(st.blocks)
                    self._clear(int(slot))
                    finished_ids.append(st.req.id)
                events.append(TickEvent(st.req, tok, finished))
        if self.tracer is not None and emitted_by:
            # ONE participation event per request per dispatch — its k
            # waves share a single harvest instant anyway.
            for rid, n in emitted_by.items():
                self.tracer.on_tokens(rid, seq, n, now)
            for rid in finished_ids:
                self.tracer.on_finish(rid, now)
        return events

    def _clear(self, slot: int) -> None:
        self.slots[slot] = None
        self.block_table[slot] = 0
        self.lengths[slot] = 0
        self.last_tok[slot] = 0
        self.limits[slot] = 0
        self.temp[slot] = 0.0
        self.top_k[slot] = 0
        self.top_p[slot] = 1.0
        self.eos[slot] = -1
        self.seeds[slot] = 0

    # -- introspection -----------------------------------------------------

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)
