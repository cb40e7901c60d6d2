"""Continuous-batching request scheduler — the host-side policy half.

Every ``tick()`` is one serving step with up to TWO decode dispatches in
flight: dispatch N+1 is enqueued BEFORE dispatch N is fetched, so it sits
in the device's queue while N runs and starts the instant N ends, and the
host does its fetch, replay, admission, growing and the next jit call under
a running wave instead of between two:

1. **admit** queued requests into free slots while the block pool can
   cover their prompts (all-or-nothing — a request never half-admits);
   a free slot was freed at an earlier tick's harvest, and the dispatch
   still in flight either did not run it or holds it frozen (below);
2. **prefill** one fixed-size chunk of the oldest still-prefilling slot
   (chunked prefill: long prompts trickle in a chunk per tick and never
   stall the decode latency of running requests). Prefill is
   fire-and-forget and still-prefilling slots are never in a decode
   wave, so the chunk queues behind the dispatch in flight — the pool
   buffers thread program-order through both, so dataflow serializes
   them on device without a host sync;
3. **grow** each decode-ready slot's block table ONE DISPATCH AHEAD: the
   mirrors of a slot that the dispatch in flight runs are k rows behind
   the device, so the table must cover that dispatch's rows and those of
   the one about to be made (``lengths + 2k - 1``, capped at the slot's
   limit);
4. **dispatch** the next k-wave decode over all decode-ready slots. The
   device carries ``(lengths, last token, run mask)`` from one dispatch
   to the next (``serve/engine.py``); the host sets them only for the
   slots it marks ``fresh`` — a slot that just finished prefill and
   joins. A slot that finished INSIDE the dispatch in flight (limit or
   EOS) is frozen in this one by the device's own carried mask: it
   writes to the trash block and emits nothing, although the host did
   not know yet;
5. **harvest** the dispatch made one tick EARLIER: one
   ``jax.device_get`` fetches its k waves of tokens; emitted tokens
   stream out, finished slots free their blocks and are refillable on
   the very next tick — the caller detokenizes/streams the events while
   the newer dispatch runs on device.

**Pool exhaustion drains first.** When the allocator cannot cover step 3,
the tick harvests the dispatch in flight BEFORE it grows (the old order,
for that tick): the mirrors are then exact, finished slots have returned
their blocks, and only if the pool is still short is the YOUNGEST active
request evicted — its blocks return to the pool and it re-queues at the
FRONT with its generated tokens folded into the prompt, so it resumes
exactly where it stopped after re-prefill (back-pressure, never OOM).
Eviction runs strictly AFTER the harvest of everything in flight, so a
preempted slot never has tokens in flight to lose. The scheduler decides
this from the allocator alone; there is no setting.

The scheduler owns host-side numpy mirrors of every per-slot array the
compiled wave consumes (block table, lengths, sampling vectors, masks).
Admission/eviction mutate the mirrors only — shapes and dtypes are fixed
at construction, which is what keeps the engine's compiled-once guarantee
(asserted via the trace counters in ``serve/engine.py``). The pipelining
invariant: a slot's ``lengths`` / ``last_tok`` mirrors are read by the
device only at the dispatch the slot is ``fresh`` in, and are exact then;
for every other running slot they trail the device by the dispatch in
flight, and harvest replays the device's own per-wave bookkeeping onto
them. Between a dispatch and its harvest the host touches only slots that
dispatch did not run or holds frozen (admission, prefill, the clearing of
a slot that finished in the dispatch before), and every mirror a dispatch
read was copied to device at dispatch time.
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
        #: Rows a window layer's ring holds a slot (0: no window layer).
        self.window = engine.spec.window
        #: The sparse layers' selection (None: no sparse layer).
        self.sparse = engine.spec.sparse
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
        #: Slots whose ``lengths`` / ``last_tok`` the NEXT dispatch takes
        #: from the mirrors: empty, prefilling and just-joined slots. A
        #: slot that a dispatch ran continues from the device's carry
        #: until it is cleared.
        self.fresh = np.ones((s,), bool)
        self.slots: list[Optional[_Slot]] = [None] * s
        self.queue: deque[Request] = deque()
        #: The newest decode dispatch, harvested AFTER the next one is
        #: enqueued (two in flight for that moment, else this one).
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
        """One scheduling round: admit / prefill one chunk / grow tables
        one dispatch ahead / dispatch the next k waves / harvest the
        dispatch made one tick earlier. Returns the tokens the HARVESTED
        dispatch emitted (one tick behind the device — the pipelining);
        an idle engine returns []. A tick that finds the pool short
        harvests first, and evicts only then."""
        # The engine dates its ``moe/expert_pairs`` records by this.
        self.engine.tick = self.ticks
        if self.queue:
            with span("serve/admit", tick=self.ticks) as sp:
                sp.set(admitted=self._admit())
        self._prefill_one()
        older, events = self.pending, []
        drained = older is not None and self._pool_short()
        if drained:
            # The old order, for this tick: with nothing in flight
            # (``pending`` None is what _last_row reads) the mirrors are
            # exact and an eviction strands no token.
            self.pending = None
            events = self._harvest(older)
            older = None
        with span("serve/grow") as sp:
            evicted = self.preemptions
            run = self._grow_tables()
            sp.set(evicted=self.preemptions - evicted, drained=int(drained))
        if run.any():
            # The counter serve/decode_pages: how much of the block table
            # this wave's running slots hold, which is all the decode
            # kernel walks (``live`` pages of ``table`` entries),
            # ``sample``, the branch its sampling takes on the device, and
            # for a model with window layers ``window_rows``, the ring rows
            # each of them attends. The host's view at dispatch: a slot
            # that finishes in flight still counts.
            with span("serve/decode_pages", tick=self.ticks) as sp:
                if sp.on:
                    sp.set(
                        live=int((self.lengths[run] // self.block_len
                                  + 1).sum()),
                        table=int(self.block_table.size),
                        sample=SAMPLE_BRANCHES[int(sample_branch(
                            self.temp, self.top_k, self.top_p, run))],
                    )
                    if self.window:
                        sp.set(window_rows=int(np.minimum(
                            self.lengths[run] + 1, self.window).sum()))
                    if self.sparse is not None:
                        sp.set(**self._sparse_pages(self.lengths[run]))
            # The dispatched ``fresh`` is never written again (the device
            # may still read it): the mirror moves on to a new array.
            fresh, self.fresh = self.fresh, self.fresh & ~run
            self.pending = self.engine.decode_dispatch(
                self.block_table, self.lengths, self.last_tok, run,
                self.limits, self.temp, self.top_k, self.top_p, self.eos,
                self.seeds, fresh,
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
        else:
            self.pending = None
        if older is not None:
            events = self._harvest(older)
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
            if sp.on and self.sparse is not None:
                sp.set(**self._sparse_pages(np.arange(start, start + valid)))
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

    def _sparse_pages(self, positions) -> dict:
        """What the sparse layers' queries at ``positions`` read: per K/V
        head and query, ``sparse_pages`` the pages attended (``topk`` of
        the live ones past ``dense_len``, all below) and ``sparse_live``
        the pages held, each summed over queries and K/V heads."""
        cfg, heads = self.sparse, self.engine.spec.num_kv_heads
        live = np.asarray(positions, np.int64) // cfg.block_size + 1
        read = np.where(np.asarray(positions) >= cfg.dense_len,
                        np.minimum(live, cfg.topk), live)
        return {"sparse_pages": int(read.sum()) * heads,
                "sparse_live": int(live.sum()) * heads}

    def _last_row(self, slot: int) -> int:
        """The highest row the NEXT dispatch may write for ``slot``: its
        k-th token lands at ``lengths + k - 1``, and the final token ever
        at ``limits - 1`` (see _admit's limit math). A slot the dispatch
        in flight runs (not ``fresh``) has a mirror k rows behind the
        device; -1 where those rows already reach its limit — it finishes
        in flight and does not run again."""
        k = self.engine.waves_per_dispatch
        length, limit = int(self.lengths[slot]), int(self.limits[slot])
        if self.pending is not None and not self.fresh[slot]:
            if length + k >= limit:
                return -1
            length += k
        return min(length + k - 1, max(limit - 1, length))

    def _pool_short(self) -> bool:
        """Whether the allocator cannot cover what :meth:`_grow_tables`
        is about to ask for. A slot's table covers the row its mirror
        names, so a tick asks at most the blocks of 2k more rows a slot:
        with that many free for every slot the count is skipped."""
        free = self.allocator.num_free
        k = self.engine.waves_per_dispatch
        if free >= len(self.slots) * (2 * k // self.block_len + 1):
            return False
        want = sum(
            max(0, self._last_row(slot) // self.block_len + 1 - len(st.blocks))
            for slot, st in enumerate(self.slots)
            if st is not None and st.prefill_done
        )
        return want > free

    def _grow_tables(self) -> np.ndarray:
        """Cover every position the next dispatch may write — up to
        ``waves_per_dispatch`` tokens per decode-ready slot beyond what
        the dispatch in flight writes, capped at the slot's length limit
        — evicting the youngest active request on pool exhaustion.
        Returns the dispatch's run mask. It meets an empty allocator only
        with no dispatch in flight (tick() harvests first when
        :meth:`_pool_short`), so eviction never strands in-flight
        tokens."""
        run = np.zeros((self.engine.max_slots,), bool)
        for slot, st in enumerate(self.slots):
            if st is None or not st.prefill_done:
                continue
            last_pos = self._last_row(slot)
            if last_pos < 0:
                continue
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

    def _harvest(self, handle) -> list[TickEvent]:
        """Fetch one dispatch (ONE ``jax.device_get`` for its k waves)
        and replay it onto the host mirrors."""
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
        self.fresh[slot] = True
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
