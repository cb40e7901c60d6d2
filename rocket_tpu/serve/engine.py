"""SlotEngine — the compiled fixed-shape step family over a slot pool.

Exactly TWO jit-compiled programs serve the whole request lifecycle:

* the **decode wave scan**: ``waves_per_dispatch`` (k) decode waves in
  ONE compiled program — a ``lax.scan`` whose carry threads the pool
  buffers, per-slot lengths, last tokens and the on-device done/run
  mask, so one host→device dispatch and ONE ``jax.device_get`` amortize
  over k tokens per slot. Each wave is one token for every slot in
  ``[0, max_slots)``: paged attention against the shared block pool,
  per-slot sampling with the knobs (temperature / top-k / top-p / EOS /
  length limit) as RUNTIME arrays, and the carried run mask freezing a
  slot the wave after it emits EOS or hits its limit — mid-scan
  finishes emit nothing further (the early-exit mask; a dispatch whose
  slots ALL finish early still executes its remaining waves, but they
  write only to the reserved trash block);
* the **prefill chunk**: a fixed-size ``(1, prefill_chunk)`` prompt slice
  through the same ``paged_step`` code path, padded + masked at
  the tail, so a prompt of ANY length runs through one compiled program
  and interleaves with decode waves chunk by chunk.

Admitting, evicting and refilling requests only changes array *values*
(block tables, masks, sampling vectors), never shapes or dtypes — the
compiled-once guarantee. Each function counts its own traces by a
Python-side increment in the traced body (trace-time side effect — the
body re-executes only on retrace), which the obs registry exposes as
``serve/decode_traces`` / ``serve/prefill_traces``: the serve test suite
and smoke assert both stay at 1 across 50+ admissions.

The step functions themselves are built by the MODULE-LEVEL builders
:func:`build_decode_wave` / :func:`build_prefill_step` (pure functions of
their arguments, jitted by the engine at construction), and
:func:`abstract_wave_inputs` produces matching ``ShapeDtypeStruct``
argument tuples — which is what lets the static serving auditor
(``rocket_tpu.analysis.serve_audit``) AOT-compile the REAL programs on a
fake backend and prove the retrace/HBM/latency story before any request
is served.

Pool buffers are DONATED through both programs (:data:`DECODE_DONATE` /
:data:`PREFILL_DONATE`), so the pool is updated in place wave over wave.
**What the donated tuple holds**: the page arrays, indexed by block
through the block table, and — for a model with state layers
(``TransformerConfig.slot_state_shapes``) — the per-slot state arrays
``(state layers, max_slots, ...)`` behind them, indexed by slot. The
decode wave's row ``s`` IS slot ``s``; the prefill program of such a model
takes one more argument, the slot its chunk belongs to (a model without
state layers keeps the six-argument program). A slot that does not run in
a wave (run mask false, from the host or frozen by the device's own carry)
keeps its state bitwise; a chunk or a wave at
position 0 starts its slot's state from zeros, so slot reuse and an
evicted request's re-prefill inherit nothing; the k waves of a dispatch
carry the state through the scan with the pages.

**The carry stays on the device.** The scan's last ``(lengths, last token,
run mask)`` is an OUTPUT of the decode program that is never fetched:
:class:`SlotEngine` keeps it beside the pool (``self.carry``) and hands it
to the next dispatch, which takes each slot's three values from it unless
the host marks the slot ``fresh`` — a slot that joins (its prefill just
finished), every slot of a first dispatch. The merge is by VALUE
(``where(fresh, host, carry)``), one program for every mix. So dispatch N+1
does not need wave N's tokens on the host: :meth:`SlotEngine.decode_dispatch`
enqueues it while N still runs, :meth:`SlotEngine.harvest` performs the one
explicit ``jax.device_get`` of N afterwards, and a slot that finished inside
N is frozen in N+1 by the carried mask although the host did not know.
The scheduler keeps up to two dispatches in flight this way, and does its
fetch, replay, admission and the next jit call under a running wave.
"""

from __future__ import annotations

import json
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rocket_tpu.models.sampling import freeze_after_eos, sample_tokens
from rocket_tpu.obs.spans import span, timed
from rocket_tpu.serve.kv_pool import KVPoolSpec

__all__ = [
    "SlotEngine",
    "WaveHandle",
    "build_decode_wave",
    "build_prefill_step",
    "abstract_wave_inputs",
    "DECODE_DONATE",
    "PREFILL_DONATE",
]

#: Donated argument positions of the two compiled programs — the pool,
#: ONE argument: the tuple of arrays the model's layers declare
#: (``(k_pages, v_pages)``, or one latent array: pages by BLOCK; then,
#: for a model with state layers, its state arrays by SLOT,
#: ``KVPoolSpec.arrays``). One definition shared by
#: the engine's jit and the static auditor's AOT compile, so they cannot
#: disagree.
DECODE_DONATE = (1,)
PREFILL_DONATE = (1,)


class WaveHandle(NamedTuple):
    """An in-flight k-wave dispatch: device arrays, fetched (ONE
    ``jax.device_get``) by :meth:`SlotEngine.harvest`. All are
    ``(waves_per_dispatch, max_slots)``: the sampled token per wave, the
    finished flag the wave raised, and whether the slot actually ran
    that wave (a slot frozen mid-scan stops emitting). ``seq`` numbers
    the dispatch: the ``serve/dispatch`` and ``serve/harvest_wait`` spans
    and the request tracer's wave record carry it. ``pairs`` (a model
    with routed layers only) rides along to the same fetch: the expert
    pair counts of this dispatch's waves, and of the prefill chunks
    enqueued before it (done on the device before it is)."""

    tokens: jax.Array    # (k, S) int32
    done: jax.Array      # (k, S) bool
    emitted: jax.Array   # (k, S) bool
    seq: int = -1
    pairs: Optional[tuple] = None   # ((k, layers, held) int32, [chunks...])


def record_expert_pairs(kind: str, tick: int, tokens: int, pairs) -> None:
    """One ``moe/expert_pairs`` record (a span of no length, on when spans
    are): ``pairs`` (routed layers, experts held) int32 of ONE program
    call — a decode wave or a prefill chunk — that processed ``tokens``
    tokens."""
    with span("moe/expert_pairs", kind=kind, tick=int(tick),
              tokens=int(tokens)) as sp:
        sp.set(pairs=json.dumps(pairs.tolist(), separators=(",", ":")))


def build_decode_wave(model, on_trace: Optional[Callable] = None,
                      waves: int = 1) -> Callable:
    """The k-wave decode program for ``model`` — PURE in its arguments
    (params and pool buffers are inputs, not closure state).

    ``waves`` (k) is baked into the trace: a ``lax.scan`` of k decode
    waves whose carry threads (pool, lengths, last token, run mask), so
    the per-slot sampling salt — ``seeds * 1000003 + lengths``, int32 —
    derives ON DEVICE each wave and a slot that finishes mid-scan is
    frozen by the carried mask (its later waves hold the token, route
    their pool writes to the trash block, and emit nothing). k=1 is the
    same scan of length one — one code path, and greedy outputs are
    bit-identical for every k by construction (the per-wave math never
    reads k).

    ``on_trace`` is invoked at TRACE time inside the body (the engine
    passes its retrace counter; the auditor passes its own). Signature::

        decode_wave(params, pages, carry, block_table, lengths,
                    last_tok, run_mask, limits, temp, top_k, top_p,
                    eos, seeds, fresh, key)
            -> (pages, carry, tokens (k, S), done (k, S), emitted (k, S),
                expert_pairs (k, layers, held) or None)

    ``pages`` is the pool, a tuple of arrays (``TransformerLM.paged_step``).
    ``carry`` is the ``(lengths, last_tok, run)`` the PREVIOUS dispatch's
    scan ended with, still on the device; ``fresh`` (S,) bool marks the
    slots whose ``lengths`` / ``last_tok`` the host sets this dispatch.
    Every other slot continues from the carry, and runs only if the carry
    has not frozen it: the host's ``lengths`` and ``last_tok`` may be one
    dispatch stale there, and a slot that finished in the dispatch before
    emits nothing though the host asked for it. ``run_mask`` still stops
    any slot.
    """
    k = int(waves)
    if k < 1:
        raise ValueError(f"build_decode_wave: waves {k} < 1")

    def decode_wave(params, pages, carry, block_table, lengths,
                    last_tok, run_mask, limits, temp, top_k, top_p,
                    eos, seeds, fresh, key):
        if on_trace is not None:
            on_trace()  # trace-time: counts (re)traces only
        held_lengths, held_tok, held_run = carry
        lengths = jnp.where(fresh, lengths, held_lengths)
        last_tok = jnp.where(fresh, last_tok, held_tok)
        run_mask = run_mask & (fresh | held_run)

        def one_wave(carry, _):
            pages, lengths, last_tok, run = carry
            valid = run.astype(jnp.int32)
            logits, pages, pairs = model.paged_step(
                params, last_tok[:, None], pages, block_table,
                lengths, valid,
            )
            # Per-wave salt, derived on device so every wave of the scan
            # samples exactly as k dispatched single waves would (int32
            # wraparound is deterministic; fold_in takes any int32).
            salts = seeds * jnp.int32(1000003) + lengths
            # ``run``: a slot still prefilling carries its request's knobs
            # and must not make a wave of greedy decoders sort.
            nxt = sample_tokens(
                logits, key, salts, temp, top_k, top_p, run=run
            ).astype(jnp.int32)
            done = jnp.zeros(nxt.shape, bool)
            nxt, done = freeze_after_eos(nxt, done, eos)
            done = done | (lengths + valid >= limits)
            # Frozen/masked slots: hold their token (host state stays
            # coherent) and emit nothing this wave.
            nxt = jnp.where(run, nxt, last_tok)
            done = done & run
            carry = (pages, lengths + valid, nxt, run & ~done)
            return carry, (nxt, done, run, pairs)

        init = (tuple(pages), lengths, last_tok, run_mask)
        (pages, *carry), (toks, done, emitted, pairs) = jax.lax.scan(
            one_wave, init, None, length=k
        )
        return pages, tuple(carry), toks, done, emitted, pairs

    return decode_wave


def build_prefill_step(model, on_trace: Optional[Callable] = None) -> Callable:
    """The prefill-chunk step function for ``model``; see
    :func:`build_decode_wave` for the builder contract. Signature::

        prefill_chunk(params, pages, block_table_row, tokens, positions,
                      valid[, slot]) -> (pages, expert_pairs (layers, held)
                                         or None)
    """

    def prefill_chunk_fn(params, pages, block_table, tokens, positions,
                         valid, *slot):
        # ``slot`` (1,) int32: given by the engine only to a model with
        # state layers, whose chunk must find its slot's state.
        if on_trace is not None:
            on_trace()  # trace-time: counts (re)traces only
        _, pages, pairs = model.paged_step(
            params, tokens, pages, block_table, positions, valid, *slot,
        )
        return pages, pairs

    return prefill_chunk_fn


def abstract_wave_inputs(
    model,
    spec: KVPoolSpec,
    *,
    max_slots: int,
    max_blocks_per_seq: int,
    prefill_chunk: int,
    abs_params=None,
):
    """``(decode_args, prefill_args)`` — ``ShapeDtypeStruct`` tuples
    matching the two step functions' signatures, for zero-FLOP AOT
    compilation (``jax.jit(fn).lower(*args).compile()``). The decode
    signature is k-invariant: ``waves`` only changes the program body
    (the scan length), never its inputs.

    ``abs_params`` defaults to ``jax.eval_shape(model.init)['params']``
    run through the same activation-dtype master-cast the engine applies
    (``_decode_params`` evaluated abstractly), so the audited programs
    see exactly the dtypes the live engine feeds.
    """
    from rocket_tpu.models.transformer import _decode_params

    if abs_params is None:
        abs_params = jax.eval_shape(model.init, jax.random.key(0))["params"]
    abs_params = jax.eval_shape(
        lambda p: _decode_params(p, model.config.activation_dtype), abs_params
    )
    s, mb, c = int(max_slots), int(max_blocks_per_seq), int(prefill_chunk)
    pool = tuple(
        jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
        for shape, dtype in spec.arrays
    )
    i32 = jnp.int32
    f32 = jnp.float32
    vec_i = jax.ShapeDtypeStruct((s,), i32)
    vec_f = jax.ShapeDtypeStruct((s,), f32)
    vec_b = jax.ShapeDtypeStruct((s,), jnp.bool_)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode_args = (
        abs_params, pool,
        (vec_i, vec_i, vec_b),                # carry: lengths, last_tok, run
        jax.ShapeDtypeStruct((s, mb), i32),   # block_table
        vec_i,                                # lengths
        vec_i,                                # last_tok
        vec_b,                                # run_mask
        vec_i,                                # limits
        vec_f,                                # temp
        vec_i,                                # top_k
        vec_f,                                # top_p
        vec_i,                                # eos
        vec_i,                                # seeds
        vec_b,                                # fresh
        key,
    )
    prefill_args = (
        abs_params, pool,
        jax.ShapeDtypeStruct((1, mb), i32),   # block_table row
        jax.ShapeDtypeStruct((1, c), i32),    # tokens
        jax.ShapeDtypeStruct((1,), i32),      # position
        jax.ShapeDtypeStruct((1,), i32),      # valid
    )
    if spec.slot_state:
        prefill_args += (jax.ShapeDtypeStruct((1,), i32),)  # slot
    return decode_args, prefill_args


class SlotEngine:
    """Owns the device pool and the two compiled step programs.

    ``model`` is a :class:`~rocket_tpu.models.transformer.TransformerLM`
    (or anything exposing ``paged_step`` with the same signature);
    ``params`` its param tree — float leaves are cast ONCE to the model's
    activation dtype (the same hoisted master-cast ``generate()`` does:
    decode is HBM-bound on parameter streaming). ``waves_per_dispatch``
    (k) sets how many decode waves one compiled dispatch runs — the
    host-dispatch amortization knob
    (``ServeConfig.decode_waves_per_dispatch``).
    """

    def __init__(
        self,
        model,
        params,
        spec: KVPoolSpec,
        *,
        max_slots: int,
        max_blocks_per_seq: int,
        prefill_chunk: int,
        waves_per_dispatch: int = 1,
        key: Optional[jax.Array] = None,
    ) -> None:
        from rocket_tpu.models.transformer import _decode_params

        if max_slots < 1 or max_blocks_per_seq < 1 or prefill_chunk < 1:
            raise ValueError(
                "SlotEngine: max_slots, max_blocks_per_seq and "
                "prefill_chunk must all be >= 1"
            )
        if waves_per_dispatch < 1:
            raise ValueError(
                f"SlotEngine: waves_per_dispatch {waves_per_dispatch} < 1"
            )
        self.model = model
        self.spec = spec
        self.max_slots = int(max_slots)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.prefill_chunk = int(prefill_chunk)
        self.waves_per_dispatch = int(waves_per_dispatch)
        self._params = _decode_params(params, model.config.activation_dtype)
        #: The device pool: the arrays the model's layers declare, as
        #: ONE tuple handed whole to both programs and taken back whole.
        self.pages = spec.init_pages()
        #: The ``(lengths, last_tok, run)`` the last decode dispatch's scan
        #: ended with: an output that stays on the device and is the next
        #: dispatch's input, never fetched. Before the first dispatch it
        #: holds nothing a slot could continue from (no slot runs).
        self.carry = (
            jnp.zeros((self.max_slots,), jnp.int32),
            jnp.zeros((self.max_slots,), jnp.int32),
            jnp.zeros((self.max_slots,), bool),
        )
        #: Expert pair counts of the prefill chunks enqueued since the
        #: last decode dispatch (a model with routed layers only): the
        #: next dispatch's handle takes them to its own fetch.
        self._chunk_pairs: list = []
        #: The scheduler's tick, which it keeps current: the ``tick=`` of
        #: ``moe/expert_pairs``.
        self.tick = 0
        self._key = jax.random.key(0) if key is None else key
        #: Trace counters — incremented at TRACE time inside the compiled
        #: bodies; == 1 each after any number of waves is the no-retrace
        #: proof surfaced through the obs registry.
        self.decode_traces = 0
        self.prefill_traces = 0
        #: Execution counters (host side). ``decode_waves`` counts WAVES
        #: (k per dispatch); ``device_gets`` counts host syncs — the
        #: smoke asserts one per dispatch, i.e. one per k tokens.
        self.decode_waves = 0
        self.decode_dispatches = 0
        self.device_gets = 0
        self.prefill_chunks = 0
        #: Cumulative seconds :meth:`harvest` spent blocked on the
        #: device fetch — what the host loop could NOT overlap.
        self.harvest_wait_s = 0.0
        #: perf_counter instants of the most recent dispatch/harvest —
        #: the tick-boundary timestamps request tracing reads (host
        #: floats only; never a device sync).
        self.last_dispatch_at: Optional[float] = None
        self.last_harvest_at: Optional[float] = None

        def count_decode():
            self.decode_traces += 1

        def count_prefill():
            self.prefill_traces += 1

        self._decode = jax.jit(
            build_decode_wave(model, on_trace=count_decode,
                              waves=self.waves_per_dispatch),
            donate_argnums=DECODE_DONATE,
        )
        self._prefill = jax.jit(
            build_prefill_step(model, on_trace=count_prefill),
            donate_argnums=PREFILL_DONATE,
        )

    @property
    def k_pages(self):
        """The first pool array (K, or the latent array)."""
        return self.pages[0]

    # -- compiled-step drivers ---------------------------------------------

    def decode_dispatch(self, block_table, lengths, last_tok, run_mask,
                        limits, temp, top_k, top_p, eos, seeds,
                        fresh=None) -> WaveHandle:
        """Enqueue one k-wave decode dispatch over every slot. All inputs
        are host arrays of shape ``(max_slots, ...)`` with fixed dtypes
        (the scheduler's mirrors); returns a :class:`WaveHandle` of
        device arrays WITHOUT synchronizing — the host keeps scheduling
        while the device runs, and :meth:`harvest` fetches the results.

        ``fresh`` (S,) bool: the slots that take ``lengths`` and
        ``last_tok`` from the host; the others continue from the carry the
        dispatch before left on the device (see :func:`build_decode_wave`),
        so this may be called before that dispatch was harvested. ``None``
        is a caller that keeps no dispatch in flight (:meth:`decode`):
        every slot is the host's."""
        if fresh is None:
            fresh = np.ones((self.max_slots,), bool)
        seq = self.decode_dispatches
        self.decode_dispatches += 1
        self.decode_waves += self.waves_per_dispatch
        with timed("serve/dispatch", seq=seq) as sp:
            if sp.on:
                # ``inflight``: the dispatches not yet harvested when this
                # one was enqueued (1 = it queued behind a running wave).
                sp.set(occupancy=int(run_mask.sum()),
                       inflight=seq - self.device_gets)
            self.last_dispatch_at = sp.start
            self.pages, self.carry, toks, done, emitted, pairs = self._decode(
                self._params, self.pages, self.carry, block_table,
                lengths, last_tok, run_mask, limits, temp, top_k, top_p,
                eos, seeds, fresh, self._key,
            )
        if pairs is not None:
            pairs = (pairs, self.tick, self._chunk_pairs)
            self._chunk_pairs = []
        return WaveHandle(tokens=toks, done=done, emitted=emitted, seq=seq,
                          pairs=pairs)

    def harvest(self, handle: WaveHandle):
        """Fetch one dispatch's results to host numpy — the single
        explicit device sync per k decoded tokens. Returns
        ``(tokens, done, emitted)`` as ``(k, S)`` numpy arrays. The span
        around the fetch is the time the host waited for the device; its
        two instants feed ``harvest_wait_s`` and ``last_harvest_at``."""
        self.device_gets += 1
        fetch = (handle.tokens, handle.done, handle.emitted)
        if handle.pairs is not None:
            wave_pairs, tick, chunks = handle.pairs
            fetch += (wave_pairs, [c[-1] for c in chunks])
        with timed("serve/harvest_wait", seq=handle.seq) as sp:
            out = jax.device_get(fetch)
        self.last_harvest_at = sp.end
        self.harvest_wait_s += sp.end - sp.start
        if handle.pairs is not None and sp.on:
            # The counter moe/expert_pairs: per program call and routed
            # layer, the pairs each held expert received and the tokens
            # the call processed — from arrays this fetch brought anyway.
            for wave, emitted in zip(out[3], out[2]):
                record_expert_pairs("decode", tick, int(emitted.sum()), wave)
            for (at, valid, _), pairs in zip(chunks, out[4]):
                record_expert_pairs("prefill", at, valid, pairs)
        return out[:3]

    def decode(self, block_table, lengths, last_tok, run_mask, limits,
               temp, top_k, top_p, eos, seeds):
        """Dispatch-and-wait convenience (tests, simple drivers):
        one k-wave dispatch harvested immediately, every slot the host's."""
        return self.harvest(self.decode_dispatch(
            block_table, lengths, last_tok, run_mask, limits, temp,
            top_k, top_p, eos, seeds,
        ))

    def prefill(self, block_table_row, tokens, position, valid,
                slot: Optional[int] = None) -> None:
        """One prefill chunk for ONE slot: ``block_table_row`` ``(1, MB)``,
        ``tokens`` ``(1, prefill_chunk)`` (tail-padded), ``position``/
        ``valid`` ``(1,)``; ``slot`` is handed to the program of a model
        with per-slot state, which reads and writes that slot's, and is
        required there. Fire-and-forget — nothing is fetched, so chunks
        pipeline behind decode waves."""
        where = ()
        if self.spec.slot_state:
            if slot is None:
                raise ValueError(
                    "SlotEngine.prefill: a model with per-slot state needs "
                    "the slot its chunk belongs to"
                )
            where = (np.asarray([slot], np.int32),)
        self.prefill_chunks += 1
        self.pages, pairs = self._prefill(
            self._params, self.pages, block_table_row, tokens, position,
            valid, *where,
        )
        if pairs is not None:
            self._chunk_pairs.append((self.tick, int(valid[0]), pairs))
