"""ServeEngine — the user-facing serving facade.

``submit()`` enqueues a request (token ids, or text when a tokenizer is
attached), ``step()`` advances the engine one scheduling round,
``stream()`` yields a request's output incrementally (detokenized when
possible), ``report()`` summarizes latency/throughput percentiles, and
the obs wiring publishes slot/pool/queue gauges plus per-request spans
into an attached :class:`~rocket_tpu.obs.telemetry.Telemetry` so a serve
run's ``telemetry.json`` carries the full serving story.

Sizing defaults: the pool holds ``max_slots`` full-length sequences plus
the reserved trash block — no oversubscription, so the engine never
preempts unless you shrink ``num_blocks`` deliberately (the knob that
turns on back-pressure testing). ``docs/serving.md`` walks the math.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Iterator, Optional, Union

import jax
import numpy as np

from rocket_tpu.obs import spans
from rocket_tpu.serve.engine import SlotEngine
from rocket_tpu.serve.kv_pool import BlockAllocator, KVPoolSpec
from rocket_tpu.serve.scheduler import Request, Scheduler, TickEvent

__all__ = ["ServeConfig", "ServeEngine", "StreamDetokenizer"]


@dataclass
class ServeConfig:
    """Engine sizing. ``None`` fields derive from the model config."""

    max_slots: int = 8
    block_len: int = 16
    #: Pool blocks INCLUDING the reserved trash block 0. Default: enough
    #: for every slot at full context (no oversubscription); set smaller
    #: to exercise back-pressure/eviction.
    num_blocks: Optional[int] = None
    #: Longest context (prompt + generation) a single request may use.
    #: Default: the model's max_seq_len.
    max_model_len: Optional[int] = None
    prefill_chunk: int = 16
    #: Pool dtype. Default: the model's activation dtype (or f32).
    dtype: Optional[str] = None
    #: Decode waves per device dispatch (k): one compiled ``lax.scan``
    #: of k waves amortizes the host→device dispatch and the one
    #: ``jax.device_get`` over k tokens per slot. Raising k multiplies
    #: steady-state tokens-per-dispatch but adds up to k-1 wave times to
    #: TTFT and makes the scheduler react to EOS/admission every k
    #: tokens — docs/serving.md ("when to raise k") has the tradeoff.
    decode_waves_per_dispatch: int = 1
    #: Completed Request records retained for ``result()``/``stream()``
    #: readers; beyond this the OLDEST finished requests are dropped so a
    #: long-running server's host memory stays bounded (``release()``
    #: drops one eagerly).
    max_completed_requests: int = 4096
    #: Per-request timeline tracing (``rocket_tpu.obs.reqtrace``): ON by
    #: default — the recorder is O(waves + requests) host dict work with
    #: no device syncs, so steady-state tokens/sec is unchanged within
    #: noise (gated by the serve bench + smoke). Set False to prove it.
    reqtrace: bool = True

    def resolve(self, model_config) -> tuple[KVPoolSpec, int, int, int]:
        """``(pool_spec, max_blocks_per_seq, num_blocks,
        waves_per_dispatch)`` for a model.

        THE sizing math — one implementation shared by the live engine
        and the static serving auditor
        (``rocket_tpu.analysis.serve_audit``), so the audited pool AND
        the audited k-wave program are byte-identical to the served
        ones."""
        mc = model_config
        max_len = self.max_model_len or mc.max_seq_len
        if max_len > mc.max_seq_len:
            raise ValueError(
                f"ServeConfig.max_model_len {max_len} exceeds the model's "
                f"max_seq_len {mc.max_seq_len}"
            )
        waves = int(self.decode_waves_per_dispatch)
        if waves < 1:
            raise ValueError(
                f"ServeConfig.decode_waves_per_dispatch {waves} < 1"
            )
        mb = -(-max_len // self.block_len)  # ceil: blocks per sequence
        lanes = tuple(mc.kv_pool_lanes)
        h_kv = (mc.num_kv_heads or mc.num_heads) if len(lanes) == 2 else 1
        num_blocks = self.num_blocks or (1 + self.max_slots * mb)
        spec = KVPoolSpec(
            # The layers that cache pages; the others carry a state a slot.
            num_layers=mc.cache_layers,
            slot_state=tuple(mc.slot_state_shapes),
            max_slots=self.max_slots,
            window=mc.window,
            sparse=mc.sparse_attention if mc.sparse_layers else None,
            num_blocks=num_blocks,
            block_len=self.block_len,
            # What a layer caches per token is the model's to declare: K
            # and V of Hkv*D lanes, or one latent array (one "head" as
            # wide as the array).
            lanes=lanes,
            num_kv_heads=h_kv,
            head_dim=lanes[0] // h_kv,
            dtype=self.dtype or mc.activation_dtype or "float32",
        )
        return spec, mb, num_blocks, waves


class StreamDetokenizer:
    """Incremental detokenization for one stream: feed token ids, get the
    NEW text suffix. Re-decodes the running token list each push (decoders
    may merge across token boundaries — byte-level BPE), which is O(n) per
    token on host strings; bounded by per-request generation lengths."""

    def __init__(self, tokenizer) -> None:
        self._tokenizer = tokenizer
        self._tokens: list[int] = []
        self._emitted = 0

    def push(self, token: int) -> str:
        self._tokens.append(int(token))
        text = self._tokenizer.decode(self._tokens)
        out = text[self._emitted:]
        self._emitted = len(text)
        return out


def _percentiles(values: list, qs=(0.5, 0.9, 0.99)) -> Optional[dict]:
    if not values:
        return None
    arr = np.sort(np.asarray(values, np.float64))
    out = {f"p{int(q * 100)}": float(np.quantile(arr, q)) for q in qs}
    out["mean"] = float(arr.mean())
    out["count"] = int(arr.size)
    return out


class ServeEngine:
    """Continuous-batching serving over one model + param tree.

    ``telemetry``: an enabled :class:`~rocket_tpu.obs.telemetry.Telemetry`
    gets serve gauges/histograms in its registry and one span per
    completed request in its trace (category ``serve``); None keeps the
    engine obs-free. The engine never owns/flushes the telemetry — the
    caller (CLI, Runtime) decides when files are written.
    """

    def __init__(
        self,
        model,
        params,
        config: Optional[ServeConfig] = None,
        *,
        tokenizer=None,
        telemetry=None,
        key=None,
    ) -> None:
        cfg = config or ServeConfig()
        spec, mb, num_blocks, waves = cfg.resolve(model.config)
        self.config = cfg
        # Compile events become compile/* spans, collections */gc spans
        # while spans are on (process-wide, once).
        spans.install_compile_listener()
        spans.install_gc_listener()
        self.engine = SlotEngine(
            model, params, spec,
            max_slots=cfg.max_slots,
            max_blocks_per_seq=mb,
            prefill_chunk=cfg.prefill_chunk,
            waves_per_dispatch=waves,
            key=key,
        )
        self.scheduler = Scheduler(self.engine, BlockAllocator(num_blocks))
        self.tokenizer = tokenizer
        self.telemetry = telemetry
        #: Per-request timeline recorder (None when cfg.reqtrace=False).
        #: Exposed on the telemetry object so the exporter can drain
        #: finished timelines + tail exemplars into the shard dir each
        #: export window.
        self.tracer = None
        if cfg.reqtrace:
            from rocket_tpu.obs.reqtrace import RequestTracer

            self.tracer = RequestTracer(
                max_records=max(cfg.max_completed_requests, 1)
            )
            self.scheduler.tracer = self.tracer
            if telemetry is not None and getattr(telemetry, "enabled", False):
                telemetry.reqtrace = self.tracer
        #: Owns every mutable record below AND the scheduler/engine tick
        #: path: ``submit``/``step``/``release``/``reset_metrics`` may be
        #: called from concurrent request threads (``stream()`` readers
        #: step the engine), and the host mirrors must never interleave
        #: with a wave in flight (RKT109 race lint).
        self._lock = threading.Lock()
        self.requests: dict[int, Request] = {}
        self._finished_order: list[int] = []  # completion-ordered rids
        # Latency records (seconds), trimmed to a bounded tail so week-long
        # servers don't grow host memory with per-token floats.
        self._ttft: list[float] = []
        self._itl: list[float] = []
        self._latency_cap = 200_000
        self._last_emit: dict[int, float] = {}  # rid -> last emit time
        self._first_wave_at: Optional[float] = None
        self._last_event_at: Optional[float] = None
        self._occupancy_sum = 0
        self._ticks = 0
        # Baselines let reset_metrics() window the engine-side
        # cumulative counters to the steady state.
        self._base_harvest_wait_s = 0.0
        self._base_device_gets = 0
        self._base_dispatches = 0
        # Windowed device-trace capture (obs.prof): armed by
        # capture_trace(), driven tick-by-tick inside step().
        self._trace_window: Optional[tuple] = None
        self._trace_session = None
        #: The last closed window's trace-event file (perfetto JSON) —
        #: render with ``python -m rocket_tpu.obs prof``.
        self.trace_file: Optional[str] = None

    # -- intake ------------------------------------------------------------

    def submit(
        self,
        prompt: Union[str, np.ndarray, list],
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token_id: Optional[int] = None,
    ) -> int:
        """Enqueue one request; returns its id. ``prompt`` may be text
        when a tokenizer is attached. Refusals (invalid sampling knobs,
        prompts the pool can never hold, text without a tokenizer) count
        as ``serve/rejected_requests`` before re-raising — submit-time
        rejections must not vanish from the metrics plane."""
        if isinstance(prompt, str):
            if self.tokenizer is None:
                with self._lock:
                    self._reject_locked()
                raise ValueError(
                    "ServeEngine.submit: text prompt needs a tokenizer"
                )
            prompt = self.tokenizer.encode(prompt)
        req = Request(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_token_id=eos_token_id,
        )
        with self._lock:
            try:
                rid = self.scheduler.submit(req)
            except ValueError:
                self._reject_locked()
                raise
            self.requests[rid] = req
            # Admission queue depth at SUBMIT granularity — a burst of
            # arrivals between wave boundaries is visible to scrapes,
            # not just the post-tick _publish() snapshot.
            self._publish_queue_locked()
        return rid

    def _reject_locked(self) -> None:
        self.scheduler.rejected += 1
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.registry.counter("serve/rejected_requests").inc()
            self._publish_queue_locked()

    def _publish_queue_locked(self) -> None:
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.registry.gauge("serve/queue_depth").set(
                self.scheduler.queue_depth
            )

    # -- stepping ----------------------------------------------------------

    def step(self) -> list[TickEvent]:
        """One scheduling round; records latency metrics and publishes the
        obs gauges. Serialized under the engine lock — concurrent
        ``stream()`` readers may each drive ``step()``.

        With ``decode_waves_per_dispatch`` > 1 a request's k tokens of
        one dispatch land in the same harvest, so inter-token latency is
        AMORTIZED: each of the n tokens a request receives this step
        contributes ``(now - previous emit) / n`` — the per-token cadence
        the k-wave scan actually delivers, which is what the static
        roofline's predicted ITL models. A request's very first batch
        contributes only its TTFT (there is no previous emit to span)."""
        with self._lock:
            self._trace_poll_locked()
            tick = self.scheduler.ticks
            with spans.span("serve/tick", tick=tick) as sp:
                # Step-annotated so a device trace gets per-tick windows
                # (measured wave attribution per tick, obs.prof).
                with jax.profiler.StepTraceAnnotation(
                    "serve_tick", step_num=tick
                ) if sp.on else spans.OFF:
                    events = self.scheduler.tick()
                self._record_tick_locked(events)
            return events

    def _record_tick_locked(self, events: list[TickEvent]) -> None:
        """The latency bookkeeping and gauge publishing at the end of a
        tick: ``serve/tick``'s self time."""
        self._ticks += 1
        self._occupancy_sum += self.scheduler.active_slots
        now = time.perf_counter()
        if events:
            if self._first_wave_at is None:
                self._first_wave_at = now
            self._last_event_at = now
        batch: dict[int, int] = {}
        for ev in events:
            batch[ev.request.id] = batch.get(ev.request.id, 0) + 1
        seen: dict[int, int] = {}
        for ev in events:
            req = ev.request
            prev = self._last_emit.get(req.id)
            first_of_batch = req.id not in seen
            seen[req.id] = seen.get(req.id, 0) + 1
            if prev is None:
                if first_of_batch:
                    self._ttft.append(
                        req.first_token_at - req.submitted_at
                    )
            else:
                # Amortized inter-token latency for this batch.
                itl = (now - prev) / batch[req.id]
                self._itl.append(itl)
                if self.telemetry is not None and self.telemetry.enabled:
                    # Registry-side distribution: what /metrics and
                    # the ITL-p99 SLO watch live, across resets of
                    # the host-list aggregates.
                    self.telemetry.registry.histogram(
                        "serve/itl_s", base=1e-6
                    ).observe(itl)
            if ev.finished:
                self._last_emit.pop(req.id, None)
                self._finish_span(req)
                self._retire_locked(req.id)
            elif seen[req.id] == batch[req.id]:
                self._last_emit[req.id] = now
        del self._ttft[:-self._latency_cap]
        del self._itl[:-self._latency_cap]
        self._publish()

    def _retire_locked(self, rid: int) -> None:
        """Bound the completed-request record: keep the newest
        ``max_completed_requests`` finished Requests readable, drop the
        oldest beyond that. Caller holds ``self._lock``."""
        self._finished_order.append(rid)
        cap = max(self.config.max_completed_requests, 0)
        while len(self._finished_order) > cap:
            old = self._finished_order.pop(0)
            self.requests.pop(old, None)
            if self.tracer is not None:
                # Timeline retention follows Request retention — the
                # finished record was already queued for persistence at
                # finish time, so only the in-memory copy goes.
                self.tracer.release(old)

    def release(self, rid: int) -> None:
        """Drop a finished request's record eagerly (long-running servers
        that consume results as they stream need no retention at all)."""
        with self._lock:
            req = self.requests.get(rid)
            if req is not None and not req.finished:
                raise ValueError(
                    f"ServeEngine.release: request {rid} still live"
                )
            self.requests.pop(rid, None)
            try:
                self._finished_order.remove(rid)
            except ValueError:
                pass
            if self.tracer is not None:
                self.tracer.release(rid)

    # -- windowed device-trace capture -------------------------------------

    def capture_trace(self, window, trace_dir: str) -> None:
        """Arm a windowed device-trace capture over engine ticks.

        ``window`` is ``(start, stop)`` tick indices (or the CLI's
        ``"A:B"`` string): the ``jax.profiler`` session opens before
        tick ``start`` and closes before tick ``stop``, each traced
        tick wrapped in a ``StepTraceAnnotation`` — the same capture
        path training and ``analysis calib`` use, so
        ``python -m rocket_tpu.obs prof`` renders the result."""
        from rocket_tpu.obs.prof import TraceSession, parse_step_window

        if isinstance(window, str):
            window = parse_step_window(window)
        start, stop = int(window[0]), int(window[1])
        if start < 0 or stop <= start:
            raise ValueError(
                f"capture_trace: window {window!r} needs 0 <= start < stop"
            )
        with self._lock:
            self._trace_window = (start, stop)
            self._trace_session = TraceSession(trace_dir)

    def _trace_poll_locked(self) -> None:
        """Open/close the armed trace window for the tick about to run."""
        if self._trace_session is None:
            return
        start, stop = self._trace_window
        if self._trace_session.active:
            if self._ticks >= stop:
                self.trace_file = self._trace_session.stop()
        elif start <= self._ticks < stop:
            self._trace_session.start()

    def finish_trace(self) -> Optional[str]:
        """Close a still-open capture window (e.g. the engine drained
        before the window's stop tick); returns the trace file."""
        with self._lock:
            if self._trace_session is not None \
                    and self._trace_session.active:
                self.trace_file = self._trace_session.stop()
            return self.trace_file

    def drain(self, max_ticks: int = 100_000) -> list[TickEvent]:
        """Step until every submitted request completed."""
        events = []
        for _ in range(max_ticks):
            if self.scheduler.idle:
                self.finish_trace()
                return events
            events.extend(self.step())
        raise RuntimeError(f"ServeEngine.drain: not idle after {max_ticks} ticks")

    def stream(self, rid: int, max_ticks: int = 100_000) -> Iterator:
        """Yield request ``rid``'s output incrementally — text pieces with
        a tokenizer, raw token ids without — stepping the engine while the
        request is live. Interleaves fine with other requests: tokens for
        everyone else keep landing on their Request records."""
        req = self.requests[rid]
        detok = (
            StreamDetokenizer(self.tokenizer)
            if self.tokenizer is not None else None
        )
        emitted = 0
        for _ in range(max_ticks):
            while emitted < len(req.tokens):
                tok = req.tokens[emitted]
                emitted += 1
                yield detok.push(tok) if detok is not None else tok
            if req.finished:
                if self.tracer is not None:
                    self.tracer.on_detokenize(rid, time.perf_counter())
                return
            if self.scheduler.idle:
                raise RuntimeError(
                    f"ServeEngine.stream: engine idle but request {rid} "
                    "unfinished"
                )
            self.step()
        raise RuntimeError(f"ServeEngine.stream: no finish in {max_ticks} ticks")

    def result(self, rid: int) -> Request:
        return self.requests[rid]

    def text(self, rid: int) -> str:
        if self.tokenizer is None:
            raise ValueError("ServeEngine.text: no tokenizer attached")
        return self.tokenizer.decode(self.requests[rid].tokens)

    # -- observability -----------------------------------------------------

    def _finish_span(self, req: Request) -> None:
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        tel.spans.add(
            f"serve/request[{req.id}]", "serve",
            req.submitted_at, req.finished_at - req.submitted_at,
        )
        tel.registry.histogram("serve/ttft_s", base=1e-4).observe(
            req.first_token_at - req.submitted_at
        )
        if self.tracer is not None:
            phases = self.tracer.phases(req.id)
            if phases is not None:
                # Per-phase latency distributions — where request wall
                # time went, fleet-wide (the waterfall's aggregate twin).
                reg = tel.registry
                reg.histogram("serve/queue_wait_s", base=1e-6).observe(
                    phases["queue_s"]
                )
                reg.histogram("serve/prefill_s", base=1e-6).observe(
                    phases["prefill_s"]
                )
                reg.histogram("serve/decode_s", base=1e-6).observe(
                    phases["decode_s"]
                )
                if phases["preempted_s"] > 0:
                    reg.histogram(
                        "serve/preempted_s", base=1e-6
                    ).observe(phases["preempted_s"])

    def _publish(self) -> None:
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        reg = tel.registry
        sched = self.scheduler
        reg.gauge("serve/slots_active").set(sched.active_slots)
        reg.gauge("serve/queue_depth").set(sched.queue_depth)
        reg.gauge("serve/blocks_free_fraction").set(
            sched.allocator.free_fraction
        )
        reg.gauge("serve/kv_pool_bytes").set(self.engine.spec.pool_bytes)
        reg.gauge("serve/tokens_generated").set(sched.tokens_generated)
        reg.gauge("serve/requests_completed").set(sched.completed)
        reg.gauge("serve/preemptions").set(sched.preemptions)
        # The compiled-once proof, surfaced where telemetry.json lands it.
        reg.gauge("serve/decode_traces").set(self.engine.decode_traces)
        reg.gauge("serve/prefill_traces").set(self.engine.prefill_traces)
        # Dispatch amortization: host syncs vs waves (the k-wave scan).
        reg.gauge("serve/decode_dispatches").set(
            self.engine.decode_dispatches
        )
        reg.gauge("serve/device_gets").set(self.engine.device_gets)

    def reset_metrics(self) -> None:
        """Zero the latency/throughput aggregates — NOT the compile-trace
        counters, which are the engine-lifetime no-retrace proof. Call
        while idle (e.g. after a warmup ``drain()``): benchmarks warm the
        compiled steps with a few requests, reset, then measure
        steady-state serving without compile time in the percentiles.

        Also windows the registry-side ``serve/*`` histograms
        (``serve/ttft_s``, ``serve/itl_s``): the Prometheus endpoint and
        ``telemetry.json`` percentiles must describe the same
        steady-state window the report does, not the warmup spikes the
        host lists just dropped."""
        with self._lock:
            self._ttft.clear()
            self._itl.clear()
            if self.telemetry is not None and self.telemetry.enabled:
                self.telemetry.registry.reset("serve/")
            self._first_wave_at = None
            self._last_event_at = None
            self._occupancy_sum = 0
            self._ticks = 0
            self._base_harvest_wait_s = self.engine.harvest_wait_s
            self._base_device_gets = self.engine.device_gets
            self._base_dispatches = self.engine.decode_dispatches
            sched = self.scheduler
            sched.submitted = sched.queue_depth + sched.active_slots
            sched.completed = 0
            sched.preemptions = 0
            sched.tokens_generated = 0
            sched.waves_idle = 0
            sched.rejected = 0

    def report(self) -> dict:
        """Latency/throughput summary for this engine's lifetime.

        Reads the lock-owned aggregates, so a snapshot taken during a
        concurrent ``step()``/``reset_metrics()`` is never torn."""
        with self._lock:
            return self._report_locked()

    def _dispatch_stats_locked(self) -> dict:
        """Dispatch-amortization accounting since the last
        ``reset_metrics()``: decoded tokens per device dispatch, host
        syncs, and the host time spent blocked on them (per tick: the
        ``serve/harvest_wait`` span beside its ``serve/tick``)."""
        eng = self.engine
        gets = eng.device_gets - self._base_device_gets
        dispatches = eng.decode_dispatches - self._base_dispatches
        wait = eng.harvest_wait_s - self._base_harvest_wait_s
        tokens = self.scheduler.tokens_generated
        return {
            "waves_per_dispatch": eng.waves_per_dispatch,
            "decode_dispatches": dispatches,
            "device_get_count": gets,
            "tokens_per_dispatch": (
                round(tokens / dispatches, 3) if dispatches else None
            ),
            "harvest_wait_s": round(wait, 6),
        }

    def _report_locked(self) -> dict:
        sched = self.scheduler
        busy = None
        if self._first_wave_at is not None and self._last_event_at is not None:
            busy = max(self._last_event_at - self._first_wave_at, 1e-9)
        return {
            "requests": {
                "submitted": sched.submitted,
                "completed": sched.completed,
                "queued": sched.queue_depth,
                "preemptions": sched.preemptions,
                "rejected": sched.rejected,
            },
            "tokens_generated": sched.tokens_generated,
            "tokens_per_sec": (
                None if busy is None else sched.tokens_generated / busy
            ),
            "time_to_first_token_s": _percentiles(self._ttft),
            "inter_token_latency_s": _percentiles(self._itl),
            "compiled": {
                "decode_traces": self.engine.decode_traces,
                "prefill_traces": self.engine.prefill_traces,
                "decode_waves": self.engine.decode_waves,
                "prefill_chunks": self.engine.prefill_chunks,
            },
            "dispatch": self._dispatch_stats_locked(),
            # Retained-request phase breakdown + ITL-gap attribution
            # (None with reqtrace off or nothing finished).
            "phases": (
                self.tracer.aggregate() if self.tracer is not None else None
            ),
            "pool": {
                # What a layer caches per token, by pool array: K and V
                # lanes, or one latent array (the bytes below sum them).
                "lanes": list(self.engine.spec.lanes),
                "num_blocks": self.engine.spec.num_blocks,
                "block_len": self.engine.spec.block_len,
                "block_bytes": self.engine.spec.block_bytes,
                "kv_pool_bytes": self.engine.spec.pool_bytes,
                "free_fraction": sched.allocator.free_fraction,
            },
            "slots": {
                "max_slots": self.engine.max_slots,
                "occupancy_mean": (
                    self._occupancy_sum / self._ticks if self._ticks else 0.0
                ),
            },
        }
