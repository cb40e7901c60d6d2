"""rocket_tpu.obs.spans: the one span primitive, its recorder and sink, the
thread CPU time on every span, the compile and collection listeners, the
legs of a request's first token, and where the spans lie in a serve tick and
a train wave.

A garbage collection can start inside any test while spans are on and adds
a ``*/gc`` span; the assertions on which spans were recorded leave those out
(:func:`_no_gc`), and those on ``ids`` leave out the ``cpu_s`` every span
carries (:func:`_ids`).

The profiler session of ``test_span_is_in_the_profilers_trace_with_its_rid``
is opened inside that test, and it is the only test here that opens one."""

import gc
import glob
import threading
import time

import jax
import numpy as np
import optax
import pytest

import rocket_tpu as rt
from rocket_tpu import optim
from rocket_tpu.models.mlp import MLP
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.obs import spans
from rocket_tpu.obs.reqtrace import PREFILL_LEGS, RequestTracer
from rocket_tpu.obs.spans import SpanRecorder
from rocket_tpu.obs.telemetry import Telemetry
from rocket_tpu.runtime.context import Runtime
from rocket_tpu.serve import ServeConfig, ServeEngine


@pytest.fixture
def sink():
    """A recorder installed as the sink: every span is on, as under an
    enabled Telemetry."""
    rec = SpanRecorder()
    spans.install(rec)
    yield rec
    spans.uninstall(rec)


def _no_gc(events):
    """The spans that are not garbage collections."""
    return [ev for ev in events if not ev.name.endswith("/gc")]


def _ids(ev):
    """A span's identifiers without the ``cpu_s`` the primitive adds."""
    return {k: v for k, v in ev.ids.items() if k != "cpu_s"}


def _since(mark):
    """The default recorder's spans that started after ``mark``."""
    return [ev for ev in spans.recorded() if ev.start >= mark]


# -- the primitive -----------------------------------------------------------


def test_span_records_parent_ids_and_thread(sink):
    with spans.span("outer", tick=7) as outer:
        with spans.span("inner", rid=3) as inner:
            inner.set(tokens=2)
        with spans.span("sibling"):
            pass
    by_name = {ev.name: ev for ev in _no_gc(sink.events())}
    assert set(by_name) == {"outer", "inner", "sibling"}
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == by_name["outer"].id
    assert by_name["sibling"].parent == by_name["outer"].id
    assert _ids(by_name["outer"]) == {"tick": 7}
    assert _ids(by_name["inner"]) == {"rid": 3, "tokens": 2}
    assert by_name["inner"].tid == threading.get_ident()
    # A child lies inside its parent; the span object shows its instants.
    assert outer.start <= by_name["inner"].start
    assert by_name["sibling"].end <= outer.end
    assert sink.open_spans() == {}


def _spin(cpu_seconds):
    """Compute until this thread has spent ``cpu_seconds`` of CPU."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


def test_an_on_span_carries_its_threads_cpu_time(sink):
    """The body's CPU is counted whatever the machine's load; a share near
    1 needs the core to itself, so the busy span may try a few times."""
    shares = []
    for _ in range(5):
        with spans.span("serve/probe") as sp:
            _spin(0.02)
        ev = _no_gc(sink.events())[-1]
        wall = ev.end - ev.start
        assert (ev.name, ev.start, ev.end) == ("serve/probe", sp.start, sp.end)
        assert 0.02 <= ev.ids["cpu_s"] <= wall + 1e-3
        shares.append(ev.ids["cpu_s"] / wall)
        if shares[-1] >= 0.8:
            break
    assert max(shares) >= 0.8, shares
    with spans.span("serve/probe"):
        time.sleep(0.05)
    ev = _no_gc(sink.events())[-1]
    assert 0.0 <= ev.ids["cpu_s"] < 0.2 * (ev.end - ev.start)


def test_timed_with_spans_off_never_reads_the_thread_clock(monkeypatch):
    def boom():
        raise AssertionError("thread_time read with spans off")

    monkeypatch.setattr(spans.time, "thread_time", boom)
    with spans.timed("serve/dispatch") as t:
        pass
    assert not t.on and t.end >= t.start
    with spans.span("serve/tick") as sp:
        pass
    assert sp is spans.OFF


def test_recorder_is_a_ring_that_keeps_the_newest_and_counts_the_dropped():
    rec = SpanRecorder(max_events=3)
    for i in range(10):
        rec.add(f"s{i}", None, float(i), 0.5)
    assert [ev.name for ev in rec.events()] == ["s7", "s8", "s9"]
    assert len(rec) == 3 and rec.dropped == 7
    assert rec.to_chrome_trace()["otherData"]["dropped"] == 7


def test_span_is_off_without_a_profiler_session_or_telemetry():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    mark = time.perf_counter()
    sp = spans.span("nothing", rid=1)
    assert sp is spans.OFF and not sp.on
    with sp as inside:
        inside.set(more=1)
        assert spans._default.open_spans() == {}
    assert inside.start is None and inside.end is None
    # The timed form reads the clock and still records nothing.
    with spans.timed("nothing") as t:
        pass
    assert not t.on and t.end >= t.start >= mark
    assert _since(mark) == []


def test_span_is_in_the_profilers_trace_with_its_rid(tmp_path):
    mark = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("serve/probe", rid=41) as sp:
            assert sp.on
            sp.set(tokens=5)
    finally:
        jax.profiler.stop_trace()
    # Recorded in the default sink, on the recorder's clock ...
    (event,) = [ev for ev in _since(mark) if ev.name == "serve/probe"]
    assert _ids(event) == {"rid": 41, "tokens": 5}
    # ... and in the profiler's own file, with its identifiers as stats.
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = [
        dict(e.stats) for plane in data.planes for line in plane.lines
        for e in line.events if e.name == "serve/probe"
    ]
    assert len(found) == 1
    assert found[0]["rid"] == 41 and found[0]["tokens"] == 5
    # The session is closed: the primitive is off again.
    assert spans.span("after") is spans.OFF


def test_telemetry_span_and_the_bare_primitive_share_one_recorder(tmp_path):
    tel = Telemetry(enabled=True)
    tel.start()
    try:
        mark = time.perf_counter()
        with tel.span("run/phase", cat="step", step=1):
            with spans.span("bare/inside", rid=9):
                pass
        spans.add_span("req/queue", mark, mark + 0.25, rid=9)
    finally:
        tel.close(str(tmp_path), write=False)
    events = {ev.name: ev for ev in _no_gc(tel.spans.events())}
    assert set(events) == {"run/phase", "bare/inside", "req/queue"}
    assert events["bare/inside"].parent == events["run/phase"].id
    assert events["run/phase"].cat == "step" and _ids(events["run/phase"]) == {"step": 1}
    assert tel.goodput.totals()["step"] > 0.0
    # Never written twice: the default recorder saw none of them.
    assert _since(mark) == []
    # Closed, the sink is the default again and the primitive is off.
    assert spans.span("after") is spans.OFF


def test_disabled_telemetry_hands_span_to_the_bare_primitive(sink):
    tel = Telemetry(enabled=False)
    with tel.span("data/next", cat="data_wait"):
        pass
    assert [ev.name for ev in _no_gc(sink.events())] == ["data/next"]
    assert len(tel.spans) == 0


# -- compile events ----------------------------------------------------------


def test_compile_listener_adds_spans_and_counts_for_an_enabled_telemetry(tmp_path):
    spans.install_compile_listener()
    spans.install_compile_listener()  # once, however often it is asked
    from jax._src import monitoring

    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(spans._on_compile_event) == 1

    mark = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    got = _since(mark - 5.0)
    names = {ev.name for ev in got if ev.end >= mark}
    assert {"compile/trace", "compile/lower", "compile/backend"} <= names
    backend = [ev for ev in got if ev.name == "compile/backend" and ev.end >= mark]
    assert len(backend) == 1 and backend[0].ids["fun"]
    assert backend[0].end > backend[0].start

    tel = Telemetry(enabled=True)
    tel.start()
    try:
        jax.jit(lambda x: x * 5 - 2)(np.arange(9.0)).block_until_ready()
    finally:
        tel.close(str(tmp_path), write=False)
    counters = tel.registry.snapshot()["counters"]
    assert counters["compile/events"] >= 3
    assert any(ev.name == "compile/backend" for ev in tel.spans.events())


# -- garbage collections ----------------------------------------------------


def _remove_gc_listener():
    while spans._on_gc in gc.callbacks:
        gc.callbacks.remove(spans._on_gc)


@pytest.mark.parametrize("path, name", [
    ("serve/tick", "serve/gc"), ("train/wave", "train/gc"), (None, "host/gc"),
])
def test_a_collection_is_a_span_named_for_the_path_it_interrupts(sink, path, name):
    spans.install_gc_listener()
    if path is None:
        gc.collect()
        outer = None
    else:
        with spans.span(path, tick=0) as sp:
            gc.collect()
        (outer,) = [ev for ev in sink.events() if ev.name == path]
    # gc.collect() is a collection of generation 2 (under a forced low
    # threshold the interpreter may start another one here: it lies alike).
    full = [ev for ev in sink.events() if ev.ids.get("gen") == 2]
    assert full
    for held in full:
        assert held.name == name and held.cat == "gc"
        assert held.parent == (None if outer is None else outer.id)
        assert held.ids["collected"] >= 0
        assert 0.0 <= held.ids["cpu_s"] <= held.end - held.start + 1e-3
        if outer is not None:
            assert sp.start <= held.start <= held.end <= sp.end
    assert sink.open_spans() == {}


def test_a_collection_with_spans_off_records_nothing():
    spans.install_gc_listener()
    assert spans.span("serve/tick") is spans.OFF
    mark = time.perf_counter()
    gc.collect()
    assert not [ev for ev in _since(mark) if ev.name.endswith("/gc")]
    assert spans._gc_open is None


def test_a_collection_that_began_with_spans_off_is_not_recorded(sink):
    spans.install_gc_listener()
    info = {"generation": 7, "collected": 0, "uncollectable": 0}   # no real one's
    spans.uninstall(sink)
    spans._on_gc("start", info)
    spans.install(sink)
    spans._on_gc("stop", info)
    assert not [ev for ev in sink.events() if ev.ids.get("gen") == 7]


def test_the_engine_and_the_runtime_install_the_gc_listener_once(tiny_lm, tmp_path):
    for build in (lambda: _serve_some(tiny_lm),
                  lambda: Runtime(mesh_shape={"data": 8}, seed=0,
                                  project_dir=str(tmp_path))):
        _remove_gc_listener()
        build()
        build()
        assert gc.callbacks.count(spans._on_gc) == 1
    spans.install_gc_listener()
    assert gc.callbacks.count(spans._on_gc) == 1


# -- the legs of a request's first token -------------------------------------


def _legs_admit_chunks_wave(tracer):
    tracer.on_submit(1, 10.0, prompt_len=300, max_new_tokens=4)
    tracer.on_admit(1, 10.3, slot=0, ctx_len=300)
    for t in (10.9, 11.2, 11.5):
        tracer.on_prefill(1, t, start=0, valid=128)
    seq = tracer.on_dispatch(occupancy=1, t=11.6)
    tracer.on_harvest(seq, 12.3)
    tracer.on_tokens(1, seq, 1, 12.3)
    tracer.on_finish(1, 12.9)
    return {"prefill_wait_s": 0.6, "prefill_run_s": 0.6, "first_token_s": 0.8}


def _legs_chunkless_context(tracer):
    tracer.on_submit(1, 0.0, prompt_len=1, max_new_tokens=2)
    tracer.on_admit(1, 0.125, slot=0, ctx_len=1)
    seq = tracer.on_dispatch(occupancy=1, t=0.25)
    tracer.on_harvest(seq, 0.75)
    tracer.on_tokens(1, seq, 1, 0.75)
    tracer.on_finish(1, 1.0)
    return {"prefill_wait_s": 0.0, "prefill_run_s": 0.0, "first_token_s": 0.625}


def _legs_across_an_eviction_and_resume(tracer):
    tracer.on_submit(1, 0.0, prompt_len=40, max_new_tokens=8)
    tracer.on_admit(1, 1.0, slot=0, ctx_len=40)
    tracer.on_prefill(1, 1.3, start=0, valid=16)
    tracer.on_prefill(1, 1.7, start=16, valid=16)
    s0 = tracer.on_dispatch(occupancy=1, t=1.8)
    tracer.on_harvest(s0, 2.1)
    tracer.on_tokens(1, s0, 1, 2.1)
    tracer.on_evict(1, 3.0)
    # Second residency: evicted again before its first wave.
    tracer.on_admit(1, 5.0, slot=1, ctx_len=41, resumed=True)
    tracer.on_prefill(1, 5.2, start=0, valid=16)
    tracer.on_evict(1, 5.9)
    # Third residency runs to the end.
    tracer.on_admit(1, 7.0, slot=0, ctx_len=41, resumed=True)
    tracer.on_prefill(1, 7.1, start=0, valid=16)
    tracer.on_prefill(1, 7.4, start=16, valid=16)
    s1 = tracer.on_dispatch(occupancy=1, t=7.5)
    tracer.on_harvest(s1, 7.9)
    tracer.on_tokens(1, s1, 1, 7.9)
    tracer.on_finish(1, 8.5)
    return {
        "prefill_wait_s": 0.3 + 0.2 + 0.1,
        "prefill_run_s": 0.4 + 0.0 + 0.3,
        "first_token_s": 0.4 + 0.7 + 0.5,
    }


@pytest.mark.parametrize("scenario", [
    _legs_admit_chunks_wave,
    _legs_chunkless_context,
    _legs_across_an_eviction_and_resume,
], ids=["admit_chunks_wave", "chunkless_context", "eviction_and_resume"])
def test_the_three_legs_sum_to_prefill_s_to_the_float(sink, scenario):
    tracer = RequestTracer()
    want = scenario(tracer)
    record = tracer.timeline(1)
    phases, legs = record["phases"], record["prefill_legs"]
    assert tuple(legs) == PREFILL_LEGS
    for leg in PREFILL_LEGS:
        assert legs[leg] == pytest.approx(want[leg], abs=1e-9), leg
    assert sum(legs.values()) == pytest.approx(phases["prefill_s"], abs=2e-6)
    # `phases` stays the four-way partition of [submit, finish]: a reader
    # that adds up its values (scripts/serve_smoke.py) still gets the wall.
    assert set(phases) == {"queue_s", "prefill_s", "decode_s", "preempted_s"}
    assert sum(phases.values()) == pytest.approx(record["total_s"], abs=4e-6)
    # The legs went to the sink as req/* spans of this rid, laid end to
    # end from the submit instant, unrounded.
    legs = {ev.name: ev for ev in _no_gc(sink.events())}
    assert list(legs) == ["req/queue", "req/prefill_wait", "req/prefill_run",
                          "req/first_token", "req/decode"]
    assert all(ev.ids == {"rid": 1} for ev in legs.values())
    assert legs["req/queue"].end == pytest.approx(legs["req/prefill_wait"].start)
    seconds = {name: ev.end - ev.start for name, ev in legs.items()}
    for leg in PREFILL_LEGS:
        assert seconds["req/" + leg[:-2]] == pytest.approx(want[leg], abs=1e-9)
    assert seconds["req/queue"] == pytest.approx(phases["queue_s"], abs=1e-6)
    assert seconds["req/decode"] == pytest.approx(phases["decode_s"], abs=1e-6)


def test_timeline_prefill_s_is_exactly_the_sum_of_its_legs():
    from rocket_tpu.obs.reqtrace import _Timeline

    tl = _Timeline(1, 0.1, 5, 5, max_events=64)
    tl.admit(0.30000000000000004)
    tl.chunk(0.7000000000000001)
    tl.chunk(1.1)
    tl.wave(1.9000000000000001, 1)
    tl.evict(2.2)
    tl.admit(3.3)
    tl.chunk(3.5000000000000004)
    tl.wave(4.1, 1)
    assert tl.prefill_s == tl.prefill_wait_s + tl.prefill_run_s + tl.first_token_s
    assert tl.prefill_s == pytest.approx((1.9 - 0.3) + (4.1 - 3.3))


# -- where the spans lie ------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    config = TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=2, num_heads=4,
        dropout=0.0,
    )
    model = TransformerLM(config)
    variables = jax.jit(model.init)(jax.random.key(0))
    return model, variables


def _serve_some(tiny_lm, telemetry=None):
    model, variables = tiny_lm
    engine = ServeEngine(
        model, variables["params"],
        ServeConfig(max_slots=2, block_len=4, prefill_chunk=4, max_model_len=32),
        telemetry=telemetry,
    )
    rng = np.random.default_rng(0)
    rids = [
        engine.submit(rng.integers(1, 64, size=n), max_new_tokens=3)
        for n in (9, 1, 6, 11)
    ]
    engine.drain()
    assert all(engine.result(r).finished for r in rids)
    return engine, rids


def test_serve_tick_children_lie_inside_their_tick_and_do_not_overlap(tiny_lm, sink):
    engine, rids = _serve_some(tiny_lm)
    events = _no_gc(sink.events())
    ticks = [ev for ev in events if ev.name == "serve/tick"]
    assert len(ticks) >= 6
    assert [ev.ids["tick"] for ev in ticks] == list(range(len(ticks)))
    kinds = set()
    for tick in ticks:
        children = sorted(
            (ev for ev in events if ev.parent == tick.id), key=lambda ev: ev.start
        )
        for child in children:
            assert child.name.startswith("serve/")
            assert tick.start <= child.start <= child.end <= tick.end
            kinds.add(child.name)
        for a, b in zip(children, children[1:]):
            assert a.end <= b.start, (a.name, b.name)
        # The order of a tick: admit, enqueue a chunk, grow, the wave's
        # page count, dispatch the next, THEN harvest and replay the one
        # before it (the pool is roomy: no tick drains first).
        order = [c.name for c in children]
        assert order == sorted(order, key=[
            "serve/admit", "serve/prefill_enqueue", "serve/grow",
            "serve/decode_pages", "serve/dispatch", "serve/harvest_wait",
            "serve/replay",
        ].index)
    assert kinds == {
        "serve/admit", "serve/prefill_enqueue", "serve/harvest_wait",
        "serve/replay", "serve/grow", "serve/decode_pages", "serve/dispatch",
    }
    # One identifier joins a dispatch, the fetch that waited for it, its
    # replay and the tracer's wave record.
    by_seq = {}
    for ev in events:
        if ev.name in ("serve/dispatch", "serve/harvest_wait", "serve/replay"):
            by_seq.setdefault(ev.ids["seq"], []).append(ev.name)
    assert by_seq and all(
        names == ["serve/dispatch", "serve/harvest_wait", "serve/replay"]
        for names in by_seq.values()
    )
    waves = [
        e["seq"] for rid in rids
        for e in engine.tracer.timeline(rid)["events"] if e["ev"] == "wave"
    ]
    assert set(waves) <= set(by_seq)
    dispatched = [ev for ev in events if ev.name == "serve/dispatch"]
    assert all(1 <= ev.ids["occupancy"] <= 2 for ev in dispatched)
    # ``inflight``: the dispatches unharvested when this one was enqueued.
    # The first finds none; in steady decoding each queues behind one.
    inflight = [ev.ids["inflight"] for ev in dispatched]
    assert inflight[0] == 0 and set(inflight) == {0, 1}
    assert inflight.count(1) >= len(inflight) - 2
    starts = {ev.ids["seq"]: ev.start for ev in dispatched}
    waited = {ev.ids["seq"]: ev.start for ev in events
              if ev.name == "serve/harvest_wait"}
    for seq, behind in zip(sorted(starts)[1:], inflight[1:]):
        assert (starts[seq] < waited[seq - 1]) == bool(behind), seq
    grows = [_ids(ev) for ev in events if ev.name == "serve/grow"]
    assert grows and all(ids == {"evicted": 0, "drained": 0} for ids in grows)
    admitted = sum(ev.ids["admitted"] for ev in events if ev.name == "serve/admit")
    assert admitted == len(rids)
    # A request's legs share its rid with its chunks' spans.
    chunk_rids = {ev.ids["rid"] for ev in events if ev.name == "serve/prefill_enqueue"}
    leg_rids = {ev.ids["rid"] for ev in events if ev.name == "req/first_token"}
    assert chunk_rids <= leg_rids == set(rids)


def test_the_tracer_is_fed_from_one_place_with_spans_on_or_off(tiny_lm, sink, monkeypatch):
    """An admission is dated by a clock read AT the admission (inside
    ``serve/admit``, after its opening), a chunk by the END of its
    ``serve/prefill_enqueue``: what an untraced run reads at the same two
    places, so the legs are one quantity in both."""
    seen = {"on_admit": [], "on_prefill": []}
    for hook, instants in seen.items():
        def spy(self, rid, t, *args, _hook=getattr(RequestTracer, hook),
                _instants=instants, **kwargs):
            _instants.append(t)
            return _hook(self, rid, t, *args, **kwargs)
        monkeypatch.setattr(RequestTracer, hook, spy)
    _serve_some(tiny_lm)
    events = sink.events()
    admit_spans = [ev for ev in events if ev.name == "serve/admit"]
    assert len(set(seen["on_admit"])) == 4
    for t in seen["on_admit"]:
        assert any(ev.start < t <= ev.end for ev in admit_spans)
    assert seen["on_prefill"] == [
        ev.end for ev in events if ev.name == "serve/prefill_enqueue"
    ]


def test_without_profiler_or_telemetry_a_serve_run_records_only_legs_and_compiles(tiny_lm):
    mark = time.perf_counter()
    _, rids = _serve_some(tiny_lm)
    names = {ev.name for ev in _since(mark)}
    assert names and all(n.startswith(("req/", "compile/")) for n in names), names
    legs = [ev for ev in _since(mark) if ev.name == "req/first_token"]
    assert sorted(ev.ids["rid"] for ev in legs) == sorted(rids)


def cross_entropy(batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        batch["logits"], batch["label"]
    ).mean()


def _train_tree(runtime, tmp_path):
    rng = np.random.default_rng(0)
    data = [
        {"image": rng.normal(size=8).astype(np.float32), "label": np.int32(i % 4)}
        for i in range(96)
    ]
    module = rt.Module(
        MLP(in_features=8, num_classes=4, hidden=(16,)),
        capsules=[rt.Loss(cross_entropy),
                  rt.Optimizer(optim.adam(), learning_rate=1e-2)],
    )
    return rt.Launcher(
        [rt.Looper([rt.Dataset(data, batch_size=32), module],
                   tag="train", progress=False)],
        num_epochs=1, runtime=runtime,
    )


def test_without_profiler_or_telemetry_a_train_run_records_only_compiles(tmp_path):
    mark = time.perf_counter()
    runtime = Runtime(mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path))
    _train_tree(runtime, tmp_path).launch()
    names = {ev.name for ev in _since(mark)}
    assert names and all(n.startswith("compile/") for n in names), names


def test_train_wave_holds_the_data_and_dispatch_spans(tmp_path, sink):
    runtime = Runtime(mesh_shape={"data": 8}, seed=0, project_dir=str(tmp_path))
    _train_tree(runtime, tmp_path).launch()
    events = sink.events()
    waves = [ev for ev in events if ev.name == "train/wave"]
    assert [ev.ids["step"] for ev in waves[:3]] == [0, 1, 2]
    inside = {
        wave.ids["step"]: [ev.name for ev in events if ev.parent == wave.id]
        for wave in waves
    }
    # The first wave compiles under its own name; later ones dispatch.
    assert any(n.startswith("compile/train_step[") for n in inside[0])
    assert "data/next" in inside[1] and "train/step_dispatch" in inside[1]
    assert "train/step_dispatch" not in inside[0]
    # Capsule.dispatch's per-event spans keep their gate: telemetry only.
    assert not any(ev.name.endswith(".launch") for ev in events)
