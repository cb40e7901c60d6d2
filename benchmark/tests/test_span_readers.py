"""The readers of the program's thread CPU time (``span_cpu``: the two
``dispatch_cpu_*`` metrics) and of its garbage-collection spans
(``span_share``: ``gc_share.*``), on hand-made spans through the metrics'
own files, on the parent's spans, and on what a tiny engine records."""

import numpy as np
import pytest

from benchmark.readers import span_cpu, span_share
from benchmark.run import HERE, load_json

READERS = {"span_cpu": span_cpu, "span_share": span_share}


def _read(metric, ctx):
    """Through the metric's own file, as ``run.read_per_layer`` does."""
    spec = load_json(HERE / "metrics" / f"{metric}.json")
    return READERS[spec["reader"]].read(ctx, **spec["args"])


def _serve_rows(cpu, gcs=()):
    """Ticks of one second from 0 to len(cpu); in tick i a dispatch of
    0.1 s whose thread spent ``cpu[i]`` seconds (``None``: no id, the
    parent's span); ``gcs``: ``(name, start, end)`` collections."""
    rows = []
    for i, c in enumerate(cpu):
        rows.append(("serve/tick", float(i), i + 1.0, None, {"tick": i}, 1, 3 * i, None))
        ids = {"seq": i} if c is None else {"seq": i, "cpu_s": c}
        rows.append(("serve/dispatch", i + 0.5, i + 0.6, 3 * i, ids, 1, 3 * i + 1, None))
    rows += [(n, s, e, 0, {"gen": 0, "collected": 1, "cpu_s": e - s}, 1, 1000 + k, "gc")
             for k, (n, s, e) in enumerate(gcs)]
    return rows


def _ctx(rows):
    return {"spans": rows, "host": {}, "cell_seconds": 1e9}


@pytest.fixture
def listening():
    from rocket_tpu.obs import spans

    spans.install_gc_listener()


def test_dispatch_cpu_reads_a_mean_and_a_share():
    ctx = _ctx(_serve_rows([0.09, 0.02, 0.05, 0.1]))
    assert _read("dispatch_cpu_ms.mean", ctx) == pytest.approx(65.0)
    assert _read("dispatch_cpu_share", ctx) == pytest.approx(100 * 0.26 / 0.4)


def test_a_clock_that_steps_in_ticks_is_read_by_its_sums():
    """The chip's host keeps thread CPU time in steps of 10 ms: a 0.1 s
    dispatch that computes throughout reads 0.1 s or 0.1 s +- one step,
    short ones 0 or a step; the sums still come out at what was spent."""
    ctx = _ctx(_serve_rows([0.11, 0.09, 0.1, 0.1, 0.1]))
    assert _read("dispatch_cpu_ms.mean", ctx) == pytest.approx(100.0)
    assert _read("dispatch_cpu_share", ctx) == pytest.approx(100.0)


def test_an_unknown_statistic_is_refused():
    with pytest.raises(ValueError, match="stat"):
        span_cpu.read(_ctx(_serve_rows([0.05])), name="serve/dispatch", stat=50)


def test_dispatch_cpu_leaves_out_what_lies_outside_the_stretch():
    rows = _serve_rows([0.08, 0.08])
    rows.append(("serve/dispatch", 5.0, 5.1, None, {"cpu_s": 0.0}, 1, 99, None))
    ctx = {"spans": rows, "host": {"stretch": (0.0, 2.0)}, "cell_seconds": 1e9}
    assert _read("dispatch_cpu_share", ctx) == pytest.approx(80.0)


def test_the_parents_spans_carry_no_cpu_time_and_read_nothing():
    ctx = _ctx(_serve_rows([None, None, None]))
    assert _read("dispatch_cpu_ms.mean", ctx) is None
    assert _read("dispatch_cpu_share", ctx) is None
    assert _read("dispatch_cpu_share", {"spans": [], "host": {}}) is None


def test_gc_share_is_the_union_of_collections_cut_to_the_stretch(listening):
    gcs = [("serve/gc", 0.2, 0.3), ("serve/gc", 0.25, 0.35),     # overlap: 0.15
           ("host/gc", 1.7, 1.8),                                # the driver's loop
           ("serve/gc", 3.95, 4.5),                              # cut at the close
           ("serve/gc", 6.0, 6.5)]                               # after the close
    ctx = {"spans": _serve_rows([0.05] * 4, gcs), "host": {"stretch": (0.0, 4.0)},
           "cell_seconds": 1e9}
    assert _read("gc_share.serve", ctx) == pytest.approx(100 * (0.15 + 0.1 + 0.05) / 4.0)


def test_gc_share_of_a_train_wave_counts_its_data_phase(listening):
    rows = [("train/wave", float(i), i + 1.0, None, {"step": i}, 1, i, None) for i in range(5)]
    rows += [("data/gc", 1.2, 1.22, 1, {}, 1, 10, "gc"), ("train/gc", 3.5, 3.53, 3, {}, 1, 11, "gc")]
    assert _read("gc_share.train", _ctx(rows)) == pytest.approx(100 * 0.05 / 5.0)


def test_no_collection_in_the_stretch_reads_zero(listening):
    assert _read("gc_share.serve", _ctx(_serve_rows([0.05] * 3))) == 0.0


def test_a_program_without_the_listener_reads_nothing(listening, monkeypatch):
    import rocket_tpu.obs.spans as span_module

    ctx = _ctx(_serve_rows([0.05] * 3, [("serve/gc", 0.1, 0.2)]))
    monkeypatch.delattr(span_module, "_on_gc")
    assert _read("gc_share.serve", ctx) is None
    assert _read("gc_share.train", ctx) is None


def test_the_engine_writes_what_the_readers_read():
    """A tiny engine under an open span sink, with one forced collection
    inside a tick: every dispatch carries ``cpu_s``, no more than its wall
    time, and the collection is a ``serve/gc`` child of the tick."""
    import gc

    import jax

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.obs import spans
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=1, num_heads=4, dropout=0.0))
    params = jax.jit(model.init)(jax.random.key(0))["params"]
    engine = ServeEngine(model, params, ServeConfig(
        max_slots=2, block_len=4, prefill_chunk=4, max_model_len=32))
    recorder = spans.SpanRecorder()
    spans.install(recorder)
    try:
        for n in (3, 6):
            engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=10)
        engine.step()
        with spans.span("serve/tick", tick=-1):
            gc.collect()
        engine.drain()
    finally:
        spans.uninstall(recorder)
    rows = [tuple(e) for e in recorder.events()]
    ctx = _ctx(rows)
    dispatch = [r for r in rows if r[0] == "serve/dispatch"]
    assert dispatch and all(0.0 <= r[4]["cpu_s"] <= r[2] - r[1] + 1e-3 for r in dispatch)
    wall_mean = 1e3 * sum(r[2] - r[1] for r in dispatch) / len(dispatch)
    assert 0.0 < _read("dispatch_cpu_ms.mean", ctx) <= wall_mean + 1.0
    assert 0.0 < _read("dispatch_cpu_share", ctx) <= 100.0 + 1e-3 * 100 / min(
        r[2] - r[1] for r in dispatch)
    assert any(r[0] == "serve/gc" and r[4]["gen"] == 2 for r in rows)
    assert _read("gc_share.serve", ctx) > 0.0
