"""The ``decode_pages_live_share`` reader on hand-made spans, and the
program's ``serve/decode_pages`` record it reads, from a tiny engine."""

import numpy as np
import pytest

from benchmark.readers import decode_pages
from benchmark.run import HERE, load_json


def _read(ctx):
    """Through the metric's own file, as ``run.read_per_layer`` does."""
    spec = load_json(HERE / "metrics" / "decode_pages_live_share.json")
    assert spec["reader"] == "decode_pages"
    return decode_pages.read(ctx, **spec["args"])


def test_live_share_on_recorded_spans():
    # Three waves in the stretch [0, 3]: 3 + 5 + 12 live pages of a table
    # of 64 each; a fourth, recorded after the last tick, is outside it.
    rows = [
        ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None),
        ("serve/decode_pages", 0.5, 0.5, 0, {"tick": 0, "live": 3, "table": 64}, 1, 1, None),
        ("serve/tick", 1.0, 2.0, None, {}, 1, 2, None),
        ("serve/decode_pages", 1.5, 1.5, 2, {"tick": 1, "live": 5, "table": 64}, 1, 3, None),
        ("serve/tick", 2.0, 3.0, None, {}, 1, 4, None),
        ("serve/decode_pages", 2.5, 2.5, 4, {"tick": 2, "live": 12, "table": 64}, 1, 5, None),
        ("serve/decode_pages", 3.5, 3.5, None, {"tick": 3, "live": 64, "table": 64}, 1, 6, None),
    ]
    ctx = {"spans": rows, "host": {}, "cell_seconds": 3.0}
    assert _read(ctx) == pytest.approx(100.0 * 20 / 192)


def test_nothing_to_read_is_none(monkeypatch):
    tick = ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None)
    # A stretch, but a program that records no such counter (the parent).
    assert _read({"spans": [tick], "host": {}, "cell_seconds": 1.0}) is None
    # The counter, but no tick to place the stretch by.
    counter = ("serve/decode_pages", 0.5, 0.5, None, {"tick": 0, "live": 3, "table": 64}, 1, 1, None)
    assert _read({"spans": [counter], "host": {}, "cell_seconds": 1.0}) is None
    # No recorder at all.
    import rocket_tpu.obs.spans as span_module

    monkeypatch.delattr(span_module, "recorded")
    assert _read({"host": {}, "cell_seconds": 1.0}) is None


def test_the_engine_writes_the_record_the_reader_reads():
    """A tiny engine under an open span sink: the reader's share equals the
    pages the running slots held over the table, wave by wave."""
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
        for n in (3, 10):
            engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4, temperature=0.0)
        engine.drain()
    finally:
        spans.uninstall(recorder)
    rows = [tuple(e) for e in recorder.events()]
    waves = [r for r in rows if r[0] == "serve/decode_pages"]
    assert waves and all(r[4]["table"] == 2 * 8 and 1 <= r[4]["live"] <= 8 for r in waves)
    share = _read({"spans": rows, "host": {}, "cell_seconds": 1e9})
    assert share == pytest.approx(100.0 * sum(r[4]["live"] for r in waves) / (16 * len(waves)))
