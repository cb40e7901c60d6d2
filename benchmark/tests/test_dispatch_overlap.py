"""The ``dispatch_overlap_share`` reader on hand-made spans, and the
``inflight`` id of the program's ``serve/dispatch`` span that it reads, from
a tiny engine."""

import numpy as np
import pytest

from benchmark.readers import dispatch_overlap
from benchmark.run import HERE, load_json


def _read(ctx):
    """Through the metric's own file, as ``run.read_per_layer`` does."""
    spec = load_json(HERE / "metrics" / "dispatch_overlap_share.json")
    assert spec["reader"] == "dispatch_overlap"
    return dispatch_overlap.read(ctx, **spec["args"])


def _rows(inflight):
    """One tick and one dispatch a second; the dispatch AFTER the last tick
    lies outside the stretch. ``None``: a span with no ``inflight``."""
    rows = []
    for i, behind in enumerate(inflight):
        ids = {"seq": i, "occupancy": 3}
        if behind is not None:
            ids["inflight"] = behind
        if i < len(inflight) - 1:
            rows.append(("serve/tick", float(i), i + 1.0, None, {}, 1, 2 * i, None))
        rows.append(("serve/dispatch", i + 0.5, i + 0.6, 2 * i, ids, 1, 2 * i + 1, None))
    return rows


@pytest.mark.parametrize("inflight, share", [
    ([0, 1, 1, 1, 0], 75.0),
    ([1, 1, 1, 0], 100.0),
    ([0, 0, 0], 0.0),
    # Two behind (no scheduler makes it) still counts as overlapped.
    ([0, 2, 1, 0, 1], 50.0),
    # The parent's spans: a dispatch, but no ``inflight`` id.
    ([None, None, None], None),
])
def test_overlap_share_on_recorded_spans(inflight, share):
    ctx = {"spans": _rows(inflight), "host": {}, "cell_seconds": float(len(inflight))}
    assert _read(ctx) == share


def test_nothing_to_read_is_none(monkeypatch):
    tick = ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None)
    assert _read({"spans": [tick], "host": {}, "cell_seconds": 1.0}) is None
    import rocket_tpu.obs.spans as span_module

    monkeypatch.delattr(span_module, "recorded")
    assert _read({"host": {}, "cell_seconds": 1.0}) is None


def test_the_engine_writes_the_id_the_reader_reads():
    """A tiny engine under an open span sink. Requests that decode for a
    while: every dispatch but the first queues behind one. A pool too small
    for the load: the ticks that drain before they evict read 0 and say so
    on ``serve/grow``."""
    import jax

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.obs import spans
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=1, num_heads=4, dropout=0.0))
    params = jax.jit(model.init)(jax.random.key(0))["params"]

    def serve(requests, **pool):
        engine = ServeEngine(model, params, ServeConfig(
            max_slots=2, block_len=4, prefill_chunk=4, max_model_len=32, **pool))
        recorder = spans.SpanRecorder()
        spans.install(recorder)
        try:
            for n, new in requests:
                engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=new)
            engine.drain()
        finally:
            spans.uninstall(recorder)
        rows = [tuple(e) for e in recorder.events()]
        behind = [r[4]["inflight"] for r in rows if r[0] == "serve/dispatch"]
        drained = sum(r[4]["drained"] for r in rows if r[0] == "serve/grow")
        return _read({"spans": rows, "host": {}, "cell_seconds": 1e9}), behind, drained

    got, behind, drained = serve([(3, 12), (6, 12)])
    assert behind[0] == 0 and set(behind[1:]) == {1} and drained == 0
    assert got == pytest.approx(100.0 * (len(behind) - 1) / len(behind))
    # 5 allocatable blocks of 4 rows for two requests of 18 rows each.
    got, behind, drained = serve([(6, 12), (6, 12)], num_blocks=6)
    assert drained >= 1 and behind.count(0) >= 1 + drained
    assert got == pytest.approx(100.0 * behind.count(1) / len(behind))
