"""The ``sample_argmax_waves_share`` reader on hand-made spans, and the
``sample`` id of the program's ``serve/decode_pages`` record that it reads,
from a tiny engine."""

import numpy as np
import pytest

from benchmark.readers import sample_path
from benchmark.run import HERE, load_json


def _read(ctx):
    """Through the metric's own file, as ``run.read_per_layer`` does."""
    spec = load_json(HERE / "metrics" / "sample_argmax_waves_share.json")
    assert spec["reader"] == "sample_path"
    return sample_path.read(ctx, **spec["args"])


def _rows(samples):
    """One tick and one wave record a second; the record AFTER the last
    tick lies outside the stretch. ``None``: a record with no ``sample``."""
    rows = []
    for i, sample in enumerate(samples):
        ids = {"tick": i, "live": 3, "table": 64}
        if sample is not None:
            ids["sample"] = sample
        if i < len(samples) - 1:
            rows.append(("serve/tick", float(i), i + 1.0, None, {}, 1, 2 * i, None))
        rows.append(("serve/decode_pages", i + 0.5, i + 0.5, None, ids, 1, 2 * i + 1, None))
    return rows


@pytest.mark.parametrize("samples, share", [
    (["argmax", "argmax", "argmax", "filter"], 100.0),
    (["argmax", "filter", "sample", "argmax", "argmax"], 50.0),
    (["sample", "filter", "argmax"], 0.0),
    # The parent's records: the counter, but no ``sample`` id.
    ([None, None, None], None),
])
def test_argmax_share_on_recorded_spans(samples, share):
    ctx = {"spans": _rows(samples), "host": {}, "cell_seconds": float(len(samples))}
    assert _read(ctx) == share


def test_nothing_to_read_is_none(monkeypatch):
    tick = ("serve/tick", 0.0, 1.0, None, {}, 1, 0, None)
    assert _read({"spans": [tick], "host": {}, "cell_seconds": 1.0}) is None
    import rocket_tpu.obs.spans as span_module

    monkeypatch.delattr(span_module, "recorded")
    assert _read({"host": {}, "cell_seconds": 1.0}) is None


def test_the_engine_writes_the_id_the_reader_reads():
    """A tiny engine under an open span sink: greedy requests read 100; a
    request with ``top_p`` beside them takes its waves off the share."""
    import jax

    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.obs import spans
    from rocket_tpu.serve import ServeConfig, ServeEngine

    model = TransformerLM(TransformerConfig(
        vocab_size=64, max_seq_len=64, dim=32, num_layers=1, num_heads=4, dropout=0.0))
    params = jax.jit(model.init)(jax.random.key(0))["params"]
    engine = ServeEngine(model, params, ServeConfig(
        max_slots=2, block_len=4, prefill_chunk=4, max_model_len=32))

    def share(requests):
        recorder = spans.SpanRecorder()
        spans.install(recorder)
        try:
            for n, knobs in requests:
                engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4, **knobs)
            engine.drain()
        finally:
            spans.uninstall(recorder)
        rows = [tuple(e) for e in recorder.events()]
        taken = [r[4]["sample"] for r in rows if r[0] == "serve/decode_pages"]
        return _read({"spans": rows, "host": {}, "cell_seconds": 1e9}), taken

    greedy = {"temperature": 0.0}
    assert share([(3, greedy), (10, greedy)])[0] == 100.0
    # Three chunks of prefill: the greedy slot decodes alone meanwhile.
    got, taken = share([(3, greedy), (10, {"temperature": 0.8, "top_p": 0.9})])
    assert set(taken) == {"argmax", "filter"}
    assert got == pytest.approx(100.0 * taken.count("argmax") / len(taken))
