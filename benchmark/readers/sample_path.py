"""The share of the decode waves whose sampling took the ``argmax`` branch
(no vocabulary sort, no softmax, no draw), in %, from the PROGRAM's
``serve/decode_pages`` counter (``rocket_tpu.obs.spans``: one record per
dispatched wave; its ``sample`` id names the branch that the sampling core
takes for the wave's running slots: ``argmax``, ``sample`` or ``filter``),
over the traced stretch.

A program whose records carry no ``sample`` id (before the sampling core
chose on the device) has nothing to read: ``None``.
"""

from benchmark.readers import program_spans


def read(ctx):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    waves = program_spans.select(ctx, spans, stretch, ["serve/decode_pages"], "stretch")
    taken = [s.ids["sample"] for s in waves if "sample" in s.ids]
    if not taken:
        return None
    return 100.0 * taken.count("argmax") / len(taken)
