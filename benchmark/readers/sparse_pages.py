"""The share of the pages they hold that the sparse layers' decode queries
read, in %, from the PROGRAM's ``serve/decode_pages`` counter
(``rocket_tpu.obs.spans``): per dispatched wave ``sparse_pages`` (per
running slot and K/V head the pages attended: the ``topk`` picked past
``dense_len``, every live page below it) and ``sparse_live`` (the pages the
slot holds, times the K/V heads), summed over the traced stretch. Dense
attention reads 100.

A program that records no such counter (one without sparse layers, or
before they were served) has nothing to read: ``None``."""

from benchmark.readers import program_spans


def read(ctx):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    waves = [s for s in program_spans.select(
        ctx, spans, stretch, ["serve/decode_pages"], "stretch")
        if "sparse_live" in s.ids]
    live = sum(int(s.ids["sparse_live"]) for s in waves)
    if not live:
        return None
    return 100.0 * sum(int(s.ids["sparse_pages"]) for s in waves) / live
