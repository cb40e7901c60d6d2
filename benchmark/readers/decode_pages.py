"""The share of the block table that the decode waves' running slots
held, in %, from the PROGRAM's ``serve/decode_pages`` counter
(``rocket_tpu.obs.spans``: one record per dispatched wave with ``live`` =
the pages of the slots that run, sum of ``lengths // block_len + 1``, and
``table`` = ``max_slots x max_blocks_per_seq``), over the traced stretch:
sum of ``live`` over sum of ``table``. It is the part of a grid over the
whole table that had work to do; a kernel that walks live pages only
pays for this share.

A program that records no such counter has nothing to read: ``None``.
"""

from benchmark.readers import program_spans


def read(ctx):
    spans, stretch = program_spans.load(ctx)
    if spans is None:
        return None
    waves = program_spans.select(ctx, spans, stretch, ["serve/decode_pages"], "stretch")
    table = sum(int(s.ids["table"]) for s in waves)
    if not table:
        return None
    return 100.0 * sum(int(s.ids["live"]) for s in waves) / table
