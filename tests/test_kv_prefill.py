"""``ops/kv_prefill.py``: the K/V pool's long-table chunk attention as ONE
Pallas kernel, run interpreted on the CPU against the two XLA forms it
stands beside (``paged_attention._attend_chunk_live``, the walk over the
live context it replaces on a TPU, and ``_attend_xla``, the one-shot
form), and the dispatch of ``paged_attention`` that chooses it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.ops import paged_attention as pa
from rocket_tpu.ops.kv_prefill import kv_prefill, kv_prefill_supported

#: (Hq, Hkv, D): a group of 6 query heads a K/V head at 128 lanes (the
#: codeagent cell's full layers, cut to 2 K/V heads), and of 8 at 256 lanes
#: (the longchat cell's attention: two blocks of 4 query heads a K/V head).
GEOMETRIES = {"g6_d128": (12, 2, 128), "g8_d256": (16, 2, 256)}
#: Chunk rows, page rows, pages a slot's table holds, key tile rows: four
#: tiles of four pages, so the walk has plain, masked, half and dead tiles.
C, BL, MB, BK = 32, 16, 16, 64
#: ``(positions, valid)`` of the slots of a call.
CASES = {
    "start_at_0": ([0], [32]),                 # one tile, its first half
    "mid_page": ([40], [32]),                  # the chunk crosses a tile
    "past_tiles_padded": ([150], [20]),        # two plain tiles, 12 padded rows
    "ends_in_a_first_half": ([100], [30]),     # the last tile holds 2 live rows
    "two_slots": ([7, 131], [32, 9]),          # lengths of their own
}


def _operands(hq, hkv, d, positions, valid, dtype=jnp.float32, seed=0):
    s = len(positions)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (s, C, hq, d), jnp.float32).astype(dtype)
    pool = (2, 1 + s * MB, BL, hkv * d)
    k_pages = jax.random.normal(ks[1], pool, jnp.float32).astype(dtype)
    v_pages = jax.random.normal(ks[2], pool, jnp.float32).astype(dtype)
    table = np.random.default_rng(seed).permutation(np.arange(1, 1 + s * MB))
    return (q, k_pages, v_pages, jnp.asarray(table.reshape(s, MB), jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32))


def _rows_read(position, valid):
    """Context rows the kernel copies for a slot: whole live tiles, but only
    the first half of the last one where the context ends in it."""
    n_ctx = position + max(valid, 1)
    tiles = -(-n_ctx // BK)
    return tiles * BK - (BK // 2 if n_ctx - (tiles - 1) * BK <= BK // 2 else 0)


def _real(valid):
    return np.arange(C)[None, :] < np.asarray(valid)[:, None]


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kv_prefill_matches_the_live_walk_and_the_one_shot_form(geometry, case):
    """The kernel, interpreted, against both XLA forms on the real rows, to
    float32 tolerance; every page it never copies (dead tiles, the second
    half of a last tile whose context ends in its first half) is poisoned
    with NaN and the output does not move; padded rows are finite."""
    hq, hkv, d = GEOMETRIES[geometry]
    positions, valid = CASES[case]
    q, k_pages, v_pages, table, pos, val = _operands(hq, hkv, d, positions, valid)
    layer = 1
    walk = pa._attend_chunk_live(q, k_pages, v_pages, table, pos, val, layer)
    one_shot = pa._attend_xla(q, k_pages, v_pages, table, pos, layer)
    real = _real(valid)
    np.testing.assert_allclose(np.asarray(walk)[real], np.asarray(one_shot)[real],
                               atol=2e-5, rtol=2e-5)

    k_bad, v_bad = np.array(k_pages), np.array(v_pages)
    for n, (p, v) in enumerate(zip(positions, valid)):
        unread = np.asarray(table[n, _rows_read(p, v) // BL:])
        k_bad[layer, unread] = np.nan
        v_bad[layer, unread] = np.nan
    got = kv_prefill(q, jnp.asarray(k_bad), jnp.asarray(v_bad), table, pos, val, layer,
                     block_kv=BK, interpret=True)
    assert got.shape == (len(positions), C, hq * d) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(walk)[real],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(one_shot)[real],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_kv_prefill_in_bfloat16_matches_the_walk():
    """The configurations' own precision: bfloat16 q and pages, float32
    statistics in both, so the two agree to bfloat16's rounding."""
    hq, hkv, d = GEOMETRIES["g6_d128"]
    ops = _operands(hq, hkv, d, [7, 131], [32, 9], dtype=jnp.bfloat16, seed=3)
    want = np.asarray(pa._attend_chunk_live(*ops, 0), np.float32)
    got = np.asarray(kv_prefill(*ops, 0, block_kv=BK, interpret=True), np.float32)
    real = _real([32, 9])
    np.testing.assert_allclose(got[real], want[real], atol=2e-2, rtol=2e-2)
    assert np.abs(got[real] - want[real]).mean() < 2e-3


def test_kv_prefill_default_tiling_and_a_traced_layer():
    """Nobody pins a tiling (the table's 256 rows make one tile of 16 pages,
    6 query heads a step) and the layer is a traced scalar, as in a scanned
    model: the same numbers."""
    hq, hkv, d = GEOMETRIES["g6_d128"]
    ops = _operands(hq, hkv, d, [200], [32], seed=4)
    want = pa._attend_chunk_live(*ops, 1)
    got = jax.jit(lambda layer, *a: kv_prefill(*a, layer, interpret=True))(jnp.int32(1), *ops)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def _chunk_call(hq, hkv, d, positions, valid, seed=5):
    q, k_pages, v_pages, table, pos, val = _operands(hq, hkv, d, positions, valid, seed=seed)
    k_new = jax.random.normal(jax.random.key(seed + 1), (len(positions), C, hkv, d))
    v_new = k_new * 0.5
    return q, k_new, v_new, k_pages, v_pages, table, pos, val


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_attention_takes_the_kernel_for_a_long_table(monkeypatch, geometry):
    """A chunk whose one-shot scores pass ``_CHUNK_SCORES_MAX``: with
    ``interpret=True`` (a TPU's choice) ``paged_attention`` runs the kernel
    after the chunk's rows are written, with the same output as the XLA
    walk that an unpinned CPU call takes, and the pool is bitwise the walk's
    one: the chunk's real rows written at their pages, every other row as
    it was."""
    hq, hkv, d = GEOMETRIES[geometry]
    ops = _chunk_call(hq, hkv, d, [7, 131], [32, 9])
    calls, walk = [], pa._attend_chunk_live
    monkeypatch.setattr(pa, "_CHUNK_SCORES_MAX", 1)
    monkeypatch.setattr(pa, "_attend_chunk_live",
                        lambda *a: calls.append("walk") or walk(*a))
    out_walk, k_walk, v_walk = pa.paged_attention(*ops, layer=1)
    assert calls == ["walk"]
    out, k_pool, v_pool = pa.paged_attention(*ops, layer=1, interpret=True)
    assert calls == ["walk"]                      # the kernel, not the walk
    real = _real([32, 9])
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(out_walk)[real],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_pool), np.asarray(k_walk))
    np.testing.assert_array_equal(np.asarray(v_pool), np.asarray(v_walk))
    q, k_new, v_new, k_pages, v_pages, table, pos, val = ops
    want_k = np.array(k_pages)
    for n, (p, v) in enumerate(zip([7, 131], [32, 9])):
        for i in range(v):
            want_k[1, int(table[n, (p + i) // BL]), (p + i) % BL] = \
                np.asarray(k_new[n, i]).reshape(-1)
    got_k = np.asarray(k_pool)
    np.testing.assert_array_equal(got_k[:, 1:], want_k[:, 1:])   # block 0: trash


def test_a_pinned_kernel_that_cannot_run_still_raises():
    """A chunk against a SHORT table is the one-shot form's: a pinned
    ``pallas`` raises there (as for a decode row the kernel cannot take),
    and an unpinned call is bitwise the pinned ``xla`` one."""
    hq, hkv, d = GEOMETRIES["g6_d128"]
    ops = _chunk_call(hq, hkv, d, [0], [32])
    assert not pa._long_chunk(1, C, hq, MB, BL)
    with pytest.raises(ValueError, match="cannot run here"):
        pa.paged_attention(*ops, impl="pallas", interpret=True)
    a, _, _ = pa.paged_attention(*ops, interpret=True)
    b, _, _ = pa.paged_attention(*ops, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kv_prefill_supported_gate():
    # The two cells that run it: codeagent's full layers, longchat's.
    assert kv_prefill_supported(1024, 48, 8, 128, 64, 16384, 2)
    assert kv_prefill_supported(1024, 16, 2, 256, 64, 16384, 2)
    assert kv_prefill_supported(C, 12, 2, 128, BL, MB * BL, 4)
    assert not kv_prefill_supported(1024, 20, 20, 64, 16, 1024, 2)   # D of 64 lanes
    assert not kv_prefill_supported(1024, 20, 1, 96, 64, 4096, 2)    # D no lane tile
    assert not kv_prefill_supported(1000, 48, 8, 128, 64, 16384, 2)  # chunk % sublane
    assert not kv_prefill_supported(2048, 48, 8, 128, 64, 16384, 2)  # chunk > a query tile
    assert not kv_prefill_supported(1, 48, 8, 128, 64, 16384, 2)     # a decode row
    assert not kv_prefill_supported(1024, 48, 8, 128, 8, 16384, 2)   # bf16 page of 8 rows
    assert not kv_prefill_supported(1024, 48, 7, 128, 64, 16384, 2)  # Hq % Hkv
    assert not kv_prefill_supported(1024, 48, 8, 128, 48, 16384, 2)  # table % page
    assert not kv_prefill_supported(1024, 10, 1, 1152, 64, 16384, 2)  # one head past a step


@pytest.mark.parametrize("event", ["kv_prefill custom-call", "kv_prefill.2 custom-call"])
def test_the_trace_reads_the_kernel_by_its_own_name(event):
    """``kv_chunk_share`` reads the kernel's events in the prefill program;
    no decode roofline or other chunk share takes them (a prefill kernel
    counted there would read its roofline too high)."""
    import json
    import pathlib
    import re

    metrics = pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "metrics"
    patterns = {p.stem: json.loads(p.read_text())["args"].get("pattern")
                for p in metrics.glob("*.json")}
    taken = {name for name, pattern in patterns.items()
             if pattern and re.search(pattern, event)}
    assert taken == {"kv_chunk_share"}
    share = json.loads((metrics / "kv_chunk_share.json").read_text())
    assert (share["reader"], share["moves"]) == ("op_share", "ttft_p90_ms")
    assert re.search(share["args"]["module"], "jit_prefill_chunk_fn(123)")
    assert not re.search(share["args"]["pattern"], "mla_prefill.1 custom-call")
