"""Each count function against a hand count for one shape."""

import pytest

from benchmark import counts
from benchmark.readers import mfu

MEDIUM = {"n_embd": 1024, "n_layer": 24, "n_head": 16, "vocab_size": 50257}
TINY = {"n_embd": 4, "n_layer": 2, "n_head": 2, "vocab_size": 10}


def test_matmul_params_and_train_flops_per_token():
    # By hand: 24 layers x (3 + 1 + 8) x 1024^2 + 50257 x 1024.
    assert counts.matmul_params(MEDIUM) == 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert counts.matmul_params(MEDIUM) == 353_453_056
    want = 6 * 353_453_056 + 12 * 24 * 1024 * 1024
    assert counts.train_flops_per_token(MEDIUM, 1024) == want
    # A step of 8 x 1024 tokens: 19.85 TFLOP.
    assert counts.train_flops_per_token(MEDIUM, 1024) * 8192 == pytest.approx(19.85e12, rel=2e-3)


def test_serve_flops_counts_every_attended_position():
    # N = 2 x 12 x 16 + 10 x 4 = 424. Tokens at positions 0, 1 and 5 attend
    # 1 + 2 + 6 = 9 keys: 2 x 424 x 3 + 4 x 2 x 4 x 9.
    assert counts.matmul_params(TINY) == 424
    assert counts.serve_flops(TINY, [0, 1, 5]) == 2 * 424 * 3 + 4 * 2 * 4 * 9
    assert counts.serve_flops(TINY, []) == 0


def test_kernel_counts():
    # Flash forward, B=2, T=8, d=4: QK^T and PV are each 2 x B x T x T x d =
    # 1024 operations, 2048 together, the causal half 1024; q, k, v, o in
    # bfloat16: 4 x (2 x 8 x 4) x 2 bytes. Backward: five matmuls for two.
    assert counts.flash_fwd(TINY, 2, 8) == {"flops": 1024.0, "bytes": 512.0}
    assert counts.flash_bwd(TINY, 2, 8) == {"flops": 2560.0, "bytes": 1024.0}
    # Paged decode, slots with 3 and 5 live positions, d=4: 8 K rows and 8 V
    # rows of 4: 4 x 8 x 4 operations; (2 x 8 x 4 + 2 x 2 x 4) x 2 bytes.
    assert counts.paged_decode(TINY, [3, 5]) == {"flops": 128.0, "bytes": 160.0}


def test_mfu_reader_divides_by_the_named_peak_and_refuses_an_unknown_device():
    ctx = {"host": {"traced_s": 2.0, "traced_flops": 197e12}, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12}, "device_kind": "TPU v5 lite"}
    assert mfu.read(ctx) == pytest.approx(50.0)
    assert mfu.read(dict(ctx, host={})) is None
    # Over the device's busy seconds (1 s of the 2): the share while it works.
    busy = {"/device:TPU:0": {"XLA Ops": [["a fusion", 0, 600_000_000], ["b fusion", 10**9, 400_000_000]]}}
    assert mfu.read(dict(ctx, trace=busy), over="busy") == pytest.approx(100.0)
    with pytest.raises(ValueError):
        mfu.read(ctx, over="wall")
    with pytest.raises(KeyError):
        mfu.read(dict(ctx, peaks=None, device_kind="TPU v9"))
