"""The main paths' pallas kernels, compiled for a v5e chip that is
described, not attached.

Interpret mode (every other kernel test) does not enforce Mosaic's
block-shape, tiling or VMEM rules; the TPU compiler installed beside JAX
does, and it compiles for a topology description without a device. Each
case lowers one kernel with ``interpret=False`` at the GPT-2 124M widths
``chip_smoke.py`` runs and asserts the Mosaic custom call is in the
compiled program. Nothing executes: these say "it lowers", never "it is
right" or "it is fast".

Everything that touches the TPU library happens inside the module-scoped
fixtures below — only one process may load libtpu, so under xdist only
the worker that is handed this file does; nothing here runs at import or
collection time, and the file must stay the ONLY one of its kind (a
second file could land on another worker, whose fixture would skip).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)`` -> a ShapeDtypeStruct placed on one described
    chip. (The persistent compilation cache is off for every test —
    conftest.py — which these compiles need: an entry compiled for a
    described chip cannot be read back without one.)"""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _fwd_bwd(op, nargs):
    """``op(*arrays) -> out`` as a loss whose gradient pulls the
    kernel's backward into the same program."""
    def loss(*arrays):
        return op(*arrays).astype(jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=tuple(range(nargs)))


def test_flash_fused_fwd_bwd_gpt2(sds):
    """The training kernel at the smoke's step shape: B=8, T=1024,
    fused QKV of 12 heads x 64."""
    from rocket_tpu.ops.flash_native import flash_fused

    def op(fused):
        return flash_fused(fused, 12, causal=True, interpret=False)

    _compile(_fwd_bwd(op, 1), sds((8, 1024, 2304), jnp.bfloat16))


@pytest.mark.parametrize("t", [1024, 4096])
def test_flash_bthd_gqa_fwd_bwd(sds, t):
    """The GQA / RoPE operand layout (12 query heads over 4 kv heads)."""
    from rocket_tpu.ops.flash_native import flash_bthd

    def op(q2, k2, v2):
        return flash_bthd(q2, k2, v2, 12, 4, causal=True, interpret=False)

    kv = sds((2, t, 4 * 64), jnp.bfloat16)
    _compile(_fwd_bwd(op, 3), sds((2, t, 12 * 64), jnp.bfloat16), kv, kv)


def test_decode_attention_gpt2(sds):
    """The dense-cache decode kernel ``generate()`` runs — the smoke's
    reference path — at B=8, 12 heads, T_max=1024."""
    from rocket_tpu.ops.decode_attention import decode_attention

    row = sds((8, 12, 64), jnp.bfloat16)
    cache = sds((8, 12, 1024, 64), jnp.bfloat16)
    _compile(
        functools.partial(decode_attention, interpret=False),
        row, row, row, cache, cache, sds((), jnp.int32),
    )


@pytest.mark.parametrize(
    "hq,hkv,d,block_len,dtype",
    [
        (12, 12, 64, 16, jnp.bfloat16),    # GPT-2 124M: g = 1
        (12, 4, 64, 16, jnp.bfloat16),     # llama-style 12/4 preset: g = 3
        (32, 8, 128, 128, jnp.bfloat16),   # lane-aligned heads, big pages
        (4, 4, 8, 8, jnp.float32),         # the support gate's low edge
    ],
    ids=["gpt2_g1", "gqa_g3", "d128_bl128", "gate_edge"],
)
def test_paged_decode_kernel(sds, hq, hkv, d, block_len, dtype):
    """The serve engine's fused paged-decode kernel at the smoke's pool
    shape (16 slots x 1024 context) — Hkv > 1 with g < 8 is exactly what
    the per-head blocking could not lower."""
    from rocket_tpu.ops.paged_attention import (
        _default_block_kv,
        _paged_decode_pallas,
        paged_decode_supported,
    )

    itemsize = jnp.dtype(dtype).itemsize
    assert paged_decode_supported(block_len, d, itemsize)
    slots, ctx = 16, 1024
    mb = ctx // block_len
    pool = sds((slots * mb, block_len, hkv, d), dtype)
    _compile(
        functools.partial(
            _paged_decode_pallas,
            block_kv=_default_block_kv(block_len, itemsize),
            interpret=False,
        ),
        sds((slots, hq, d), dtype), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
    )


# -- the names the device trace finds the kernels by -------------------------


def _kernel_instructions(text):
    """The names of the Mosaic custom-call instructions of a compiled
    program: what the profiler's ``XLA Ops`` line calls their events."""
    import re

    return [
        m.group(1) for m in re.finditer(
            r"^\s*%?(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text, re.M,
        )
    ]


def test_flash_kernels_carry_their_names_at_the_train_cell_width(sds):
    """GPT-2 medium's step shape (B=8, T=1024, 16 heads x 64): forward and
    backward kernels are named in the compiled program, so the benchmark's
    roofline metrics find them by name and not by ``jvp__.N``."""
    from rocket_tpu.ops.flash_native import flash_fused

    def op(fused):
        return flash_fused(fused, 16, causal=True, interpret=False)

    text = _compile(_fwd_bwd(op, 1), sds((8, 1024, 3 * 1024), jnp.bfloat16))
    names = _kernel_instructions(text)
    assert any("flash_fwd" in n for n in names), names
    assert any("flash_bwd_dkv" in n for n in names), names


def test_flash_dq_kernel_carries_its_name(sds):
    """The split-dq backward (long sequences) names its second kernel."""
    from rocket_tpu.ops.flash_native import flash_bthd

    def op(q2, k2, v2):
        return flash_bthd(q2, k2, v2, 12, 4, causal=True, interpret=False,
                          dq_split=True)

    kv = sds((2, 4096, 4 * 64), jnp.bfloat16)
    text = _compile(_fwd_bwd(op, 3), sds((2, 4096, 12 * 64), jnp.bfloat16),
                    kv, kv)
    names = _kernel_instructions(text)
    assert any("flash_bwd_dq" in n for n in names), names
    assert any("flash_bwd_dkv" in n for n in names), names


def test_paged_decode_kernel_carries_its_name_at_the_chat_cell_width(sds):
    """GPT-2 large's pool (20 heads x 64, 32 slots x 1024 of block 16)."""
    from rocket_tpu.ops.paged_attention import (
        _default_block_kv,
        _paged_decode_pallas,
    )

    slots, ctx, bl, h, d = 32, 1024, 16, 20, 64
    mb = ctx // bl
    pool = sds((1 + slots * mb, bl, h, d), jnp.bfloat16)
    text = _compile(
        functools.partial(
            _paged_decode_pallas, block_kv=_default_block_kv(bl, 2),
            interpret=False,
        ),
        sds((slots, h, d), jnp.bfloat16), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
    )
    names = _kernel_instructions(text)
    assert any("paged_decode" in n for n in names), names
