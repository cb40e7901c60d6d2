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
        (16, 16, 8, 8, jnp.float32),       # the support gate's low edge
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
    assert paged_decode_supported(block_len, d, itemsize, lanes=hkv * d)
    slots, ctx = 16, 1024
    mb = ctx // block_len
    pool = sds((1, slots * mb, block_len, hkv * d), dtype)
    _compile(
        functools.partial(
            _paged_decode_pallas,
            block_kv=_default_block_kv(block_len, itemsize, 2 * hkv * d),
            interpret=False,
        ),
        sds((slots, hq, d), dtype), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32),
    )


# -- the names the device trace finds the kernels by -------------------------


def _kernel_instructions(text):
    """The names of the Mosaic custom-call instructions of a compiled
    program: what the profiler's ``XLA Ops`` line calls their events."""
    import re

    return [
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
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
    pool = sds((1, 1 + slots * mb, bl, h * d), jnp.bfloat16)
    text = _compile(
        functools.partial(
            _paged_decode_pallas,
            block_kv=_default_block_kv(bl, 2, 2 * h * d),
            interpret=False,
        ),
        sds((slots, h, d), jnp.bfloat16), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32),
    )
    names = _kernel_instructions(text)
    assert any("paged_decode" in n for n in names), names


def test_latent_decode_kernel_carries_its_name_at_the_docqa_cell_width(sds):
    """The latent pool of the docqa cell (128 query heads over ONE 640-lane
    row a token, values its first 512 lanes, 32 slots x 8192 of block 64):
    the same body with no V array, at the tile nobody pinned."""
    from rocket_tpu.ops.paged_attention import (
        _default_block_kv,
        _paged_decode_pallas,
        paged_decode_supported,
    )

    slots, ctx, bl, h, lanes = 32, 8192, 64, 128, 640
    mb = ctx // bl
    assert paged_decode_supported(bl, lanes, 2, lanes=lanes)
    assert _default_block_kv(bl, 2, lanes) == 512
    text = _compile(
        functools.partial(
            _paged_decode_pallas, block_kv=_default_block_kv(bl, 2, lanes),
            interpret=False, scale=0.1, d_v=512, name="mla_decode",
        ),
        sds((slots, h, lanes), jnp.bfloat16),
        sds((1, 1 + slots * mb, bl, lanes), jnp.bfloat16), None,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32),
    )
    names = _kernel_instructions(text)
    assert any("mla_decode" in n for n in names), names
    assert not any("paged_decode" in n for n in names), names


# -- the KV pool is read and written where it lies ---------------------------


def _materialised(text):
    """``(opcode, name, elements, line)`` of every instruction of a compiled
    program that owns a result buffer: those of the entry computation and
    of loop bodies, not those inside a fusion (they never reach memory).
    ``elements`` is the largest array in the result's type."""
    import math
    import re

    fused = set(re.findall(r" fusion\([^\n]*calls=%?([\w.\-]+)", text))
    out, inside = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (.+?) ([\w\-]+)\(", line)
        if not m or inside in fused:
            continue
        sizes = [
            math.prod(map(int, dims.split(",")))
            for dims in re.findall(r"\w+\[([0-9,]+)\]", m.group(2))
        ]
        out.append((m.group(3), m.group(1), max(sizes, default=0), line))
    return out


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_serve_programs_update_the_pool_in_place(
    sds, monkeypatch, scan_layers
):
    """The engine's own two programs at the chat cell's widths (GPT-2
    large: 20 heads x 64, 32 slots x 1024 positions in blocks of 16; two
    layers and a small vocabulary, so that it compiles in seconds and the
    pool is the only large array): in the optimized HLO nothing but the
    donated pool's own row scatter produces a result of one layer slice's
    size (NB*BL*Hkv*D elements) or more, the temporaries stay under one
    layer slice, and both pool buffers are aliased input to output.

    The op is told the backend is a TPU, so the decode program holds the
    REAL Mosaic kernel and not its interpreted stand-in: what is asserted
    is the whole program, the kernel's operands included. (A count of
    instructions in a compile for a described chip; not a device number.)"""
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve import ServeConfig

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    model = TransformerLM(TransformerConfig(
        vocab_size=1024, max_seq_len=1024, dim=1280, num_layers=2,
        num_heads=20, dropout=0.0, activation_dtype="bfloat16",
        scan_layers=scan_layers,
    ))
    sc = ServeConfig(max_slots=32, block_len=16, prefill_chunk=128)
    spec = sc.resolve(model.config)[0]
    assert spec.pages_shapes == ((2, 2049, 16, 1280),) * 2
    _assert_pool_in_place(sds, model, sc, "paged_decode")


def _docqa_attention_widths():
    """``TransformerConfig`` fields of the docqa cell's attention (128 heads
    of 128 + 64 / 128 over a latent of 512 + 64, YaRN, a table of 8192
    positions) around a small model width, FFN and vocabulary."""
    from rocket_tpu.nn.attention import LatentAttentionConfig, YarnScaling

    return dict(
        vocab_size=1024, max_seq_len=8192, dim=1024, num_heads=128,
        dropout=0.0, activation_dtype="bfloat16", pos_embedding="rope",
        norm="rmsnorm", norm_eps=1e-6, mlp="swiglu", mlp_hidden=1024,
        mlp_bias=False, tied_embeddings=False,
        latent_attention=LatentAttentionConfig(
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128,
            yarn=YarnScaling(factor=40, original_max_position_embeddings=4096,
                             mscale=1.0, mscale_all_dim=1.0),
        ),
    )


def test_latent_serve_programs_update_the_pool_in_place(sds, monkeypatch):
    """The same two programs for a latent-attention, routed-expert model at
    the docqa cell's attention widths (128 heads, latent 512 + 64, 32
    slots x 8192 positions in blocks of 64; one dense and one routed layer,
    FFN widths and vocabulary small so that it compiles in seconds and the
    pool is the only large array): ONE pool array of 576 lanes in 640, read by
    the ``mla_decode`` kernel and by the prefill's page gathers where it
    lies and written only by its own row scatter."""
    import rocket_tpu.nn.moe as moe
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.nn.moe import RoutedExpertsConfig
    from rocket_tpu.serve import ServeConfig

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    model = TransformerLM(TransformerConfig(
        num_layers=2,
        routed_experts=RoutedExpertsConfig(
            num_experts=256, top_k=8, hidden=256, n_group=8, topk_group=4,
            routed_scaling_factor=2.5, shared_hidden=256, experts_held=(0, 16),
        ),
        first_dense_layers=1,
        **_docqa_attention_widths(),
    ))
    sc = ServeConfig(max_slots=32, block_len=64, prefill_chunk=512)
    spec = sc.resolve(model.config)[0]
    assert spec.pages_shapes == ((2, 4097, 64, 640),)
    kernels = _assert_pool_in_place(sds, model, sc, "mla_decode")
    # (The prefill chunk discards its logits, so its LAST layer's experts
    # are dead code: with one routed layer it holds no grouped matmul.)
    for name in ("moe_gmm_gate_up", "moe_gmm_down"):
        assert any(name in k for k in kernels["decode"]), kernels


def test_latent_prefill_program_attends_in_the_mla_prefill_kernel(sds, monkeypatch):
    """The prefill program of a docqa-shaped dense model (a chunk of 512
    rows against a table of 8192) for the chip: every layer's chunk
    attention is an ``mla_prefill`` custom-call, the layers share ONE
    lowering of it, nothing is left of the XLA loop (no ``while``, no
    (S, H, C, tk) float32 scores), the latent pool is still only scattered
    into in place, and the decode program keeps ``mla_decode``."""
    import re

    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.ops.latent_prefill import mla_prefill_supported
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import abstract_wave_inputs, build_prefill_step

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    layers = 3
    model = TransformerLM(TransformerConfig(
        num_layers=layers, **_docqa_attention_widths()))
    sc = ServeConfig(max_slots=32, block_len=64, prefill_chunk=512)
    assert mla_prefill_supported(512, 128, 512, 128, 64, 128, 8192, 2)
    kernels = _assert_pool_in_place(sds, model, sc, "mla_decode")
    # (The chunk discards its logits and this model routes nothing, so the
    # LAST layer's attention output is dead code: its kernel call is gone.)
    assert sum("mla_prefill" in k for k in kernels["prefill"]) == layers - 1, kernels
    assert not any("mla_prefill" in k for k in kernels["decode"]), kernels

    spec, mb, _, _ = sc.resolve(model.config)
    _, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(model, spec, max_slots=sc.max_slots,
                             max_blocks_per_seq=mb, prefill_chunk=sc.prefill_chunk),
    )
    stablehlo = jax.jit(build_prefill_step(model)).lower(*prefill_args).as_text()
    # ONE body for the layers: one function holds the kernel, they call it.
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", stablehlo)) == 1
    assert len(re.findall(r"call @mla_prefill", stablehlo)) == layers - 1
    assert "stablehlo.while" not in stablehlo
    assert "x128x512x512xf32>" not in stablehlo


def _assert_carry_stays_on_device(text, slots):
    """The decode program's carry — ``(lengths, last_tok, run)``, what lets
    the scheduler enqueue a dispatch before it has fetched the one before —
    compiled for the described chip: three ``(slots,)`` vectors among the
    entry computation's parameters AND among its results (the next
    dispatch's inputs, which the engine never fetches), and no instruction
    that moves anything to the host from inside the program."""
    import re

    entry = re.search(r"^ENTRY [^\n]*?\((.*)\) -> \((.*)\) \{$", text, re.M)
    assert entry, text[:300]
    params, results = entry.groups()
    for side in (params, results):
        assert len(re.findall(rf"\bs32\[{slots}\]", side)) >= 2, side
        assert len(re.findall(rf"\bpred\[{slots}\]", side)) >= 1, side
    moved = re.findall(r" (outfeed|send|send-done|recv|recv-done)\(", text)
    assert not moved and "MoveToHost" not in text, moved


def _assert_pool_in_place(sds, model, sc, decode_kernel):
    """Both programs of ``model`` under ``sc``, compiled for the described
    chip: see the tests above. Returns the kernels of each program."""
    import re

    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    spec, mb, _, waves = sc.resolve(model.config)
    pool_types = [
        "bf16[%s]" % ",".join(map(str, shape)) for shape in spec.pages_shapes
    ]
    layer_slice = spec.num_blocks * spec.block_len * max(spec.lanes)  # elements
    decode_args, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    programs = {
        "decode": (build_decode_wave(model, waves=waves), decode_args,
                   DECODE_DONATE),
        "prefill": (build_prefill_step(model), prefill_args, PREFILL_DONATE),
    }
    found = {}
    for name, (fn, args, donate) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        text = compiled.as_text()
        kernels = _kernel_instructions(text)
        found[name] = kernels
        if name == "decode":
            assert any(decode_kernel in k for k in kernels), (name, kernels)
            _assert_carry_stays_on_device(text, sc.max_slots)
        large = [
            (op, inst) for op, inst, elements, line in _materialised(text)
            if elements >= layer_slice
            and op not in ("parameter", "tuple", "get-tuple-element",
                           "bitcast", "while")
            # the one thing a program may do to the pool: scatter the new
            # rows into the buffer it was given
            and not (op == "fusion" and "/scatter\"" in line
                     and any(t in line for t in pool_types))
        ]
        assert not large, (name, large)
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < layer_slice * 2, (   # bf16 bytes
            name, memory.temp_size_in_bytes)
        assert memory.alias_size_in_bytes >= spec.pool_bytes, (
            name, memory.alias_size_in_bytes)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert aliases and aliases.group(1).count("alias") == len(pool_types), (
            name, text[:300])
    return found


# -- the decode wave sorts the vocabulary only where a row filters -----------


def test_decode_program_sorts_only_inside_a_conditional(sds, monkeypatch):
    """The decode program at the chat cell's widths (GPT-2 large's 20 heads
    x 64 and its 50,257 tokens, 32 slots; two layers), compiled for the
    described chip: the sampling core's ONE vocabulary sort lies in a
    computation that a ``conditional`` calls, and the entry computation
    holds none. A wave whose running slots are greedy (both serve cells)
    takes the branch without it. (XLA may flatten a conditional into both
    branches and a select; this asserts it did not.)"""
    import re

    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
    )

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    model = TransformerLM(TransformerConfig(
        vocab_size=50257, max_seq_len=1024, dim=1280, num_layers=2,
        num_heads=20, dropout=0.0, activation_dtype="bfloat16",
    ))
    sc = ServeConfig(max_slots=32, block_len=16, prefill_chunk=128)
    spec, mb, _, waves = sc.resolve(model.config)
    decode_args, _ = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    text = jax.jit(
        build_decode_wave(model, waves=waves), donate_argnums=DECODE_DONATE
    ).lower(*decode_args).compile().as_text()

    sorts, branches, entry, inside = [], set(), None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(2)
            entry = inside if head.group(1) else entry
        elif re.search(r" sort\(", line):
            sorts.append(inside)
        elif " conditional(" in line:
            called = re.search(r"branch_computations=\{([^}]*)\}", line)
            branches.update(
                name.strip(" %") for name in called.group(1).split(","))
    assert entry and len(sorts) == 1, sorts
    assert sorts[0] != entry and sorts[0] in branches, (sorts, branches)


# -- per-slot state beside the pages: both are updated where they lie --------


def test_hybrid_serve_programs_update_pages_and_state_in_place(sds, monkeypatch):
    """The engine's two programs for a hybrid of state-space and attention
    layers at the reason cell's widths (d 2560, mixer 5120 x 16 with dt
    rank 160, MLP 8192, 20 query heads over 1 K/V head of 128; 64 slots x
    4096 positions in blocks of 64, chunks of 512; one Mamba and one
    attention layer and a small vocabulary, so that it compiles in
    seconds): the decode program holds ``paged_decode`` at 20 heads over 1
    and ``ssm_step``, the prefill program ``ssm_scan``; the four donated
    arrays — K and V pages by block, ``h`` and the convolution's tail by
    slot — are aliased input to output, and nothing but a program's own
    update of them (the kernel that owns ``h``, a row scatter, a slot's
    dynamic-update-slice) produces an array of their types: no copy. (One
    kind of move is the compiler's own and is let through: an asynchronous
    ``copy-start``/``copy-done`` of a small array into the chip's fast
    memory, ``S(1)`` in the layout, and back. At this test's one state layer
    ``h`` is small enough for it; at the cell's 26 only the convolution's
    51 MB tail is, in the decode program: PERF.md.)"""
    import re

    import rocket_tpu.nn.ssm as ssm
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.nn.ssm import SSMConfig
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    monkeypatch.setattr(ssm, "_on_cpu", lambda: False)
    model = TransformerLM(TransformerConfig(
        vocab_size=1024, max_seq_len=262144, dim=2560, num_layers=2,
        num_heads=20, num_kv_heads=1, dropout=0.0, activation_dtype="bfloat16",
        pos_embedding="none", norm="rmsnorm", norm_eps=1e-6, mlp="swiglu",
        mlp_hidden=8192, mlp_bias=False, attn_bias=False,
        ssm=SSMConfig(d_inner=5120, dt_rank=160, d_state=16, d_conv=4),
        attn_layer_period=2, attn_layer_offset=1,
    ))
    sc = ServeConfig(max_slots=64, block_len=64, prefill_chunk=512,
                     max_model_len=4096)
    spec, mb, _, waves = sc.resolve(model.config)
    assert spec.pages_shapes == ((1, 4097, 64, 128),) * 2
    assert spec.state_shapes == (((1, 64, 16, 5120), "float32"),
                                 ((1, 64, 15360), "bfloat16"))
    donated = ["bf16[1,4097,64,128]", "f32[1,64,16,5120]", "bf16[1,64,15360]"]
    decode_args, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    programs = {
        "decode": (build_decode_wave(model, waves=waves), decode_args,
                   DECODE_DONATE, ("paged_decode", "ssm_step")),
        "prefill": (build_prefill_step(model), prefill_args, PREFILL_DONATE,
                    ("ssm_scan",)),
    }
    for name, (fn, args, donate, wanted) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        text = compiled.as_text()
        kernels = _kernel_instructions(text)
        for kernel in wanted:
            assert any(kernel in k for k in kernels), (name, kernel, kernels)
        if name == "decode":
            _assert_carry_stays_on_device(text, sc.max_slots)
        materialised = _materialised(text)
        # Asynchronous moves into or out of the chip's fast memory.
        moves = {inst for op, inst, _, line in materialised
                 if op == "copy-start" and "S(1)" in line}
        made = [
            (op, inst) for op, inst, _, line in materialised
            if any(t in line.split(" = ")[1].split("(")[0] for t in donated)
            and op not in ("parameter", "tuple", "get-tuple-element", "bitcast",
                           "while", "custom-call", "dynamic-update-slice",
                           "scatter")
            and not (op == "copy-start" and inst in moves)
            and not (op == "copy-done" and any(
                f"copy-done(%{m})" in line or f"copy-done({m})" in line
                for m in moves))
            and not (op == "fusion" and re.search(
                r"/scatter\"|dynamic_update_slice|dynamic-update-slice", line))
        ]
        assert not made, (name, made)
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= spec.pool_bytes, (
            name, memory.alias_size_in_bytes, spec.pool_bytes)
        # Temporaries: activations of a chunk, never a copy of the state.
        assert memory.temp_size_in_bytes < spec.state_bytes // 2, (
            name, memory.temp_size_in_bytes)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert aliases and aliases.group(1).count("alias") == 4, (name, text[:300])


def test_linear_moe_serve_programs_update_pages_and_state_in_place(sds, monkeypatch):
    """The engine's two programs for Gated DeltaNet layers beside gated
    attention with a routed feed-forward at the longchat cell's widths (d
    2048; 16 key and 32 value heads of 128; 16 query heads over 2 K/V heads
    of 256, rotary over 64 lanes; 512 experts scored, 128 of width 512 held,
    10 chosen; 64 slots x 16384 positions in blocks of 64, chunks of 1024;
    one DeltaNet and one attention layer and a small vocabulary, so that it
    compiles in seconds): the decode program holds ``paged_decode`` at 16
    heads over 2 of 256, ``gdn_step`` and the grouped matmuls, the prefill
    program ``gdn_chunk``; the four donated arrays — K and V pages by
    block, the rank-5 matrix state ``S`` and the convolution's tail by
    slot — are aliased input to output, and nothing but a program's own
    update of them (the kernel that owns ``S``, a row scatter, a slot's
    dynamic-update-slice) produces an array of their types: no copy."""
    import re

    import rocket_tpu.nn.gdn as gdn
    import rocket_tpu.nn.moe as moe
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.nn.gdn import GatedDeltaNetConfig
    from rocket_tpu.nn.moe import RoutedExpertsConfig
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    monkeypatch.setattr(gdn, "_on_cpu", lambda: False)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    model = TransformerLM(TransformerConfig(
        vocab_size=1024, max_seq_len=16384, dim=2048, num_layers=2,
        num_heads=16, num_kv_heads=2, head_dim=256, dropout=0.0,
        tied_embeddings=False, activation_dtype="bfloat16",
        pos_embedding="rope", rope_base=1e7, rope_fraction=0.25,
        norm="rmsnorm", norm_eps=1e-6, norm_zero_centered=True,
        attn_bias=False, attn_gate=True, qk_norm=True,
        gdn=GatedDeltaNetConfig(16, 32, 128, 128),
        attn_layer_period=2, attn_layer_offset=1,
        routed_experts=RoutedExpertsConfig(
            num_experts=512, top_k=10, hidden=512, shared_hidden=512,
            shared_gate=True, scoring="softmax", experts_held=(0, 128)),
    ))
    sc = ServeConfig(max_slots=64, block_len=64, prefill_chunk=1024,
                     max_model_len=16384)
    spec, mb, _, waves = sc.resolve(model.config)
    assert spec.pages_shapes == ((1, 16385, 64, 512),) * 2
    assert spec.state_shapes == (((1, 64, 32, 128, 128), "float32"),
                                 ((1, 64, 24576), "bfloat16"))
    donated = ["bf16[1,16385,64,512]", "f32[1,64,32,128,128]", "bf16[1,64,24576]"]
    decode_args, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    programs = {
        "decode": (build_decode_wave(model, waves=waves), decode_args,
                   DECODE_DONATE, ("paged_decode", "gdn_step", "moe_gmm_gate_up",
                                   "moe_gmm_down")),
        "prefill": (build_prefill_step(model), prefill_args, PREFILL_DONATE,
                    ("gdn_chunk", "moe_gmm_gate_up")),
    }
    for name, (fn, args, donate, wanted) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        text = compiled.as_text()
        kernels = _kernel_instructions(text)
        for kernel in wanted:
            assert any(kernel in k for k in kernels), (name, kernel, kernels)
        if name == "decode":
            _assert_carry_stays_on_device(text, sc.max_slots)
        materialised = _materialised(text)
        moves = {inst for op, inst, _, line in materialised
                 if op == "copy-start" and "S(1)" in line}
        made = [
            (op, inst) for op, inst, _, line in materialised
            if any(t in line.split(" = ")[1].split("(")[0] for t in donated)
            and op not in ("parameter", "tuple", "get-tuple-element", "bitcast",
                           "while", "custom-call", "dynamic-update-slice",
                           "scatter")
            and not (op == "copy-start" and inst in moves)
            and not (op == "copy-done" and any(
                f"copy-done(%{m})" in line or f"copy-done({m})" in line
                for m in moves))
            and not (op == "fusion" and re.search(
                r"/scatter\"|dynamic_update_slice|dynamic-update-slice", line))
        ]
        assert not made, (name, made)
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= spec.pool_bytes, (
            name, memory.alias_size_in_bytes, spec.pool_bytes)
        # Temporaries: activations of a chunk (a tile of its attention
        # scores alone is 134 MB, as much as this test's one layer of
        # state: ``made`` above is what says no state array is copied),
        # never a copy of a page array (1 GB).
        assert memory.temp_size_in_bytes < 2 * spec.state_bytes, (
            name, memory.temp_size_in_bytes)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert aliases and aliases.group(1).count("alias") == 4, (name, text[:300])


def test_window_moe_serve_programs_update_pages_and_rings_in_place(sds, monkeypatch):
    """The engine's two programs for full and sliding-window attention layers
    with a routed feed-forward at the codeagent cell's widths (d 2048; 48
    query heads in a full layer, 64 in a sliding one, over 8 K/V heads of
    128; a window of 512; YaRN over 64 lanes and a gate a head; 256 experts
    scored, 64 of width 512 held, 8 chosen; 64 slots x 16384 positions in
    blocks of 64, chunks of 1024; one full and one sliding layer and a small
    vocabulary, so that it compiles in seconds): the decode program holds
    ``paged_decode`` for the full layer, ``window_decode`` for the sliding
    one and the grouped matmuls; the four donated arrays — K and V pages by
    block, K and V rings by slot — are aliased input to output, and nothing
    but a program's own update of them (a row scatter) produces an array of
    their types: no copy."""
    import re

    import rocket_tpu.nn.moe as moe
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import AttentionKind, TransformerConfig, TransformerLM
    from rocket_tpu.nn.attention import YarnScaling
    from rocket_tpu.nn.moe import RoutedExpertsConfig
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    yarn = YarnScaling(factor=64, original_max_position_embeddings=4096, beta_fast=64,
                       beta_slow=1)
    model = TransformerLM(TransformerConfig(
        vocab_size=1024, max_seq_len=16384, dim=2048, num_layers=2,
        num_heads=48, num_kv_heads=8, head_dim=128, dropout=0.0,
        tied_embeddings=False, activation_dtype="bfloat16", pos_embedding="rope",
        norm="rmsnorm", norm_eps=1e-6, attn_bias=False, attn_head_gate=True,
        layer_types=("full", "sliding"), attention_kinds={
            "full": AttentionKind(num_heads=48, rope_base=5e5, rope_fraction=0.5,
                                  rope_yarn=yarn),
            "sliding": AttentionKind(num_heads=64, rope_base=1e4, window=512)},
        routed_experts=RoutedExpertsConfig(
            num_experts=256, top_k=8, hidden=512, routed_scaling_factor=2.5,
            shared_hidden=512, scoring="softmax", experts_held=(0, 64)),
    ))
    sc = ServeConfig(max_slots=64, block_len=64, prefill_chunk=1024,
                     max_model_len=16384)
    spec, mb, _, waves = sc.resolve(model.config)
    assert spec.pages_shapes == ((1, 16385, 64, 1024),) * 2
    assert spec.state_shapes == (((1, 64, 512, 1024), "bfloat16"),) * 2
    donated = ["bf16[1,16385,64,1024]", "bf16[1,64,512,1024]"]
    decode_args, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    programs = {
        "decode": (build_decode_wave(model, waves=waves), decode_args,
                   DECODE_DONATE, ("paged_decode", "window_decode", "moe_gmm_gate_up",
                                   "moe_gmm_down")),
        "prefill": (build_prefill_step(model), prefill_args, PREFILL_DONATE,
                    ("moe_gmm_gate_up",)),
    }
    for name, (fn, args, donate, wanted) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        text = compiled.as_text()
        kernels = _kernel_instructions(text)
        for kernel in wanted:
            assert any(kernel in k for k in kernels), (name, kernel, kernels)
        if name == "decode":
            _assert_carry_stays_on_device(text, sc.max_slots)
        made = [
            (op, inst) for op, inst, _, line in _materialised(text)
            if any(t in line.split(" = ")[1].split("(")[0] for t in donated)
            and op not in ("parameter", "tuple", "get-tuple-element", "bitcast",
                           "while", "custom-call", "scatter")
            and not (op == "fusion" and re.search(r"/scatter\"", line))
        ]
        assert not made, (name, made)
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= spec.pool_bytes, (
            name, memory.alias_size_in_bytes, spec.pool_bytes)
        # Temporaries: a chunk's activations and a tile of its scores (a
        # full layer's 2,048-row tile at 48 heads is 403 MB; ``made`` above
        # is what says no page or ring array is copied), never a page array.
        assert memory.temp_size_in_bytes < 1 << 30, (name, memory.temp_size_in_bytes)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert aliases and aliases.group(1).count("alias") == 4, (name, text[:300])


# -- the K/V pool's long-table chunk attention is one kernel -----------------


def _kv_chunk_model(cell):
    """A dense model with a cell's caching attention widths and its table:
    codeagent's 48 query heads over 8 K/V heads of 128 in full layers beside
    sliding ones (YaRN over 64 lanes, a gate a head), longchat's 16 over 2
    of 256 (gated, q/k norms, rotary over 64 lanes); 16,384 positions,
    chunks of 1,024. Small FFN and vocabulary, so that it compiles in
    seconds."""
    from rocket_tpu.models.transformer import AttentionKind, TransformerConfig, TransformerLM
    from rocket_tpu.nn.attention import YarnScaling

    common = dict(vocab_size=1024, max_seq_len=16384, dim=2048, dropout=0.0,
                  tied_embeddings=False, activation_dtype="bfloat16", pos_embedding="rope",
                  norm="rmsnorm", norm_eps=1e-6, attn_bias=False, mlp="swiglu",
                  mlp_hidden=512, mlp_bias=False)
    if cell == "codeagent":
        yarn = YarnScaling(factor=64, original_max_position_embeddings=4096, beta_fast=64,
                           beta_slow=1)
        config = TransformerConfig(
            num_layers=4, num_heads=48, num_kv_heads=8, head_dim=128, attn_head_gate=True,
            layer_types=("full", "sliding", "full", "sliding"), attention_kinds={
                "full": AttentionKind(num_heads=48, rope_base=5e5, rope_fraction=0.5,
                                      rope_yarn=yarn),
                "sliding": AttentionKind(num_heads=64, rope_base=1e4, window=512)},
            **common)
        return TransformerLM(config), (1, 8, 6)
    config = TransformerConfig(
        num_layers=3, num_heads=16, num_kv_heads=2, head_dim=256, attn_gate=True,
        qk_norm=True, rope_base=1e7, rope_fraction=0.25, **common)
    return TransformerLM(config), (1, 2, 8)


@pytest.mark.parametrize("cell", ["codeagent", "longchat"])
def test_kv_prefill_program_attends_in_the_kv_prefill_kernel(sds, monkeypatch, cell):
    """The prefill program of a cell-shaped model for the chip: every live
    full-attention layer's chunk attention is a ``kv_prefill`` custom-call
    (the last layer's output is dead code in a chunk, which discards its
    logits), the layers share ONE lowering of it, no loop of float32 ``(S,
    Hkv, G, C, tile)`` scores is left, and the pages are still only
    scattered into in place."""
    import re

    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.ops.kv_prefill import kv_prefill_supported
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import PREFILL_DONATE, abstract_wave_inputs, build_prefill_step

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    model, (s, h_kv, g) = _kv_chunk_model(cell)
    d = model.config.head_dim
    sc = ServeConfig(max_slots=8, block_len=64, prefill_chunk=1024, max_model_len=16384)
    spec, mb, _, _ = sc.resolve(model.config)
    assert kv_prefill_supported(1024, h_kv * g, h_kv, d, 64, 16384, 2)
    _, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
                             prefill_chunk=sc.prefill_chunk),
    )
    lowered = jax.jit(build_prefill_step(model), donate_argnums=PREFILL_DONATE).lower(
        *prefill_args)
    stablehlo = lowered.as_text()
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", stablehlo)) == 1
    assert len(re.findall(r"call @kv_prefill", stablehlo)) == 2
    assert f"{s}x{h_kv}x{g}x1024x2048xf32>" not in stablehlo
    text = lowered.compile().as_text()
    assert sum("kv_prefill" in k for k in _kernel_instructions(text)) == 2, text[:300]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliases and aliases.group(1).count("alias") == len(spec.arrays), text[:300]


@pytest.mark.parametrize("cell", ["chat-busy", "reason"])
def test_short_table_prefill_programs_hold_no_kv_prefill(sds, monkeypatch, cell):
    """The chat-busy and reason cells' chunks (20 heads x 64 against 1,024
    positions, chunks of 128; 20 over 1 K/V head of 128 against 4,096,
    chunks of 512) make fewer one-shot scores than the long-table walk's
    threshold, so their prefill programs attend in XLA as before: no
    ``kv_prefill`` in them."""
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import abstract_wave_inputs, build_prefill_step

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    if cell == "chat-busy":
        model = TransformerLM(TransformerConfig(
            vocab_size=1024, max_seq_len=1024, dim=1280, num_layers=2, num_heads=20,
            dropout=0.0, activation_dtype="bfloat16"))
        sc = ServeConfig(max_slots=32, block_len=16, prefill_chunk=128)
    else:
        model = TransformerLM(TransformerConfig(
            vocab_size=1024, max_seq_len=4096, dim=2560, num_layers=2, num_heads=20,
            num_kv_heads=1, head_dim=128, dropout=0.0, activation_dtype="bfloat16",
            pos_embedding="none", norm="rmsnorm", attn_bias=False))
        sc = ServeConfig(max_slots=64, block_len=64, prefill_chunk=512, max_model_len=4096)
    spec, mb, _, _ = sc.resolve(model.config)
    assert not paged._long_chunk(1, sc.prefill_chunk, 20, mb, sc.block_len)
    _, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
                             prefill_chunk=sc.prefill_chunk),
    )
    stablehlo = jax.jit(build_prefill_step(model)).lower(*prefill_args).as_text()
    assert "kv_prefill" not in stablehlo and "tpu_custom_call" not in stablehlo


# -- lightning state and block-sparse attention: five kernels ----------------


def _conditionals_reaching(text, kernel):
    """For each ``conditional`` of a compiled program whose branches reach
    a custom call named ``kernel``: the bodies of every computation its
    branches reach (calls, fusions and loops followed)."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name:
            bodies[name].append(line)
    bodies = {k: "\n".join(v) for k, v in bodies.items()}

    def reached(roots):
        seen, todo = set(), list(roots)
        while todo:
            n = todo.pop()
            if n in bodies and n not in seen:
                seen.add(n)
                todo += re.findall(r"%([\w.\-]+)", bodies[n])
        return [bodies[n] for n in seen]

    out = []
    for body in bodies.values():
        for branches in re.findall(
                r" conditional\([^\n]*branch_computations=\{([^}]*)\}", body):
            inside = reached(re.findall(r"%?([\w.\-]+)", branches))
            if any(re.search(rf"%{kernel}[\w.]* = [^\n]*custom-call\(", b)
                   for b in inside):
                out.append(inside)
    return out


def test_sala_serve_programs_hold_the_four_kernels_and_update_in_place(sds, monkeypatch):
    """The engine's two programs for a block-sparse layer and a lightning
    layer at the MiniCPM-SALA cell's widths (d 4096; 32 query heads over 2
    K/V heads of 128, no rotary, an elementwise gate; 32 lightning heads of
    128; 32 slots x 65536 positions in blocks of 64, chunks of 1024; the
    sparse layer first, so that its attention feeds the lightning state,
    and a small vocabulary, so that it compiles in seconds): the decode
    program holds ``sparse_decode`` and ``lightning_step``, the prefill
    program ``sparse_prefill`` and ``lightning_chunk``, and each one
    ``select_scores`` inside the page choice's conditional, which holds no
    ``while`` (the XLA loop over a group's heads is the CPU's); the four
    donated arrays — K and V pages by block, the rank-5 lightning state and the
    compressed keys by slot — are aliased input to output, and nothing but
    a program's own update of them (the kernel that owns ``S``, a row
    scatter) produces an array of their types: no copy."""
    import re

    import rocket_tpu.nn.lightning as lightning
    import rocket_tpu.ops.paged_attention as paged
    from rocket_tpu.models.transformer import AttentionKind, TransformerConfig, TransformerLM
    from rocket_tpu.nn.lightning import LightningConfig
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE,
        PREFILL_DONATE,
        abstract_wave_inputs,
        build_decode_wave,
        build_prefill_step,
    )

    monkeypatch.setattr(paged, "_on_cpu", lambda: False)
    monkeypatch.setattr(lightning, "_on_cpu", lambda: False)
    model = TransformerLM(TransformerConfig(
        vocab_size=1024, max_seq_len=65536, dim=4096, num_layers=2, num_heads=32,
        num_kv_heads=2, head_dim=128, dropout=0.0, tied_embeddings=False,
        activation_dtype="bfloat16", pos_embedding="rope", norm="rmsnorm",
        norm_eps=1e-6, mlp="swiglu", mlp_hidden=16384, mlp_bias=False,
        attn_bias=False, attn_gate=True, qk_norm=True,
        layer_types=("minicpm4", "lightning-attn"), attention_kinds={
            "minicpm4": AttentionKind(rope=False, sparse=True),
            "lightning-attn": AttentionKind(state=True)},
        lightning=LightningConfig(32, 128, published_layers=32, first_layer=16),
        sparse_attention=paged.SparseAttentionConfig(),
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5, logit_divisor=16.0,
    ))
    sc = ServeConfig(max_slots=32, block_len=64, prefill_chunk=1024,
                     max_model_len=65536)
    spec, mb, _, waves = sc.resolve(model.config)
    assert spec.pages_shapes == ((1, 32769, 64, 256),) * 2
    assert spec.state_shapes == (((1, 32, 32, 128, 128), "float32"),
                                 ((1, 32, 4096, 256), "bfloat16"))
    donated = ["bf16[1,32769,64,256]", "f32[1,32,32,128,128]", "bf16[1,32,4096,256]"]
    decode_args, prefill_args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        abstract_wave_inputs(
            model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
            prefill_chunk=sc.prefill_chunk,
        ),
    )
    programs = {
        "decode": (build_decode_wave(model, waves=waves), decode_args,
                   DECODE_DONATE, ("sparse_decode", "lightning_step", "select_scores")),
        "prefill": (build_prefill_step(model), prefill_args, PREFILL_DONATE,
                    ("sparse_prefill", "lightning_chunk", "select_scores")),
    }
    for name, (fn, args, donate, wanted) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        text = compiled.as_text()
        kernels = _kernel_instructions(text)
        for kernel in wanted:
            assert sum(kernel in k for k in kernels) == 1, (name, kernel, kernels)
        choice = _conditionals_reaching(text, "select_scores")
        assert len(choice) == 1, (name, choice)
        assert not any(re.search(r" while\(", body) for body in choice[0]), name
        if name == "decode":
            _assert_carry_stays_on_device(text, sc.max_slots)
        materialised = _materialised(text)
        moves = {inst for op, inst, _, line in materialised
                 if op == "copy-start" and "S(1)" in line}
        made = [
            (op, inst) for op, inst, _, line in materialised
            if any(t in line.split(" = ")[1].split("(")[0] for t in donated)
            and op not in ("parameter", "tuple", "get-tuple-element", "bitcast",
                           "while", "custom-call", "dynamic-update-slice",
                           "scatter", "conditional")
            and not (op == "copy-start" and inst in moves)
            and not (op == "copy-done" and any(
                f"copy-done(%{m})" in line or f"copy-done({m})" in line
                for m in moves))
            and not (op == "fusion" and re.search(
                r"/scatter\"|dynamic_update_slice|dynamic-update-slice", line))
        ]
        assert not made, (name, made)
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= spec.pool_bytes, (
            name, memory.alias_size_in_bytes, spec.pool_bytes)
        # Temporaries: a chunk's activations and its selection's scores,
        # never a copy of a page array (1 GB) or of a state array.
        assert memory.temp_size_in_bytes < 2 * spec.state_bytes, (
            name, memory.temp_size_in_bytes)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert aliases and aliases.group(1).count("alias") == 4, (name, text[:300])
