"""Paged KV block pool — fixed-shape HBM arrays + the host-side allocator.

The pool is the serving engine's only model-state memory: the arrays the
model's layers declare (``TransformerConfig.kv_pool_lanes``), each
``(L, num_blocks, block_len, lanes)``, allocated ONCE and sized
independently of how many requests ever flow through the engine. ``L``
counts the layers that CACHE PAGES (``TransformerConfig.cache_layers``: 2
of a 28-layer hybrid's, not 28). A
multi-head model declares two, K and V, of ``Hkv*D`` lanes; a latent-
attention model ONE, of ``kv_lora_rank + qk_rope_head_dim`` lanes (the
normed latent and the rotated shared key side by side: there is no V). A
page is stored as the ``(block_len, lanes)`` tile both compiled programs
read and write in place: everything a token caches in a layer side by
side on the minor (lane) axis, so a row is one scatter update, a page is
one kernel block and no program ever relayouts the pool
(``ops/paged_attention.py``). Requests
own *blocks*, not cache rows: the allocator hands out integer block ids on
the host and the compiled step indexes the pool through per-slot block
tables, so admitting a request is a few host list operations and never
touches compiled code.

**State by slot, beside the pages by block.** A layer that caches no
rows but carries a fixed-size state declares it in
``TransformerConfig.slot_state_shapes`` — a VECTOR a channel (a
state-space mixer, ``nn/ssm.py``: ``(d_state, d_inner)``) or a MATRIX a
head (a Gated DeltaNet, ``nn/gdn.py``: ``(value heads, dk, dv)``, so the
array is of rank 5), each with its convolution's tail; the pool then
holds, behind its page arrays and in the SAME donated tuple, one array per
declared state, ``(state layers, max_slots, ...)``, indexed by SLOT: no
allocator hands it out, a request has it for as long as it has its slot.
The pool reads the shapes and nothing else of the mixer. Nothing on the host resets it: both programs start a slot's
state from zeros wherever its chunk starts at position 0, which is where a
new request, a reused slot and an evicted request's re-prefill all start.

**Compressed keys by slot for the sparse layers.** A layer that attends
the blocks it picks (``TransformerConfig.sparse_attention``) caches its
K/V rows in the pages like any other, and behind the mixers' state one
more per-slot array holds its compressed keys, ``(sparse layers,
max_slots, max_seq_len / kernel_stride, Hkv*D)``: a row every
``kernel_stride`` positions, written when the ``kernel_size`` keys it
pools are all in the pages. Nothing resets it either: a row is read only
once a position at or past its last key has been written again.

**A ring by slot for the window layers.** A layer that attends only the
last ``window`` positions (``TransformerConfig.attention_kinds``) caches no
pages either: its K and V rows live in two more per-slot arrays,
``(window layers, max_slots, window, Hkv*D)``, declared through the same
``slot_state_shapes`` — position ``p`` at ring row ``p mod window``, no
allocator traffic, not counted by ``cache_layers``. Nothing resets a ring:
which of its rows are live follows from the position alone
(``ops.paged_attention.window_attention``), so a reused slot and a
re-prefill read nothing stale. A model without window layers has no ring.

Block 0 is RESERVED as the trash sink: masked writes (prompt padding,
inactive slots) land there and unmapped block-table entries point at it,
which is what lets one fixed-shape compiled step serve every admission
state. The allocator never hands it out.

Fragmentation: blocks are the unit of allocation, so there is no external
fragmentation by construction — any free block serves any request; the
only waste is internal (the tail of a sequence's last block, bounded by
``block_len - 1`` rows per sequence).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

__all__ = ["KVPoolSpec", "BlockAllocator"]


@dataclass(frozen=True)
class KVPoolSpec:
    """Shape of the paged pool for one model. ``lanes`` holds, for each
    pool array, what a layer caches per token in it; left empty it is the
    K and V of ``num_kv_heads * head_dim`` lanes each. ``slot_state``
    (``TransformerConfig.slot_state_shapes``: ``(layers, per-slot shape,
    dtype)`` an array) and ``max_slots`` size the per-slot state arrays
    that follow the page arrays in the engine's donated tuple."""

    num_layers: int
    num_blocks: int
    block_len: int
    num_kv_heads: int = 0
    head_dim: int = 0
    dtype: str = "float32"
    lanes: tuple = ()
    slot_state: tuple = ()
    max_slots: int = 0
    #: Rows of a window layer's ring a slot (``TransformerConfig.window``;
    #: 0: the model has no window layer). The rings are two of the
    #: per-slot arrays; the scheduler reads this to say how many rows a
    #: wave's window layers attend.
    window: int = 0
    #: The sparse layers' selection (``TransformerConfig.
    #: sparse_attention``; None: the model has no sparse layer). Their
    #: compressed keys are the last per-slot array; the scheduler reads
    #: this to say how many pages a wave's sparse layers read.
    sparse: Optional[object] = None

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError(
                "KVPoolSpec: need at least 2 blocks (block 0 is the "
                f"reserved trash sink), got {self.num_blocks}"
            )
        if self.block_len < 1:
            raise ValueError(f"KVPoolSpec: block_len {self.block_len} < 1")
        if not self.lanes:
            kv = self.num_kv_heads * self.head_dim
            object.__setattr__(self, "lanes", (kv, kv))
        if not all(int(n) > 0 for n in self.lanes):
            raise ValueError(f"KVPoolSpec: lanes {self.lanes} must be > 0")
        if self.slot_state and self.max_slots < 1:
            raise ValueError(
                "KVPoolSpec: per-slot state needs max_slots >= 1, got "
                f"{self.max_slots}"
            )

    @property
    def block_bytes(self) -> int:
        """HBM bytes ONE block costs across the pool's arrays (K and V,
        or the one latent array) and all layers."""
        itemsize = jnp.dtype(self.dtype).itemsize
        return self.num_layers * self.block_len * sum(self.lanes) * itemsize

    @property
    def state_shapes(self) -> tuple:
        """``(shape, dtype)`` of each per-slot state array: ``(state
        layers, max_slots) + per-slot shape``."""
        return tuple(
            ((int(layers), self.max_slots) + tuple(shape), dtype)
            for layers, shape, dtype in self.slot_state
        )

    @property
    def state_bytes(self) -> int:
        """HBM of the per-slot state arrays (0 for a model without)."""
        return sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for shape, dtype in self.state_shapes
        )

    @property
    def pool_bytes(self) -> int:
        """Total HBM of the donated tuple: ``num_blocks * block_bytes`` of
        pages plus :attr:`state_bytes` — the serving engine's peak cache
        memory regardless of request count."""
        return self.num_blocks * self.block_bytes + self.state_bytes

    @property
    def pages_shapes(self) -> tuple:
        """Shape of each pool array: ``(L, NB, BL, lanes)``."""
        return tuple(
            (self.num_layers, self.num_blocks, self.block_len, int(n))
            for n in self.lanes
        )

    @property
    def pages_shape(self) -> tuple[int, int, int, int]:
        """Shape of the first pool array (of ``k_pages``; ``v_pages`` has
        the same): ``(L, NB, BL, Hkv*D)``."""
        return self.pages_shapes[0]

    @property
    def arrays(self) -> tuple:
        """``(shape, dtype)`` of every array of the donated tuple, in its
        order: the page arrays, then the per-slot state arrays."""
        return tuple(
            (shape, self.dtype) for shape in self.pages_shapes
        ) + self.state_shapes

    def init_pages(self) -> tuple:
        """The zeroed device pool: one array per entry of ``lanes`` —
        ``(k_pages, v_pages)`` for a K/V pool — then the per-slot state
        arrays, if the model declares any."""
        return tuple(
            jnp.zeros(shape, jnp.dtype(dtype)) for shape, dtype in self.arrays
        )


class BlockAllocator:
    """Host-side free-list over block ids ``1 .. num_blocks-1``.

    All-or-nothing ``alloc(n)`` (a partially admitted request would leak
    on the failure path) and loud invariant checks: double-alloc,
    double-free and freeing the reserved block are bugs, not conditions
    to paper over.
    """

    RESERVED = 0  # the trash block — never allocated

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"BlockAllocator: need at least 2 blocks, got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() -> low ids first
        self._used: set[int] = set()

    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the reserved trash block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return len(self._used)

    @property
    def free_fraction(self) -> float:
        return len(self._free) / max(self.capacity, 1)

    def alloc(self, n: int = 1) -> Optional[list[int]]:
        """``n`` block ids, or None when the pool can't serve all of them
        (the caller applies back-pressure / eviction — this is the one
        condition that is NOT an error)."""
        if n < 0:
            raise ValueError(f"BlockAllocator.alloc: n {n} < 0")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._used.update(blocks)
        return blocks

    def free(self, blocks) -> None:
        for block in blocks:
            if block == self.RESERVED:
                raise ValueError(
                    "BlockAllocator.free: block 0 is the reserved trash sink"
                )
            if block not in self._used:
                raise ValueError(
                    f"BlockAllocator.free: block {block} is not allocated "
                    "(double free?)"
                )
            self._used.remove(block)
            self._free.append(block)
