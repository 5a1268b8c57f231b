"""Refcounted fixed-size KV block allocator (host-side, jax-free).

The device pool is a flat array of ``num_blocks`` KV blocks per layer;
this allocator owns WHICH blocks are free, who holds references, and the
gauges the serving surface exports (``kv_blocks_{total,free,shared}``).
Pure host bookkeeping over small integer lists — it never touches the
device, so the router and tests can reason about pool pressure on hosts
with no accelerator runtime.

Reference protocol (copy-on-write sharing):

- every *user* of a block holds one reference: a slot whose block table
  points at it, and the radix prefix cache for every block it has
  indexed;
- a block with ``refcount >= 2`` is **shared** — by construction it is
  frozen (only fully-written prompt blocks enter the prefix cache, and
  decode writes land strictly beyond the prompt), so sharing needs no
  device-side copy;
- a block whose last reference drops returns to the free list.

Block id 0 is RESERVED as the trash block: masked device writes
(inactive slots, padded prefill tail) are steered to it instead of being
predicated out, so one compiled program serves every occupancy pattern.
The allocator never hands it out.
"""

from __future__ import annotations


class NoFreeBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation even after cache eviction.

    Raised to the serving layer, which parks the admission until decode
    retirements free blocks (backpressure, not failure)."""


class BlockAllocator:
    """Free-list + refcount bookkeeping over ``num_blocks`` KV blocks.

    ``num_blocks`` INCLUDES the reserved trash block 0, so a pool sized
    ``slots * blocks_per_slot + 1`` is exactly dense-slot-pool capacity.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved), got "
                f"{num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._refs = [0] * num_blocks
        self._refs[0] = 1  # the trash block is permanently held
        # LIFO free list: recently-freed blocks are re-used first (their
        # pool rows are hottest in any cache hierarchy).
        self._free = list(range(num_blocks - 1, 0, -1))

    # ------------------------------------------------------------- queries

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def usable_blocks(self) -> int:
        """Blocks a single request could ever hold (total minus trash)."""
        return self.num_blocks - 1

    def refcount(self, block_id: int) -> int:
        return self._refs[block_id]

    @property
    def shared_count(self) -> int:
        """Blocks referenced more than once (prefix-cache sharing at work;
        the cache's own index reference is excluded by the >2 threshold
        for blocks it holds — callers report the simpler >=2 count)."""
        return sum(1 for r in self._refs[1:] if r >= 2)

    # ----------------------------------------------------------- lifecycle

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` free blocks (refcount 1 each); raises
        :class:`NoFreeBlocksError` without allocating when short."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise NoFreeBlocksError(
                f"need {n} KV blocks, only {len(self._free)} free"
            )
        taken = [self._free.pop() for _ in range(n)]
        for block_id in taken:
            self._refs[block_id] = 1
        return taken

    def ref(self, block_ids: list[int]) -> None:
        """Add one reference to each block (prefix-cache hit / index)."""
        for block_id in block_ids:
            if self._refs[block_id] < 1:
                raise ValueError(f"block {block_id} is not allocated")
            self._refs[block_id] += 1

    def deref(self, block_ids: list[int]) -> int:
        """Drop one reference per block; returns how many blocks freed."""
        freed = 0
        for block_id in block_ids:
            if block_id == 0:
                raise ValueError("the trash block is never deref'd")
            refs = self._refs[block_id]
            if refs < 1:
                raise ValueError(f"block {block_id} is not allocated")
            self._refs[block_id] = refs - 1
            if refs == 1:
                self._free.append(block_id)
                freed += 1
        return freed

    def gauges(self) -> dict:
        """The /metrics view: total (usable), free, shared."""
        return {
            "kv_blocks_total": self.usable_blocks,
            "kv_blocks_free": self.free_count,
            "kv_blocks_shared": self.shared_count,
        }


class WindowChain:
    """One slot's chain of blocks in a sliding-window pool group.

    A window layer reads the keys ``p - window < j <= p`` of a query at
    position ``p``, so a block that lies wholly below ``p - window + 1`` is
    dead.  The chain holds the blocks from ``first`` (a logical block index)
    on, at most ``cap`` of them; :meth:`advance` returns dead blocks to the
    allocator's free list and takes as many again for the positions ahead.
    With ``cap = (window + chunk) // block_size`` and block-aligned chunks
    that is always enough for the next chunk or the next token, and because
    a chain frees before it takes, it never asks the allocator for more than
    it was admitted with: a reservation, like the full group's whole chain.
    A request that fits under ``cap`` never recycles and is today's chain.
    """

    def __init__(self, allocator: BlockAllocator, cap: int, need: int):
        #: Blocks the request will ever touch (its full group's chain length).
        self.need = need
        self.cap = cap
        self.allocator = allocator
        self.first = 0
        self.ids = allocator.alloc(min(need, cap))
        self.recycled = 0

    def advance(self, lo_pos: int) -> int:
        """Positions below ``lo_pos`` will not be read again: free the
        blocks wholly below it, extend the chain by as many (never past the
        request's end).  Returns how many blocks were recycled."""
        dead = min(max(lo_pos, 0) // self.allocator.block_size - self.first, len(self.ids))
        if dead <= 0:
            return 0
        self.allocator.deref(self.ids[:dead])
        self.first += dead
        ahead = min(self.cap, self.need - self.first) - (len(self.ids) - dead)
        self.ids = self.ids[dead:] + self.allocator.alloc(max(ahead, 0))
        self.recycled += dead
        return dead

    def covers(self, pos: int) -> bool:
        block = pos // self.allocator.block_size
        return self.first <= block < self.first + len(self.ids)

    def release(self) -> None:
        self.allocator.deref(self.ids)
        self.ids = []


class GrowingWindowChain:
    """One slot's chain in a window group that is **not** a reservation: the
    blocks from ``first`` (a logical block index) on, taken one by one as
    the slot's launches reach them (:meth:`reach`) and given back as soon as
    the launch that read them is queued (:meth:`advance`).  `WindowChain`
    holds ``window + chunk`` positions a slot from admission on; where the
    window is far shorter than a chunk that is many windows' worth held by
    every slot for the one chunk in flight, so here the group is sized for
    what the slots hold *between* launches - the blocks back from a slot's
    next query's window start - and one chunk's beside them, and it is the
    caller (`host_cache.HostGroupedRows`) that cuts every chain back before
    it lets one reach ahead."""

    def __init__(self, allocator: BlockAllocator):
        self.allocator = allocator
        self.first = 0
        self.ids: list[int] = []

    def advance(self, lo_pos: int) -> int:
        """Positions below ``lo_pos`` will not be read again: free the
        blocks wholly below it.  Returns how many."""
        dead = min(max(lo_pos, 0) // self.allocator.block_size - self.first, len(self.ids))
        if dead <= 0:
            return 0
        self.allocator.deref(self.ids[:dead])
        self.first, self.ids = self.first + dead, self.ids[dead:]
        return dead

    def reach(self, lo_pos: int, last_pos: int) -> bool:
        """Hold the blocks of positions ``lo_pos .. last_pos`` (what lies
        below was given up by :meth:`advance`).  Returns whether the chain
        grew."""
        block_size = self.allocator.block_size
        if not self.ids:  # nothing live: the chain starts at the window
            self.first = max(lo_pos, 0) // block_size
        ahead = last_pos // block_size + 1 - self.first - len(self.ids)
        if ahead > 0:
            self.ids = self.ids + self.allocator.alloc(ahead)
        return ahead > 0

    def release(self) -> None:
        self.allocator.deref(self.ids)
        self.ids = []
