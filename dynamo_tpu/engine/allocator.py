"""Paged KV block allocator with content-addressed prefix reuse.

The G1 (device/HBM) tier of the KV block manager (reference:
lib/llm/src/block_manager/pool/{active.rs,inactive.rs} — ref-counted
active blocks + LRU-ordered inactive pool with sequence-hash dedupe).

Block 0 is reserved as the padding/garbage block: padded entries of block
tables and slot mappings point at it, so masked lanes have somewhere
harmless to read/write.

Emits KV events (stored/removed) through ``on_event`` — the feed for the
KV-aware router's radix indexer (reference: kv_router/publisher.rs).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Optional

KvEventFn = Callable[[str, list[int], list[int]], None]
# signature: (op="stored"|"removed", block_hashes, parent_info) — see publisher


@dataclass
class _Block:
    id: int
    ref_count: int = 0
    seq_hash: Optional[int] = None  # set once the block's content is complete


class NoBlocksError(RuntimeError):
    pass


class StateSlots:
    """Slots of the per-sequence state plane (models with recurrent
    layers keep a fixed-size state per running sequence beside their
    paged rows). Slot 0 is the garbage slot of padded rows, like block
    0. A slot is NOT cleared on release or on acquisition: the model
    starts a row from a zero state whenever the row starts at position
    0, whatever its slot holds (models/kimi_linear.py)."""

    def __init__(self, num_slots: int):
        if num_slots < 2:
            raise ValueError("need at least 2 state slots (slot 0 is reserved)")
        self.num_slots = num_slots
        self._free = list(range(num_slots - 1, 0, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_slots - 1 - len(self._free)

    def acquire(self) -> int:
        if not self._free:
            raise NoBlocksError("no free state slots")
        return self._free.pop()

    def release(self, slot: int) -> None:
        if slot <= 0 or slot >= self.num_slots or slot in self._free:
            raise ValueError(f"state slot {slot} is not held")
        self._free.append(slot)


class WindowPlane:
    """The SECOND page plane of a model whose window layers attend only
    the last ``window`` keys (``models/__init__.py``: the third question
    asked of a family): its own block ids, its own free list, and a
    table a row (``Sequence.window_table``) indexed by the SAME absolute
    column as the row's full-plane table — column ``c`` holds positions
    ``c * block_size ...`` — in which a column behind the window reads 0.
    Both attention kernels walk only a row's live pages under a window
    (``ops/paged_attention.py``), so a dead column is never dereferenced;
    the table's bytes are 4 a column a row (512 B at a 128-page table: a
    ring of the live columns would save those and cost every reader a
    modulus).

    A page goes back once every key in it is older than ``p - (window -
    1)`` for the row's NEXT query position ``p`` (``release_behind``,
    called as the host's view of the row advances: after a prefill
    chunk, after an appended token). That is safe with steps in flight:
    a dispatch carries its own snapshot of the table, a page is only
    handed to a row by a LATER dispatch, and the device runs its
    programs in order. Block 0 is the garbage block, as in the full
    plane. Nothing here is content-addressed: a released plane has no
    prefix to reuse."""

    def __init__(self, num_blocks: int, block_size: int, window: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        if window < 1:
            raise ValueError(f"window {window} < 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.window = window
        self._free = list(range(num_blocks - 1, 0, -1))
        # pages handed back behind a live row's window (not a finished
        # or preempted row's whole table)
        self.released_total = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def first_live(self, next_query: int) -> int:
        """The first column a query at position ``next_query`` (or any
        later one) can still read."""
        return max(0, next_query - (self.window - 1)) // self.block_size

    @staticmethod
    def pages_spanned(window: int, block_size: int, tokens: int) -> int:
        """The most pages the keys of ``tokens`` consecutive queries
        touch under ``window``: a run of ``window - 1 + tokens``
        positions, wherever it starts."""
        run = window - 1 + max(1, tokens)
        return (run - 2) // block_size + 2 if run > 1 else 1

    def span_pages(self, tokens: int) -> int:
        """``pages_spanned`` at this plane's window and page size."""
        return self.pages_spanned(self.window, self.block_size, tokens)

    def release_behind(self, table: list[int], next_query: int) -> int:
        """Hand back the row's pages behind the window of its next
        query; returns how many."""
        # the columns a row holds are one run (``cover`` fills every hole
        # from the first live column up, this takes from the run's left),
        # so the walk goes down from the window's edge and ends at the
        # first column already given back: a token's worth, not a row's
        n = 0
        col = min(self.first_live(next_query), len(table)) - 1
        while col >= 0 and table[col]:
            self._free.append(table[col])
            table[col] = 0
            n += 1
            col -= 1
        self.released_total += n
        return n

    def cover(self, table: list[int], cols: int, next_query: int) -> None:
        """Make the row hold columns ``first_live(next_query) ... cols -
        1``. Raises NoBlocksError (the scheduler's admission reserve is
        what keeps that from happening) with nothing taken."""
        lo = min(self.first_live(next_query), cols)
        table.extend([0] * (cols - len(table)))
        missing = [c for c in range(lo, cols) if not table[c]]
        if len(missing) > len(self._free):
            raise NoBlocksError(
                f"window plane: need {len(missing)} pages, have "
                f"{len(self._free)}")
        for c in missing:
            table[c] = self._free.pop()

    def free_row(self, table: list[int]) -> None:
        """A finished, cancelled or preempted row's pages."""
        self._free.extend(b for b in table if b)
        table.clear()

    def held(self, table: list[int]) -> int:
        return len(table) - table.count(0)


class BlockAllocator:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        on_event: Optional[KvEventFn] = None,
    ):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.enable_prefix_caching = enable_prefix_caching
        self.on_event = on_event
        self._blocks = [_Block(i) for i in range(num_blocks)]
        # free blocks in LRU order (least-recently-freed first = evict first)
        self._free: OrderedDict[int, None] = OrderedDict(
            (i, None) for i in range(1, num_blocks)
        )
        # seq_hash -> block id, for complete cached blocks (active or free)
        self._hash_index: dict[int, int] = {}
        # free blocks still holding content-addressed KV (maintained
        # incrementally: O(free) scans per scrape would defeat the
        # point of a per-step gauge)
        self._cached_free = 0

    # -- introspection ----------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached_free(self) -> int:
        """Free blocks whose content is still content-addressed — the
        prefix cache's evictable working set (observability)."""
        return self._cached_free

    @property
    def usage(self) -> float:
        usable = self.num_blocks - 1
        return 1.0 - len(self._free) / usable if usable else 1.0

    def lookup_block(self, seq_hash: int) -> Optional[int]:
        """Device block currently holding this content (if cached)."""
        return self._hash_index.get(seq_hash)

    def match_prefix(self, seq_hashes: list[int]) -> int:
        """How many leading complete blocks are cached (no allocation)."""
        n = 0
        for h in seq_hashes:
            if h in self._hash_index:
                n += 1
            else:
                break
        return n

    def pinned_prefix(self, seq_hashes: list[int]) -> tuple[int, int]:
        """Of this prompt's leading cached blocks, how many some sequence
        holds now, and how many of those exactly ONE sequence holds (no
        allocation). The first cost the FREE pool nothing at admission —
        charging them would make admission stall on exactly the
        shared-prefix workloads prefix caching exists for; the second
        are pages their one holder's finish would have given back and,
        once this prompt pins them too, will not."""
        pinned = alone = 0
        if self.enable_prefix_caching:
            for h in seq_hashes:
                bid = self._hash_index.get(h)
                if bid is None:
                    break
                if bid not in self._free:
                    pinned += 1
                    alone += self._blocks[bid].ref_count == 1
        return pinned, alone

    def held_alone(self, block_ids: list[int]) -> int:
        """How many of a sequence's blocks nobody else pins: what its
        finish gives back to the free pool."""
        if not self.enable_prefix_caching:
            return len(block_ids)  # nothing is ever shared
        blocks = self._blocks
        return sum(blocks[bid].ref_count == 1 for bid in block_ids)

    # -- allocation -------------------------------------------------------
    def allocate_prefix(self, seq_hashes: list[int]) -> tuple[list[int], int]:
        """Allocate blocks for a prompt: reuse the cached complete-block
        prefix, fresh-allocate the rest. Returns (block_ids, cached_blocks).

        Raises NoBlocksError (allocating nothing) if capacity is short.
        """
        reused: list[int] = []
        if self.enable_prefix_caching:
            for h in seq_hashes:
                bid = self._hash_index.get(h)
                if bid is None:
                    break
                reused.append(bid)
        # pin reused blocks FIRST so _pop_free can't evict them from the
        # free/cached list while we allocate the fresh tail
        for bid in reused:
            self._ref(bid)
        need_fresh = len(seq_hashes) - len(reused)
        if need_fresh > len(self._free):
            self.free_sequence(reused)  # rollback pins
            raise NoBlocksError(
                f"need {need_fresh} fresh blocks, have {len(self._free)}"
            )
        fresh = [self._pop_free() for _ in range(need_fresh)]
        return reused + fresh, len(reused)

    def allocate_block(self) -> int:
        """One fresh block (decode growth)."""
        if not self._free:
            raise NoBlocksError("no free blocks")
        return self._pop_free()

    def commit_block(self, block_id: int, seq_hash: int) -> None:
        """Mark a block's content complete + content-addressed."""
        if not self.enable_prefix_caching:
            return
        block = self._blocks[block_id]
        if block.seq_hash == seq_hash:
            return  # already committed: no duplicate event
        old = self._hash_index.get(seq_hash)
        if old is not None and old != block_id:
            # duplicate content computed concurrently; keep the existing entry
            return
        block.seq_hash = seq_hash
        self._hash_index[seq_hash] = block_id
        if block_id in self._free:  # defensive: commits normally target
            self._cached_free += 1  # active blocks
        if self.on_event:
            self.on_event("stored", [seq_hash], [block_id])

    def free_sequence(self, block_ids: list[int]) -> None:
        """Release a sequence's blocks. Hashed blocks stay cached (LRU);
        unhashed blocks are recycled immediately."""
        for bid in block_ids:
            if bid == 0:
                continue
            block = self._blocks[bid]
            block.ref_count -= 1
            if block.ref_count > 0:
                continue
            if block.seq_hash is None:
                self._free[bid] = None  # plain free
                self._free.move_to_end(bid, last=False)  # recycle soon
            else:
                self._free[bid] = None  # cached-free: evict LRU-last
                self._free.move_to_end(bid, last=True)
                self._cached_free += 1

    # -- internals --------------------------------------------------------
    def _ref(self, bid: int) -> None:
        block = self._blocks[bid]
        if block.ref_count == 0:
            if self._free.pop(bid, -1) is None and block.seq_hash is not None:
                self._cached_free -= 1
        block.ref_count += 1

    def _evictable_count(self) -> int:
        return len(self._free)

    def _pop_free(self) -> int:
        if not self._free:
            raise NoBlocksError("no free blocks")
        bid, _ = self._free.popitem(last=False)
        block = self._blocks[bid]
        if block.seq_hash is not None:
            # evicting cached content
            self._cached_free -= 1
            self._hash_index.pop(block.seq_hash, None)
            if self.on_event:
                self.on_event("removed", [block.seq_hash], [bid])
            block.seq_hash = None
        block.ref_count = 1
        return bid
