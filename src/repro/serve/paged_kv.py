"""Paged KV cache: fixed-size pages, per-sequence block tables, a free-list.

The continuous-batching engine (serve/engine.py ``run``) stores decode K/V in
a pool of fixed-size pages shared by all in-flight sequences instead of one
dense ``[B, max_len, ...]`` buffer per batch.  Each sequence owns a *block
table* -- logical block ``i`` (token positions ``i*page_size ..
(i+1)*page_size - 1``) maps to a physical page id -- and pages are allocated
from / returned to a free-list as requests start, grow, and finish.  This is
the vLLM paged-attention memory model reduced to its jnp-serving essentials:
no copy-on-write (no beam search here), no swapping, and attention gathers
whole pages through the block table (models/layers.py::paged_attention)
rather than running a per-page kernel.

Invariants the rest of the stack relies on:

* **Page 0 is the trash page.**  It is never handed out by the allocator.
  Unmapped block-table entries (idle slots' whole rows, and every active
  sequence's not-yet-grown tail blocks) point at it, so gathers *do* read
  trash -- which is safe because page 0's position plane is all-sentinel
  and must stay that way: idle decode lanes write with
  ``pos = POS_SENTINEL`` (scheduler.batch), so the only writes that ever
  reach page 0 are themselves unattendable.
* **Position-sentinel scrubbing.**  A page's ``pos`` slots are reset to
  ``POS_SENTINEL`` (int32 max) at *allocation* time (:func:`scrub_pages`).
  K/V bytes from a previous owner may persist, but the causal mask
  ``kv_pos <= q_pos`` rejects sentinel positions, so stale data is
  unreachable.  Freeing is O(1) -- no scrub on release.
* **Layout contract** (built by ``LM.init_paged_cache``, keyed by
  ``LMConfig.cache_kinds()``): ``"paged"`` entries are
  ``{"k","v": (R, P, Hkv, page_size, hd), "pos": (R, P, page_size)}``
  (int8 pools add ``"k_s","v_s": (R, P, Hkv, page_size)``);
  ``"memory"`` / ``"state"`` entries are the dense per-slot caches with the
  batch axis sized to the number of scheduler slots.  ``R`` is the scan
  stack (n_repeat); all repeats of a block write the same positions, so one
  block table serves every layer.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the models' sentinel conventions (unwritten/unattendable KV positions;
# the reserved trash page sentinel lanes write into) are the single source
# of truth: the scheduler's idle-lane writes, the pool's scrub value and
# the allocator's reserved page must be bit-equal to what the model's
# attention mask rejects and its paged write path routes to
from repro.models.transformer import POS_SENTINEL, TRASH_PAGE


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages required to hold ``n_tokens`` KV positions."""
    return -(-max(n_tokens, 0) // page_size)


class PageAllocator:
    """Free-list allocator over physical page ids ``1 .. num_pages-1``.

    Page 0 (``TRASH_PAGE``) is reserved and never allocated.  ``alloc`` is
    all-or-nothing: it raises :class:`PagesExhausted` rather than returning a
    partial set, so callers either get a usable block run or can keep the
    request queued (admission backpressure).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PagesExhausted(
                f"requested {n} pages, {len(self._free)} free of "
                f"{self.num_pages - 1} allocatable")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"bad page id {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)


class PagesExhausted(RuntimeError):
    """Raised when the KV pool cannot back a required allocation."""


class BlockTables:
    """Per-slot logical-block -> physical-page maps, as one int32 array.

    Row ``s`` is slot ``s``'s table; unmapped blocks point at ``TRASH_PAGE``.
    The array view (:meth:`as_array`) is what ``decode_step_paged`` indexes
    with ``pos // page_size`` on device.
    """

    def __init__(self, n_slots: int, blocks_per_seq: int):
        self.blocks_per_seq = blocks_per_seq
        self._table = np.full((n_slots, blocks_per_seq), TRASH_PAGE, np.int32)
        self._held: Dict[int, List[int]] = {s: [] for s in range(n_slots)}

    def held(self, slot: int) -> List[int]:
        """Per-logical-block entries for ``slot``: physical page ids, with
        ``TRASH_PAGE`` placeholders where a leading block was reclaimed
        (:meth:`free_prefix`) -- logical indices never shift."""
        return list(self._held[slot])

    def n_live(self, slot: int) -> int:
        """Physical pages actually held (excludes reclaimed placeholders)."""
        return sum(1 for p in self._held[slot] if p != TRASH_PAGE)

    def n_blocks(self, slot: int) -> int:
        return len(self._held[slot])

    def append(self, slot: int, pages: Sequence[int]) -> None:
        """Map ``pages`` to the next logical blocks of ``slot``."""
        start = len(self._held[slot])
        if start + len(pages) > self.blocks_per_seq:
            raise ValueError(
                f"slot {slot}: {start}+{len(pages)} blocks exceeds "
                f"blocks_per_seq={self.blocks_per_seq}")
        for i, p in enumerate(pages):
            self._table[slot, start + i] = p
        self._held[slot].extend(pages)

    def free_prefix(self, slot: int, upto: int) -> List[int]:
        """Unmap still-held pages of logical blocks ``[0, upto)``.

        Out-of-window reclamation for sliding-window sequences: the freed
        entries become ``TRASH_PAGE`` placeholders in both the table row and
        the held list, so later blocks keep their logical indices (block
        ``i`` must always mean positions ``i*page_size ..``) and gathers of
        the reclaimed range read the all-sentinel trash page.  Returns the
        freed physical pages (caller returns them to the allocator).
        """
        held = self._held[slot]
        freed = []
        for b in range(min(upto, len(held))):
            if held[b] != TRASH_PAGE:
                freed.append(held[b])
                held[b] = TRASH_PAGE
                self._table[slot, b] = TRASH_PAGE
        return freed

    def truncate_to(self, slot: int, n_blocks: int) -> List[int]:
        """Unmap logical blocks ``>= n_blocks`` of ``slot``; return their
        still-held physical pages (caller frees them).

        Speculative-decode rollback: a verify step grows pages out to the
        full draft span up front; after acceptance lands at position
        ``pos``, the scheduler truncates the table back to
        ``pages_needed(pos, page_size)`` blocks -- exactly the blocks
        plain decode would hold at that position -- so over-speculated
        pages return to the pool the same step they were rejected.  The
        tail is the mirror of :meth:`free_prefix`'s head: dropped entries
        shrink the held list (growth re-appends from ``n_blocks``), while
        any reclaimed ``TRASH_PAGE`` placeholders inside the kept prefix
        stay put.  The truncated table entries go back to ``TRASH_PAGE``,
        so gathers of the rolled-back range read the all-sentinel trash
        page; K/V bytes of *kept* pages past ``pos`` are left as-is --
        they carry positions ``> pos`` that the causal mask rejects until
        the stream overwrites them (the rollback invariant,
        docs/speculative.md).
        """
        held = self._held[slot]
        if n_blocks < 0:
            raise ValueError(f"n_blocks must be >= 0, got {n_blocks}")
        freed = [p for p in held[n_blocks:] if p != TRASH_PAGE]
        for b in range(n_blocks, len(held)):
            self._table[slot, b] = TRASH_PAGE
        del held[n_blocks:]
        return freed

    def release(self, slot: int) -> List[int]:
        """Unmap and return the slot's pages (caller frees them; reclaimed
        placeholder blocks are skipped -- their pages were freed already)."""
        pages = [p for p in self._held[slot] if p != TRASH_PAGE]
        self._held[slot] = []
        self._table[slot, :] = TRASH_PAGE
        return pages

    def as_array(self) -> np.ndarray:
        return self._table.copy()


# --------------------------------------------------------- pool operations
def scrub_pages(paged_cache, kinds: Sequence[str], pages: Sequence[int]):
    """Reset ``pos`` of freshly allocated pages to the sentinel.

    Must run between a page leaving the free-list and any gather that could
    see it; K/V bytes are left as-is (masked out by the sentinel positions).
    """
    if not pages:
        return paged_cache
    idx = jnp.asarray(list(pages), jnp.int32)
    out = []
    for kind, entry in zip(kinds, paged_cache):
        if kind == "paged":
            entry = dict(entry)
            entry["pos"] = entry["pos"].at[:, idx].set(POS_SENTINEL)
        out.append(entry)
    return tuple(out)


def write_prefill(paged_cache, dense_cache, kinds: Sequence[str], slot: int,
                  blocks: Sequence[int], page_size: int):
    """Scatter one request's freshly prefilled dense cache into the pool.

    ``dense_cache`` is a batch-1 cache filled by ``LM.prefill``; ``blocks``
    is the slot's physical pages in logical order (must already cover the
    prompt and be scrubbed).  The scatter is driven by the dense cache's own
    ``pos`` plane, so ring-buffer (sliding-window) prefill caches -- which
    hold only the last ``window`` positions -- copy exactly the positions
    they kept.  Every per-slot plane of a ``"paged"`` entry copies the same
    way -- k/v values, ``pos``, and (int8 pools) the ``k_s``/``v_s`` scale
    pages -- so a ``kv_bits=8`` prefill lands in the pool with the exact
    scales the dense quantizer chose.  ``"memory"`` and ``"state"`` entries
    copy whole into batch slot ``slot``.
    """
    blocks_np = np.asarray(list(blocks), np.int32)
    out = []
    for kind, pool, pre in zip(kinds, paged_cache, dense_cache):
        if kind == "paged":
            pos = np.asarray(pre["pos"][0, 0])            # same across R
            j = np.nonzero(pos != POS_SENTINEL)[0]
            p = pos[j]
            phys = jnp.asarray(blocks_np[p // page_size])
            pslot = jnp.asarray(p % page_size)
            j = jnp.asarray(j)
            # dense planes are (R, 1, S[, Hkv, ...]); pool planes are
            # (R, P, ps) for pos and head-major (R, P, Hkv, ps, ...) for
            # k/v/scales, whose [:, phys, :, pslot] scatter takes the
            # written slots first: (n, R, Hkv, ...)
            entry = {}
            for key in pool:
                val = pre[key][:, 0, j].astype(pool[key].dtype)
                if key == "pos":
                    entry[key] = pool[key].at[:, phys, pslot].set(val)
                else:
                    entry[key] = pool[key].at[:, phys, :, pslot].set(
                        jnp.moveaxis(val, 1, 0))
            out.append(entry)
        elif kind == "memory":
            out.append({key: pool[key].at[:, slot].set(
                pre[key][:, 0].astype(pool[key].dtype)) for key in pool})
        else:                                             # "state"
            out.append(jax.tree.map(
                lambda pl, pr: pl.at[:, slot].set(pr[:, 0].astype(pl.dtype)),
                pool, pre))
    return tuple(out)
