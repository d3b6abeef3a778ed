"""Block-paged memory pool for the continuous-batching serving engine.

The windowed engine allocates its KV/recurrent cache as one dense
``[lead, n_slots, S, ...]`` block — slot count times max length, whether a
request needs it or not. The continuous engine instead backs every
sequence-axis cache leaf with a pool of fixed-size PAGES
(``[lead, n_pages, page_size, ...]``) plus a per-slot page table, vLLM
style, and applies the same idea to the per-slot adapter state (one mask
"page" = one slot's aggregated Â/B̂ record). Slot count is then decoupled
from max-length allocation: a request holds exactly
``ceil(len/page_size)`` pages, pages free the moment the request retires,
and the scheduler preempts-to-pending when the pool runs dry.

Two layers live here:

- ``PageAllocator`` — HOST bookkeeping: per-color free lists (colors map
  pages to data-mesh shards so a slot's pages stay on its shard), owner
  tracking that makes double-booking structurally impossible, OOM raised
  BEFORE any state mutates, and ``compact()`` for pool-shrink remaps.
- pure jit-friendly DEVICE helpers — ``writeback`` (scatter each slot's
  one new K/V row into its page, dropped for slots whose pages may since
  be re-owned; ``rows_at`` takes those rows out of a dense-view step's
  cache), ``dense_view`` (page-table gather back to the dense
  ``[lead, B, S, ...]`` layout the model's cached attention understands,
  for the steps that cannot read pages in place — speculation, sliding
  windows, hydrated prefix rows, f8 caches, the decode megakernel; the
  in-place T=1 decode reads pages through the table in its attention
  kernel, ``ops.paged_decode_attention``), ``writeback_span``,
  ``insert_group`` (batched prefill insert), and the per-slot
  extract/restore pair used by preempt/resume swaps.

Paged leaves are stored LANE-DENSE: ``[lead, n_pages, page, KV*hd]``, so
one layer's page is one contiguous DMA and a 64-wide head dim never sits
alone in the minor (lane) dimension. A dense ``[.., KV, hd]`` row and a
pool row differ by a reshape only.

The sentinel page index is ``n_pages`` (one past the pool): gathers clamp
it to a junk page that attention masks out (positions >= kv_valid), and
scatters use ``mode="drop"`` so a sentinel write never lands — a freed
slot can never corrupt a page it no longer owns.

Recurrent archs (rwkv/mamba) have NO sequence-axis leaves — their state is
O(1) per slot and stays slot-resident. All helpers degenerate gracefully
(the page table is a [n_slots, 1] sentinel column, dense_view is the
identity), so the continuous engine runs unchanged on them: it gets the
mid-stream admission and mask-entry pooling wins without KV paging.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from repro.utils import map_with_path, map_with_paths

# cache leaves with a sequence axis (dim 2 of [lead, B, S, ...]) — the same
# name convention distributed/sharding.cache_specs keys on. Everything else
# (recurrent conv/ssd/wkv state, token-shift carries) has no length axis
# and stays slot-resident.
PAGED_LEAVES = ("k", "v", "attn_k", "attn_v")


def leaf_is_paged(path: str) -> bool:
    return path.rsplit("/", 1)[-1] in PAGED_LEAVES


class PageOOM(RuntimeError):
    """The pool cannot satisfy an allocation. Raised BEFORE any allocator
    state mutates, so a failed alloc never leaks or double-books pages —
    the engine's response is preempt-to-pending (or deferring admission),
    never a corrupted table."""


class PageAllocator:
    """Host-side free-list allocator over ``n_pages`` fixed-size pages.

    ``n_colors`` partitions the pool into contiguous color classes (color
    of page p = ``p * n_colors // n_pages``). ``alloc(color=...)`` prefers
    pages of the caller's color — the engine colors slots by their
    data-mesh shard so a slot's pages land on the shard that owns the
    slot — and falls back to any free page (correctness never depends on
    affinity). Every page tracks its owner; freeing a page you don't own,
    double-freeing, or double-booking raises instead of corrupting.
    """

    def __init__(self, n_pages: int, *, n_colors: int = 1):
        if n_pages <= 0:
            raise ValueError(f"n_pages must be positive, got {n_pages}")
        if not (1 <= n_colors <= n_pages):
            raise ValueError(f"n_colors {n_colors} not in [1, {n_pages}]")
        self.n_pages = n_pages
        self.n_colors = n_colors
        self._owner: Dict[int, object] = {}           # page -> owner
        self._pages_of: Dict[object, List[int]] = {}  # owner -> pages
        # LIFO free stacks per color: recently freed pages are re-used
        # first (their lines are warm)
        self._free: List[List[int]] = [[] for _ in range(n_colors)]
        for p in range(n_pages - 1, -1, -1):
            self._free[self.color_of(p)].append(p)
        self.allocs = 0
        self.frees = 0
        self.oom_events = 0
        self.high_water = 0

    # ------------------------------------------------------------------ query
    def color_of(self, page: int) -> int:
        return page * self.n_colors // self.n_pages

    def used(self) -> int:
        return len(self._owner)

    def free_count(self) -> int:
        return self.n_pages - len(self._owner)

    def owner_of(self, page: int):
        return self._owner.get(page)

    def pages_of(self, owner) -> List[int]:
        return list(self._pages_of.get(owner, ()))

    def owners(self) -> List:
        return list(self._pages_of)

    # ------------------------------------------------------------ alloc/free
    def alloc(self, n: int, owner, *, color: int = 0) -> List[int]:
        """Allocate ``n`` pages for ``owner`` (color-preferring). Raises
        ``PageOOM`` — with the allocator untouched — if fewer than ``n``
        pages are free."""
        if n < 0:
            raise ValueError(f"alloc of {n} pages")
        if n > self.free_count():
            self.oom_events += 1
            raise PageOOM(f"need {n} pages, {self.free_count()} free "
                          f"of {self.n_pages}")
        got: List[int] = []
        order = [color % self.n_colors] + \
            [c for c in range(self.n_colors) if c != color % self.n_colors]
        for c in order:
            while self._free[c] and len(got) < n:
                got.append(self._free[c].pop())
            if len(got) == n:
                break
        assert len(got) == n, "free_count said yes but stacks were short"
        for p in got:
            assert p not in self._owner, f"double-booked page {p}"
            self._owner[p] = owner
        self._pages_of.setdefault(owner, []).extend(got)
        self.allocs += n
        self.high_water = max(self.high_water, self.used())
        return got

    def free(self, pages: List[int], owner) -> None:
        """Return ``pages`` to the pool; every page must belong to
        ``owner`` (ownership is validated BEFORE any page is freed)."""
        for p in pages:
            if self._owner.get(p) != owner:
                raise ValueError(
                    f"page {p} owned by {self._owner.get(p)!r}, "
                    f"not {owner!r} (double free / foreign free)")
        own = self._pages_of.get(owner, [])
        for p in pages:
            del self._owner[p]
            own.remove(p)
            self._free[self.color_of(p)].append(p)
        if owner in self._pages_of and not self._pages_of[owner]:
            del self._pages_of[owner]
        self.frees += len(pages)

    def free_owner(self, owner) -> List[int]:
        """Free every page ``owner`` holds; returns the freed list."""
        pages = self.pages_of(owner)
        if pages:
            self.free(pages, owner)
        return pages

    # -------------------------------------------------------------- compact
    def compact(self) -> Dict[int, int]:
        """Re-pack live pages onto the lowest indices (owner assignment and
        per-owner page ORDER preserved) and rebuild the free lists above
        them. Returns the ``{old_page: new_page}`` remap for the device
        side (`apply_remap`) and any page tables. Used by pool shrinks /
        elastic resizes; an identity remap comes back when already packed."""
        live = sorted(self._owner)
        remap = {old: new for new, old in enumerate(live)}
        self._owner = {remap[p]: o for p, o in self._owner.items()}
        self._pages_of = {o: [remap[p] for p in ps]
                          for o, ps in self._pages_of.items()}
        self._free = [[] for _ in range(self.n_colors)]
        for p in range(self.n_pages - 1, len(live) - 1, -1):
            self._free[self.color_of(p)].append(p)
        return remap

    def check(self) -> None:
        """Invariant audit (tests): owned ∪ free is exactly the pool, with
        no page in both and no duplicates anywhere."""
        free_flat = [p for stack in self._free for p in stack]
        assert len(free_flat) == len(set(free_flat)), "duplicate free page"
        owned = set(self._owner)
        assert not (owned & set(free_flat)), "page both owned and free"
        assert owned | set(free_flat) == set(range(self.n_pages)), \
            "pages leaked from the pool"
        by_owner = [p for ps in self._pages_of.values() for p in ps]
        assert sorted(by_owner) == sorted(owned), "owner index out of sync"

    def reset_stats(self) -> None:
        """Zero the flow counters (engine.reset_stats()); ownership and
        free lists are untouched. `high_water` restarts from the CURRENT
        occupancy — live pages are real occupancy, not history."""
        self.allocs = 0
        self.frees = 0
        self.oom_events = 0
        self.high_water = self.used()

    def stats(self) -> dict:
        return {"n_pages": self.n_pages, "used": self.used(),
                "free": self.free_count(), "high_water": self.high_water,
                "allocs": self.allocs, "frees": self.frees,
                "oom_events": self.oom_events}


# ----------------------------------------------------------------------------
# Device-side helpers (pure functions; call them inside jit)
# ----------------------------------------------------------------------------

def pages_needed(upto_len: int, page_size: int) -> int:
    """Pages covering write positions 0..upto_len-1."""
    return -(-int(upto_len) // page_size)


def paged_seq_len(cache_template) -> int:
    """The (single) sequence length of the template's paged leaves, or 0
    when the arch has none (pure recurrent state)."""
    found = set()
    map_with_path(lambda p, x: found.add(x.shape[2])
                  if leaf_is_paged(p) else None, cache_template)
    assert len(found) <= 1, f"mixed sequence lengths {found}"
    return found.pop() if found else 0


def make_paged_cache(cache_template, n_pages: int, page_size: int,
                     n_slots: int) -> dict:
    """Build the paged cache from a dense-cache template (arrays or
    ShapeDtypeStructs): paged leaves ``[lead, B, S, KV, hd]`` become
    lane-dense ``[lead, n_pages, page, KV*hd]`` pools, resident leaves
    keep their dense shapes with B = n_slots, plus the sentinel-filled
    page table. Returns ``{"data": tree, "table": [n_slots, S/page]
    int32}``."""
    S = paged_seq_len(cache_template)
    assert S % page_size == 0, (S, page_size)

    def one(path, leaf):
        if leaf_is_paged(path):
            row = int(np.prod(leaf.shape[3:]))
            return jnp.zeros((leaf.shape[0], n_pages, page_size, row),
                             leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    mp = max(S // page_size, 1)
    table = jnp.full((n_slots, mp), n_pages, jnp.int32)
    return {"data": map_with_path(one, cache_template), "table": table}


# the paged decode's gather and scatter run under these scopes, which name
# their ops in the compiled step's metadata and in device traces
@jax.named_scope("kv_dense_view")
def dense_view(data, table, page_size: int, row_shape):
    """Gather the paged leaves back to the dense ``[lead, B, S, *row_shape]``
    layout through the page table (sentinel entries clamp to a junk page
    that attention masks out — every junk position is >= kv_valid);
    ``row_shape`` (the model's ``(KV, hd)``) unfolds the lane-dense row.
    Resident leaves pass through, so the result is exactly the cache tree
    ``models.forward`` already takes: paged decode stays ONE compiled
    program with bitwise-dense numerics."""
    B, mp = table.shape

    def one(path, leaf):
        if not leaf_is_paged(path):
            return leaf
        v = jnp.take(leaf, table, axis=1, mode="clip")
        return v.reshape((leaf.shape[0], B, mp * page_size)
                         + tuple(row_shape))

    return map_with_path(one, data)


@jax.named_scope("kv_writeback")
def rows_at(dense_new, lengths):
    """The one position ``lengths[b]`` of every paged leaf of a dense-view
    step's new cache ``[lead, B, S, ...]`` -> ``[lead, B, ...]``, the rows
    ``writeback`` takes; resident leaves pass through."""
    def one(path, leaf):
        if not leaf_is_paged(path):
            return leaf
        idx = lengths.reshape((1, -1) + (1,) * (leaf.ndim - 2))
        return jnp.squeeze(jnp.take_along_axis(leaf, idx, axis=2), axis=2)

    return map_with_path(one, dense_new)


@jax.named_scope("kv_writeback")
def writeback(data, rows, table, lengths, active, page_size: int):
    """Write each slot's ONE new position (``lengths[b]``) of every paged
    leaf into its page: ``rows`` holds, per paged leaf, the step's new
    rows ``[lead, B, ...]`` (``rows_at`` takes them out of a dense-view
    step's cache); resident leaves take the model's new value wholesale.
    Inactive slots route to the sentinel and are DROPPED — their
    pad-compute write must never land in a page that may since belong to
    another slot (a retired slot's table row is already sentinel, so this
    is belt and braces)."""
    B = table.shape[0]
    pidx = table[jnp.arange(B), lengths // page_size]
    off = lengths % page_size

    def one(path, pool, leaf):
        if not leaf_is_paged(path):
            return leaf
        lead, n_pages = pool.shape[:2]
        # scatter into the pool's [lead * n_pages * page, row] view, one
        # index per (layer, slot): the indexed axis leads, so the write
        # needs no relayout of the pool around it
        flat = (jnp.arange(lead)[:, None] * n_pages + pidx) * page_size + off
        keep = active & (pidx < n_pages)
        flat = jnp.where(keep, flat, lead * n_pages * page_size)
        view = pool.reshape((lead * n_pages * page_size,) + pool.shape[3:])
        row = leaf.reshape((lead * B,) + pool.shape[3:]).astype(pool.dtype)
        return view.at[flat.reshape(-1)].set(row, mode="drop").reshape(
            pool.shape)

    return map_with_paths(one, data, rows)


@jax.named_scope("kv_writeback")
def writeback_span(data, dense_new, table, lengths, span: int, active,
                   page_size: int):
    """Scatter ``span`` consecutive written positions per slot
    (``lengths[b] .. lengths[b]+span-1``) back into their pages — the
    speculative round's writeback: one draft+verify round writes gamma+1
    positions at once. Positions past the slot's allocated pages (or past
    S) route to the sentinel and are DROPPED; only positions the engine
    can later COMMIT are guaranteed page-backed (the window sizing does
    that), so a dropped overhang write only costs acceptance, never
    correctness — the next round rewrites those positions anyway."""
    B, mp = table.shape
    pos = lengths[:, None] + jnp.arange(span)             # [B, span]
    page_of = pos // page_size
    in_range = active[:, None] & (page_of < mp)
    pidx_owned = jnp.take_along_axis(table, jnp.clip(page_of, 0, mp - 1),
                                     axis=1)              # [B, span]
    off = pos % page_size

    def one(path, pool, new):
        if not leaf_is_paged(path):
            return new
        idx = pos.reshape((1, B, span) + (1,) * (new.ndim - 3))
        rows = jnp.take_along_axis(new, jnp.clip(idx, 0, new.shape[2] - 1),
                                   axis=2)
        rows = rows.reshape(rows.shape[:3] + pool.shape[3:]).astype(
            pool.dtype)
        pidx = jnp.where(in_range, pidx_owned, jnp.int32(pool.shape[1]))
        return pool.at[:, pidx, off].set(rows, mode="drop")

    return map_with_paths(one, data, dense_new)


def insert_group(data, mini, slots, table, page_size: int):
    """Batched prefill insert for one length-bucket group: the stacked
    mini-cache ``[lead, Bp, S, ...]`` chunks into pages and scatters
    through the group's table rows (chunks addressed by sentinel entries —
    pages past a request's current allocation — are dropped; decode fills
    them lazily as the sequence grows). Resident leaves scatter by slot
    index, exactly like the dense engine's insert."""
    B = slots.shape[0]
    pidx = table[slots]                                   # [B, mp]
    mp = pidx.shape[1]

    def one(path, big, small):
        if not leaf_is_paged(path):
            return big.at[:, slots].set(small[:, :B].astype(big.dtype))
        lead, rest = big.shape[0], tuple(big.shape[3:])
        rows = small[:, :B].reshape((lead, B, mp, page_size) + rest)
        return big.at[:, pidx].set(rows.astype(big.dtype), mode="drop")

    return map_with_paths(one, data, mini)


def extract_slot(data, table_row, slot):
    """Gather ONE slot's cache for a preempt-to-host swap: paged leaves as
    ``[lead, mp, page, ...]`` page rows (sentinel entries clamp to junk the
    resume's sentinel-drop then ignores), resident leaves as their
    ``[lead, ...]`` slice."""
    def one(path, leaf):
        if leaf_is_paged(path):
            return jnp.take(leaf, table_row, axis=1, mode="clip")
        return leaf[:, slot]

    return map_with_path(one, data)


def restore_slot(data, rows, table_row, slot):
    """Scatter a preempted slot's swapped cache back in (the resume half of
    ``extract_slot``; sentinel table entries drop their padded rows). The
    new table_row need not equal the one extracted from — pages are
    position-addressed through the table, never by identity."""
    def one(path, big, saved):
        if leaf_is_paged(path):
            return big.at[:, table_row].set(saved.astype(big.dtype),
                                            mode="drop")
        return big.at[:, slot].set(saved.astype(big.dtype))

    return map_with_paths(one, data, rows)


def apply_remap(data, table_h: np.ndarray, remap: Dict[int, int],
                n_pages: int):
    """Apply an allocator ``compact()`` remap to the device pools and the
    HOST page-table mirror: page contents move to their new indices (a
    gather by the inverse permutation), table entries follow through a
    lookup table, sentinels stay sentinel. Returns (data, new_table_h)."""
    perm = np.arange(n_pages)
    for old, new in remap.items():
        perm[old] = new
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n_pages)

    def one(path, leaf):
        if leaf_is_paged(path):
            return jnp.take(leaf, jnp.asarray(inv), axis=1)
        return leaf

    lut = np.concatenate([perm, [n_pages]]).astype(table_h.dtype)
    return map_with_path(one, data), lut[table_h]
