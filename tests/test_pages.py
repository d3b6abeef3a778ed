"""Page allocator + device paging helpers (serve/pages.py).

The allocator properties are checked with hypothesis when it is installed
(CI installs it); without it the same property body runs over seeded
numpy-random op sequences, so the invariants are exercised either way.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.serve.pages import (PageAllocator, PageOOM, apply_remap,
                               dense_view, insert_group, make_paged_cache,
                               pages_needed, rows_at, writeback)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------- properties
def _snapshot(alloc):
    return (dict(alloc._owner),
            {o: list(ps) for o, ps in alloc._pages_of.items()},
            [list(s) for s in alloc._free])


def run_op_sequence(ops, n_pages=16, n_colors=2):
    """Interpret coded (op, a, b) triples against the allocator and a
    mirror model; after EVERY op the allocator's own audit must pass, no
    page may be double-booked, and a failed alloc must leave the state
    bitwise-untouched."""
    alloc = PageAllocator(n_pages, n_colors=n_colors)
    mirror = {}  # owner -> [pages]
    for code, a, b in ops:
        op = code % 4
        if op == 0:                                   # alloc
            owner, n = a % 6, b % (n_pages + 2)       # may exceed the pool
            before = _snapshot(alloc)
            try:
                got = alloc.alloc(n, owner, color=a % n_colors)
            except PageOOM:
                assert n > alloc.free_count()
                assert _snapshot(alloc) == before, \
                    "OOM mutated allocator state"
            else:
                booked = {p for ps in mirror.values() for p in ps}
                assert not (set(got) & booked), f"double-booked {got}"
                assert len(set(got)) == len(got) == n
                mirror.setdefault(owner, []).extend(got)
        elif op == 1:                                 # partial free
            owner = a % 6
            if mirror.get(owner):
                k = 1 + b % len(mirror[owner])
                alloc.free(mirror[owner][:k], owner)
                del mirror[owner][:k]
                if not mirror[owner]:
                    del mirror[owner]
        elif op == 2:                                 # free_owner
            owner = a % 6
            freed = alloc.free_owner(owner)
            assert sorted(freed) == sorted(mirror.pop(owner, []))
        else:                                         # compact
            remap = alloc.compact()
            mirror = {o: [remap[p] for p in ps]
                      for o, ps in mirror.items()}
        alloc.check()
        for owner, ps in mirror.items():
            assert alloc.pages_of(owner) == ps, "owner pages drifted"
    # every owner's pages are reusable after a full teardown
    for owner in list(mirror):
        alloc.free(mirror.pop(owner), owner)
    alloc.check()
    assert alloc.free_count() == n_pages
    alloc.alloc(n_pages, "reuser")                    # pool fully reusable
    alloc.check()


if HAVE_HYPOTHESIS:
    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 63),
                              st.integers(0, 63)), max_size=60))
    def test_allocator_properties(ops):
        run_op_sequence(ops)
else:
    def test_allocator_properties():
        rng = np.random.default_rng(0)
        for _ in range(200):
            n = int(rng.integers(0, 61))
            ops = rng.integers(0, 64, size=(n, 3))
            ops[:, 0] %= 4
            run_op_sequence([tuple(map(int, row)) for row in ops])


# ------------------------------------------------------------------- directed
def test_oom_raises_before_any_mutation():
    alloc = PageAllocator(4)
    alloc.alloc(3, "a")
    before = _snapshot(alloc)
    with pytest.raises(PageOOM):
        alloc.alloc(2, "b")
    assert _snapshot(alloc) == before
    assert alloc.stats()["oom_events"] == 1
    # the remaining page is still cleanly allocatable
    assert len(alloc.alloc(1, "b")) == 1
    alloc.check()


def test_foreign_and_double_free_raise():
    alloc = PageAllocator(4)
    pages = alloc.alloc(2, "a")
    with pytest.raises(ValueError):
        alloc.free(pages, "b")                  # foreign free
    alloc.free(pages, "a")
    with pytest.raises(ValueError):
        alloc.free(pages, "a")                  # double free
    alloc.check()


def test_freed_pages_reusable():
    alloc = PageAllocator(8)
    alloc.alloc(8, "a")
    with pytest.raises(PageOOM):
        alloc.alloc(1, "b")
    alloc.free_owner("a")
    assert sorted(alloc.alloc(8, "b")) == list(range(8))
    alloc.check()


def test_color_affinity_prefers_own_shard():
    alloc = PageAllocator(8, n_colors=2)       # colors: pages 0-3 / 4-7
    got = alloc.alloc(2, "a", color=1)
    assert all(alloc.color_of(p) == 1 for p in got)
    # exhausting the preferred color falls back without failing
    got2 = alloc.alloc(4, "b", color=1)
    assert any(alloc.color_of(p) == 0 for p in got2)
    alloc.check()


def test_compact_packs_low_and_preserves_order():
    alloc = PageAllocator(8)
    a = alloc.alloc(3, "a")
    b = alloc.alloc(3, "b")
    alloc.free_owner("a")
    remap = alloc.compact()
    assert sorted(remap) == sorted(b)
    assert alloc.pages_of("b") == [remap[p] for p in b]  # order preserved
    assert set(alloc.pages_of("b")) == set(range(3))     # packed low
    alloc.check()
    assert alloc.compact() == {0: 0, 1: 1, 2: 2}         # now identity


def test_apply_remap_preserves_dense_view():
    """compact() + apply_remap move page CONTENTS and table entries
    together: the dense view through the table is bitwise unchanged."""
    n_pages, page = 6, 4
    alloc = PageAllocator(n_pages)
    a = alloc.alloc(2, "a")
    b = alloc.alloc(2, "b")
    alloc.free_owner("a")
    pool = {"k": jnp.arange(n_pages * page * 6, dtype=jnp.float32)
            .reshape(1, n_pages, page, 6)}    # lane-dense rows of 2 x 3
    table_h = np.full((2, 2), n_pages, np.int32)
    table_h[0] = b                             # slot 0 owns b's pages
    before = np.asarray(
        dense_view(pool, jnp.asarray(table_h), page, (2, 3))["k"])
    assert before.shape == (1, 2, 2 * page, 2, 3)
    remap = alloc.compact()
    pool2, table2 = apply_remap(pool, table_h, remap, n_pages)
    after = np.asarray(
        dense_view(pool2, jnp.asarray(table2), page, (2, 3))["k"])
    np.testing.assert_array_equal(before, after)
    assert (table2[1] == n_pages).all()        # sentinels stay sentinel


def test_writeback_drops_inactive_and_sentinel():
    """An inactive slot's pad-compute write and a sentinel table entry must
    both be DROPPED — a freed slot can never touch a re-owned page."""
    n_pages, page, B, S = 2, 4, 2, 8
    pool = {"k": jnp.zeros((1, n_pages, page, 2))}
    table = jnp.full((B, S // page), n_pages, jnp.int32)
    table = table.at[0, 0].set(0)              # slot 0 owns page 0 only
    dense = {"k": jnp.ones((1, B, S, 1, 2))}
    lengths = jnp.array([1, 1], jnp.int32)
    out = writeback(pool, rows_at(dense, lengths), table, lengths,
                    jnp.array([True, False]), page)
    got = np.asarray(out["k"])
    assert (got[0, 0, 1] == 1.0).all()         # active slot's write landed
    assert got.sum() == 2.0                    # nothing else was touched


def test_writeback_of_rows_lands_per_layer_and_drops_sentinel():
    """The in-place decode's one scatter of the step's new rows (no dense
    cache): layer l's row of slot b lands at
    (layer l, table[b, len // page], len % page); an inactive slot and an
    active slot whose page is a sentinel write nothing — not even into
    another layer's pages."""
    L, n_pages, page, B = 2, 3, 4, 3
    pool = {"k": jnp.zeros((L, n_pages, page, 2))}
    table = jnp.array([[2, 0], [1, n_pages], [n_pages, n_pages]], jnp.int32)
    lengths = jnp.array([5, 6, 0], jnp.int32)
    active = jnp.array([True, True, False])
    rows = {"k": (jnp.arange(L * B, dtype=jnp.float32) + 1)
            .reshape(L, B, 1, 1) * jnp.ones((L, B, 1, 2))}
    got = np.asarray(writeback(pool, rows, table, lengths, active,
                               page)["k"])
    want = np.zeros((L, n_pages, page, 2), np.float32)
    for l in range(L):
        want[l, 0, 1] = l * B + 1              # slot 0: page table[0, 1]
    np.testing.assert_array_equal(got, want)   # slot 1: sentinel page


def test_lane_dense_pool_round_trips_a_prefill():
    """Pools are lane-dense [lead, n_pages, page, KV*hd]; a prefill's
    dense rows go in by a reshape and come back out of the dense view
    unchanged, on whatever pages the table names."""
    lead, B, S, KV, hd, page = 2, 2, 8, 2, 3, 4
    template = {"k": jnp.zeros((lead, B, S, KV, hd)),
                "conv": jnp.zeros((lead, B, 5))}
    cache = make_paged_cache(template, 5, page, B)
    assert cache["data"]["k"].shape == (lead, 5, page, KV * hd)
    assert cache["data"]["conv"].shape == (lead, B, 5)
    table = jnp.array([[3, 1], [0, 4]], jnp.int32)
    mini = {"k": jnp.arange(lead * B * S * KV * hd, dtype=jnp.float32)
            .reshape(lead, B, S, KV, hd),
            "conv": jnp.ones((lead, B, 5))}
    data = insert_group(cache["data"], mini, jnp.arange(B), table, page)
    back = dense_view(data, table, page, (KV, hd))
    np.testing.assert_array_equal(np.asarray(back["k"]),
                                  np.asarray(mini["k"]))
    np.testing.assert_array_equal(np.asarray(back["conv"]), 1.0)


def test_pages_needed():
    assert pages_needed(0, 16) == 0
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
