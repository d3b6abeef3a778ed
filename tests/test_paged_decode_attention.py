"""Paged decode attention (kernels/paged_decode_attention.py): the exact
kernel body (Pallas interpret mode) against the jnp reference, which the
engine's CPU decode runs and which keeps its tokens bitwise those of the
dense cache.

Shapes keep the benchmark's lane-dense rows (KV*hd = 1024) and page size
(16), with a page budget that splits each sequence into two work items,
so the double-buffered walk crosses blocks and slots."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.paged_decode_attention import pages_per_block, work_list

L, KV, HD, PAGE, S = 2, 16, 64, 16, 512
MP = S // PAGE
B = 3
LAYER = 1


def _inputs(G, lengths, dtype=jnp.float32, sentinel=(), seed=0):
    """Slots 0..B-1 with ``lengths``; page tables are a shuffle of the
    pool (plus a spare page), slot 2's row is all sentinel when its
    length is 0 (a retired slot), and ``sentinel`` lists extra
    (slot, page) entries to blank."""
    rng = np.random.default_rng(seed)
    n_pages = B * MP + 1
    D = KV * HD
    kp = rng.standard_normal((L, n_pages, PAGE, D))
    vp = rng.standard_normal((L, n_pages, PAGE, D))
    table = rng.permutation(n_pages)[:B * MP].reshape(B, MP).astype(np.int32)
    if lengths[2] == 0:
        table[2] = n_pages
    for b, i in sentinel:
        table[b, i] = n_pages
    q = rng.standard_normal((B, KV * G, HD))
    kn = rng.standard_normal((B, KV, HD))
    vn = rng.standard_normal((B, KV, HD))
    cast = lambda x: jnp.asarray(x, dtype)
    return (cast(q), cast(kn), cast(vn), cast(kp), cast(vp),
            jnp.int32(LAYER), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


def _both(args):
    got = ops.paged_decode_attention(*args, impl="interpret")
    want = ops.paged_decode_attention(*args, impl="ref")
    return (np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_budget_splits_the_sequence():
    assert pages_per_block(PAGE, KV * HD, 4, MP) == MP // 2
    # the benchmark widths: qwen1.5-0.5b 32 pages of 32 KB, deepseek-7b
    # 8 pages of 128 KB per work item; never below one page or above mp
    assert pages_per_block(16, 16 * 64, 2, 64) == 32
    assert pages_per_block(16, 32 * 128, 2, 256) == 8
    assert pages_per_block(16, 1 << 20, 2, 256) == 1
    assert pages_per_block(16, 128, 2, 4) == 4


def test_work_list_enumerates_each_slots_blocks():
    slot, blk, total = work_list(jnp.array([0, 17, 33, 16]), 16, 2, 8)
    assert int(total[0]) == 4          # 0, 1 (2 pages), 2 (3 pages), 1
    np.testing.assert_array_equal(np.asarray(slot)[:4], [1, 2, 2, 3])
    np.testing.assert_array_equal(np.asarray(blk)[:4], [0, 0, 1, 0])


@pytest.mark.parametrize("length", [0, 15, 16, 17, S - 1])
@pytest.mark.parametrize("G", [1, 2])
def test_interpret_matches_ref(G, length):
    """Slot 0 at the length under test, slot 1 mid-page, slot 2 retired
    (length 0, sentinel row): every slot's output matches the reference,
    on shuffled tables, at a layer other than 0."""
    got, want = _both(_inputs(G, [length, 40, 0]))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("G", [1, 2])
def test_sentinel_page_inside_the_length_is_skipped(G):
    """A sentinel entry below a slot's length is never fetched and its
    positions are never attended (the reference masks them too)."""
    args = _inputs(G, [300, S - 1, 0], sentinel=[(0, 5), (1, MP - 1)])
    got, want = _both(args)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    blank = _inputs(G, [300, S - 1, 0], sentinel=[(0, 5), (1, MP - 1)],
                    seed=1)
    # the sentinel pages' contents cannot matter: a different pool with
    # the same live pages gives the same output
    table = np.asarray(args[6])
    live = [int(p) for p in table.reshape(-1) if p < B * MP + 1]
    kp, vp = np.asarray(blank[3]).copy(), np.asarray(blank[4]).copy()
    kp[:, live] = np.asarray(args[3])[:, live]
    vp[:, live] = np.asarray(args[4])[:, live]
    again = ops.paged_decode_attention(*args[:3], jnp.asarray(kp),
                                       jnp.asarray(vp), *args[5:],
                                       impl="interpret")
    np.testing.assert_array_equal(np.asarray(again, np.float32), got)


def test_interpret_matches_ref_bf16():
    got, want = _both(_inputs(1, [100, S - 1, 0], dtype=jnp.bfloat16))
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-2)
