"""Pallas TPU kernel: T=1 decode attention read straight from the paged KV pool.

The continuous engine keeps every slot's K/V in a stacked, lane-dense page
pool ``[L, n_pages, page, KV*hd]`` addressed through a per-slot page table
(``serve/pages.py``). This kernel attends one new query per slot against
the slot's cached positions without ever materialising a dense
``[B, max_seq, ...]`` copy:

- the whole pool stays in HBM (``memory_space=ANY``); the layer index, the
  flattened page table and the per-slot lengths ride as scalar-prefetch
  operands, so a page is addressed as ``pool[layer, table[b, i]]`` — one
  contiguous ``[page, KV*hd]`` DMA, no per-layer slice of the pool;
- only pages below ``lengths[b]`` are fetched, ``P`` pages per work item,
  double-buffered across items AND across slots (the work list enumerates
  every (slot, block) pair, so slot b+1's first block streams in while
  slot b's last block is attended); sentinel table entries and slots of
  length 0 issue no DMA;
- an online softmax runs over the fetched blocks, then the new token's own
  ``k``/``v`` row is folded in as the last position (the pool write of
  that row happens after the layer scan, outside the kernel).

Heads stay in lanes: the scores of all KV heads for a block come from one
MXU matmul of the block ``[T, KV*hd]`` against a block-diagonal query
``[KV, KV*hd]`` (row h holds head h's query in lanes h*hd..h*hd+hd-1), and
the values from ``p [KV, T] @ V [T, KV*hd]``, whose diagonal head blocks
are the per-head outputs. No reshape ever splits the lane axis, so
``hd = 64`` costs no relayout. GQA (G = H/KV > 1) runs one such pass per
query group.

``P`` (pages per work item) comes from the page's bytes and a VMEM budget
for one K block, never from a model name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one K block in VMEM; the kernel holds four (K and V, two
# buffers each) plus block-sized temporaries
BLOCK_BYTES = 1 << 20
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


def pages_per_block(page: int, row_elems: int, itemsize: int,
                    max_pages: int) -> int:
    """Pages per work item: as many as fit ``BLOCK_BYTES``, at least one,
    at most a whole sequence."""
    per_page = page * row_elems * itemsize
    return max(1, min(max_pages, BLOCK_BYTES // per_page))


def work_list(lengths, page: int, pp: int, n_items: int):
    """Enumerate the (slot, block) pairs with something to fetch: block j
    of slot b covers its pages j*pp .. j*pp+pp-1 below ``lengths[b]``.
    Returns (item_slot [n_items], item_blk [n_items], total [1])."""
    n_live = (lengths + page - 1) // page
    n_blk = (n_live + pp - 1) // pp
    ends = jnp.cumsum(n_blk)
    w = jnp.arange(n_items, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       lengths.shape[0] - 1).astype(jnp.int32)
    blk = w - (ends - n_blk)[slot]
    return slot, blk.astype(jnp.int32), ends[-1:].astype(jnp.int32)


def _kernel(layer_ref, table_ref, len_ref, slot_ref, blk_ref, total_ref,
            q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
            n_slots, max_pages, page, pp, heads, head_dim, groups, scale):
    n_pool = k_hbm.shape[1]
    T = pp * page
    D = heads * head_dim
    layer = layer_ref[0]
    total = total_ref[0]

    lane = lax.broadcasted_iota(jnp.int32, (heads, D), 1)
    row = lax.broadcasted_iota(jnp.int32, (heads, D), 0)
    # et[h, j] = 1 where lane j belongs to head h
    et = ((lane >= row * head_dim) &
          (lane < row * head_dim + head_dim)).astype(jnp.float32)
    tok = lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def n_live(b):
        return (len_ref[b] + page - 1) // page

    def page_of(b, j, i):
        """(fetch?, pool index) of page i of slot b's block j."""
        pi = j * pp + i
        idx = table_ref[b * max_pages + jnp.minimum(pi, max_pages - 1)]
        ok = (pi < n_live(b)) & (idx < n_pool)
        return ok, jnp.minimum(idx, n_pool - 1)

    def copies(w, buf):
        b, j = slot_ref[w], blk_ref[w]
        for i in range(pp):
            ok, idx = page_of(b, j, i)
            dst = pl.ds(i * page, page)
            yield i, ok, (
                pltpu.make_async_copy(k_hbm.at[layer, idx],
                                      kbuf.at[buf, dst], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, idx],
                                      vbuf.at[buf, dst], sems.at[1, buf]))

    def start(w, buf):
        for _, ok, cps in copies(w, buf):
            @pl.when(ok)
            def _():
                for c in cps:
                    c.start()

    def wait(w, buf):
        """Wait for item w's pages; a page not fetched gets zero values
        (its stale rows would meet p = 0, and 0 * NaN is NaN)."""
        for i, ok, cps in copies(w, buf):
            @pl.when(ok)
            def _():
                for c in cps:
                    c.wait()

            @pl.when(jnp.logical_not(ok))
            def _():
                vbuf[buf, pl.ds(i * page, page), :] = jnp.zeros(
                    (page, D), vbuf.dtype)

    def reset():
        m_ref[...] = jnp.full(m_ref.shape, MASK, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def query(b, g):
        """Block-diagonal query of group g: row h = head (h, g)'s q."""
        qrow = q_ref[b, pl.ds(g, 1), :]                      # [1, D]
        return (qrow.astype(jnp.float32) * et).astype(qrow.dtype)

    def update(g, s, valid, v):
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    def finish(b):
        """Fold the new token in as the last position; write slot b."""
        kn = kn_ref[b].astype(jnp.float32)                   # [1, D]
        vn = vn_ref[b]
        for g in range(groups):
            qt = query(b, g).astype(jnp.float32)
            s = jnp.sum(qt * kn, axis=1, keepdims=True) * scale   # [KV, 1]
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, s)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            lsum = alpha * l_ref[g] + p
            acc = alpha * acc_ref[g] + \
                p.astype(vn.dtype).astype(jnp.float32) * \
                vn.astype(jnp.float32)
            num = jnp.sum(acc * et, axis=0, keepdims=True)      # [1, D]
            den = jnp.sum(lsum * et, axis=0, keepdims=True)
            o_ref[b, pl.ds(g, 1), :] = (num / den).astype(o_ref.dtype)

    def item(w, _):
        buf = w % 2
        b, j = slot_ref[w], blk_ref[w]

        @pl.when(w + 1 < total)
        def _():
            start(w + 1, 1 - buf)

        @pl.when(j == 0)
        def _():
            reset()

        wait(w, buf)
        pos = j * T + tok
        valid = pos < len_ref[b]
        for i in range(pp):
            ok, _ = page_of(b, j, i)
            in_page = (tok >= i * page) & (tok < i * page + page)
            valid = valid & jnp.logical_not(in_page & jnp.logical_not(ok))
        k = kbuf[buf]                                        # [T, D]
        v = vbuf[buf]
        for g in range(groups):
            s = lax.dot_general(query(b, g), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, MASK)                    # [KV, T]
            update(g, s, valid, v)

        last = (j + 1) * pp >= n_live(b)

        @pl.when(last)
        def _():
            finish(b)
        return 0

    @pl.when(total > 0)
    def _():
        start(0, 0)

    lax.fori_loop(0, total, item, 0)

    def idle_slot(b, _):
        @pl.when(len_ref[b] == 0)
        def _():
            reset()
            finish(b)
        return 0

    lax.fori_loop(0, n_slots, idle_slot, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, layer, table,
                           lengths, *, interpret: bool = False):
    """q [B, H, hd], k_new/v_new [B, KV, hd] (the new token's rows),
    pools [L, n_pages, page, KV*hd], layer int32 scalar, table [B, mp]
    int32 (``n_pages`` = sentinel), lengths [B] int32 (cached positions
    slot b attends; its new token sits at position ``lengths[b]``)
    -> [B, H, hd]."""
    B, H, hd = q.shape
    KV = k_new.shape[1]
    G = H // KV
    D = KV * hd
    _, n_pages, page, Dp = k_pool.shape
    assert Dp == D, (k_pool.shape, KV, hd)
    mp = table.shape[1]
    pp = pages_per_block(page, D, k_pool.dtype.itemsize, mp)
    n_items = B * (-(-mp // pp))
    slot, blk, total = work_list(lengths.astype(jnp.int32), page, pp,
                                 n_items)
    # q rows per group: qf[b, g, h*hd + d] = q[b, h*G + g, d]
    qf = q.reshape(B, KV, G, hd).transpose(0, 2, 1, 3).reshape(B, G, D)
    kn = k_new.reshape(B, 1, D).astype(k_pool.dtype)
    vn = v_new.reshape(B, 1, D).astype(v_pool.dtype)
    kernel = functools.partial(
        _kernel, n_slots=B, max_pages=mp, page=page, pp=pp, heads=KV,
        head_dim=hd, groups=G, scale=float(1.0 / (hd ** 0.5)))
    full = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[full((B, G, D)), full((B, 1, D)), full((B, 1, D)),
                      any_, any_],
            out_specs=full((B, G, D)),
            scratch_shapes=[
                pltpu.VMEM((2, pp * page, D), k_pool.dtype),
                pltpu.VMEM((2, pp * page, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((G, KV, 1), jnp.float32),
                pltpu.VMEM((G, KV, 1), jnp.float32),
                pltpu.VMEM((G, KV, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, G, D), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      slot, blk, total, qf, kn, vn, k_pool, v_pool)
    return out.reshape(B, G, KV, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)
