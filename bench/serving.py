"""Serving cells: the program's continuous ``ServeEngine`` with its profile
store, driven through ``submit`` + ``run_until_drained(max_steps=
sync_every)``, open loop (requests due at fixed times) or from a backlog
(the queue never empties).

Set-up makes the weights and profiles from the seed, builds the engine,
runs every shape the cell's traffic can reach once (prefill bucket x row
count, admission wave size x prefill group size, aggregation count, sync
window width), then fills the profile cache with the mix's hottest
profiles, as steady traffic would leave it. The window then measures; the
harness's own spans (``admit``, ``decode_window``, ``await_arrival``,
``drain``) let a traced run attribute device idle time to host work.
"""
from __future__ import annotations

import gc
import time
from typing import List, Optional

import numpy as np

from bench import check, device, model, tracing, traffic
from bench.tracing import span

DRAIN_S = 60.0          # how long past the close a due request may take
TRACE_S = 8.0           # traced part of the window, from its start
WARM_NEW = 2            # tokens per warm-up request: prefill + one step


class Record:
    """Host-side view of one request: when it was due, submitted, first
    and last seen with tokens."""

    __slots__ = ("arrival", "req", "submitted_s", "first_s", "last_s",
                 "seen", "seen_first")

    def __init__(self, arrival, req, submitted_s):
        self.arrival, self.req, self.submitted_s = arrival, req, submitted_s
        self.first_s: Optional[float] = None
        self.last_s: Optional[float] = None
        self.seen = 0
        self.seen_first = 0      # tokens already there when first seen


class Book:
    """Every request of the run and what the harness saw of it."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.records: List[Record] = []
        self.live: List[Record] = []
        self.window_tokens = 0
        self.ctx_tokens = 0        # sum of context lengths of window tokens
        self.depth = []            # (seconds, queued requests) per call
        self.by_uid = {}

    def add(self, rec: Record) -> None:
        self.records.append(rec)
        self.live.append(rec)
        self.by_uid[rec.arrival.uid] = rec

    def see(self, rec: Record, now: float) -> None:
        """Note the tokens of one request the harness can see at ``now``."""
        n = len(rec.req.generated)
        if n > rec.seen:
            if rec.first_s is None:
                rec.first_s = now
                rec.seen_first = n
            rec.last_s = now
            if now <= self.seconds:
                self.window_tokens += n - rec.seen
                # context each new token was decoded against
                p = len(rec.req.prompt)
                self.ctx_tokens += sum(p + i for i in range(rec.seen, n))
            rec.seen = n

    def observe(self, now: float) -> None:
        keep = []
        for rec in self.live:
            self.see(rec, now)
            if not rec.req.done:
                keep.append(rec)
        self.live = keep

    def admitted(self, reqs, now: float) -> None:
        """An admission returned: each admitted request's first token is on
        the host now (the prefill's greedy token), whatever the decode
        window that follows it."""
        for r in reqs:
            rec = self.by_uid.get(r.uid)
            if rec is not None:
                self.see(rec, now)

    def unfinished(self, due_by: float) -> bool:
        return any(r.arrival.due_s <= due_by and not r.req.done
                   for r in self.records)


class Hooks:
    """The harness's span around each admission, a log of what each
    admission did (``engine.last_admission``), and ``on_admit(reqs, t)``
    with the admitted requests as soon as their first tokens exist."""

    def __init__(self, eng):
        self.log = []
        self.on_admit = None
        orig = eng.admit_many

        def admit_many(reqs):
            cached = {int(r.profile_id): int(r.profile_id)
                      in eng.profile_cache for r in reqs}
            t = time.perf_counter()
            with span("admit"):
                n = orig(reqs)
            if n and self.on_admit is not None:
                self.on_admit(reqs[:n], time.perf_counter())
            if n and eng.last_admission is not None:
                rec = dict(eng.last_admission)
                rec["prompt_lengths"] = [len(r.prompt) for r in reqs[:n]]
                # the distinct profiles of the admitted requests that the
                # cache did not hold: the aggregation the wave needed
                # (``aggregated_profiles`` is that count padded to a
                # power of two)
                rec["missed_profiles"] = len({
                    int(r.profile_id) for r in reqs[:n]
                    if not cached[int(r.profile_id)]})
                self.log.append((t, rec))
            return n

        eng.admit_many = admit_many


def _request(arr):
    from repro.serve.scheduler import Request

    return Request(uid=arr.uid, prompt=arr.prompt, profile_id=arr.profile,
                   max_new_tokens=arr.max_new)


def _drain(eng, reqs) -> None:
    eng.submit(reqs)
    eng.run_until_drained()
    bad = [r.uid for r in reqs if not r.done or r.degraded]
    if bad:
        raise RuntimeError(f"warm-up requests {bad} did not finish")


def warm_up(eng, mix: dict, n_profiles: int, seed: int, vocab: int) -> int:
    """Run every shape the mix can reach once; returns the waves run."""
    from repro.utils import pow2_count

    rng = model.host_rng(seed, 20)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    by_bucket = {}
    for t in range(lo, hi + 1):
        b = eng.scheduler.bucket_of(_request(traffic.Arrival(
            0, 0.0, np.zeros(t, np.int32), 1, 0)))
        by_bucket[b] = t                 # longest prompt in each bucket
    buckets = sorted(by_bucket)
    slots = eng.n_slots
    pow2s = [p for p in (1, 2, 4, 8, 16, 32, 64) if p <= pow2_count(slots)
             and p <= slots]
    uid = [10 ** 9]
    next_pid = [0]

    def reqs(bucket, n, max_new=WARM_NEW):
        out = []
        for _ in range(n):
            uid[0] += 1
            pid = next_pid[0] % n_profiles
            next_pid[0] += 1
            out.append(_request(traffic.Arrival(
                uid[0], 0.0, rng.integers(0, vocab, by_bucket[bucket],
                                          dtype=np.int32), max_new, pid)))
        return out

    def wave(parts):
        eng.profile_cache.clear()        # every profile misses
        _drain(eng, [r for b, n, *m in parts for r in reqs(b, n, *m)])

    waves = 0
    have = set()
    # every prefill bucket at every padded row count
    for b in buckets:
        for p in pow2s:
            wave([(b, p)])
            waves += 1
            have |= {("g", p, p), ("s", p, p)}
    # every wave size with every prefill group size it can hold (row
    # gathers [n] -> [pow2(B)], token slices [pow2(B)] -> [B])
    b1, b2 = buckets[0], buckets[-1]
    for n in range(1, slots + 1):
        for B in range(n, 0, -1):
            keys = {("g", n, pow2_count(B)), ("s", pow2_count(B), B)}
            if n - B:
                if b1 == b2:
                    continue
                keys |= {("g", n, pow2_count(n - B)),
                         ("s", pow2_count(n - B), n - B)}
            if keys - have:
                wave([(b1, B)] + ([(b2, n - B)] if n - B else []))
                waves += 1
                have |= keys
    # every sync window width
    for w in range(1, eng.sync_every + 1):
        wave([(b1, 1, w + 1)])
        waves += 1
    return waves


def entry_bytes(conf: dict) -> int:
    """Bytes of one profile-cache entry: Â, B̂ in the served dtype and the
    float32 adapter-LN affine."""
    s = model.sizes(conf)
    item = np.dtype(np.float16 if s["dtype"] == "bfloat16"
                    else s["dtype"]).itemsize
    return 2 * s["L"] * s["d"] * s["b"] * item + 2 * s["L"] * s["b"] * 4


def prewarm_cache(eng, mix: dict, conf: dict, cache_bytes: int, seed: int,
                  vocab: int) -> List[int]:
    """Admit the hottest profiles once, least popular first, so the cache
    opens the window as steady traffic would leave it."""
    eng.profile_cache.clear()
    fit = max(1, cache_bytes // entry_bytes(conf))
    hot = traffic.hottest_profiles(mix, seed, fit)[::-1]
    rng = model.host_rng(seed, 21)
    t = mix["prompt_tokens"]["min"]
    for i in range(0, len(hot), eng.n_slots):
        _drain(eng, [_request(traffic.Arrival(
            2 * 10 ** 9 + j, 0.0, rng.integers(0, vocab, t, dtype=np.int32),
            1, pid)) for j, pid in enumerate(hot[i:i + eng.n_slots])])
    return hot


def build(run):
    """Weights, profiles, store and engine of the cell, all from the
    seed."""
    import jax

    from repro.serve.engine import ServeEngine

    conf, E = run.conf, run.cell["engine"]
    cfg = model.model_config(conf, run.kernel_impl)
    params = model.init_params(run.seed, conf)
    jax.block_until_ready(params)
    profiles = model.init_profiles(run.seed, conf,
                                   run.mix["profiles"]["count"])
    store = model.build_store(conf, profiles)
    eng = ServeEngine(cfg, params, store, max_slots=E["slots"],
                      max_seq=E["max_seq"], sync_every=E["sync_every"],
                      cache_bytes=E["cache_bytes"], continuous=True,
                      page_size=E["page_size"])
    return params, profiles, eng


def window(run, eng, arrivals, book: Book, open_loop: bool,
           hooks: Hooks) -> dict:
    """The measured window, then (open loop) the drain of every request
    due in it. Returns host timings of the traced part."""
    seconds = run.seconds
    sync_every = eng.sync_every
    tw = tracing.Window(run.trace_dir) if run.trace else None
    if tw is not None:
        tw.start()
    t0 = time.perf_counter()
    hooks.on_admit = lambda reqs, t: book.admitted(reqs, t - t0)
    marks = {"longest_loop_s": 0.0}
    nxt = 0
    last = 0.0
    if not open_loop:
        reqs = [_request(a) for a in arrivals]
        for a, r in zip(arrivals, reqs):
            book.add(Record(a, r, 0.0))
        eng.submit(reqs)
        nxt = len(arrivals)
    while True:
        now = time.perf_counter() - t0
        # the longest pass of this loop: how late a due request can be
        marks["longest_loop_s"] = max(marks["longest_loop_s"], now - last)
        last = now
        if now >= seconds:
            break
        if open_loop:
            batch = []
            while nxt < len(arrivals) and arrivals[nxt].due_s <= now:
                r = _request(arrivals[nxt])
                book.add(Record(arrivals[nxt], r, now))
                batch.append(r)
                nxt += 1
            if batch:
                eng.submit(batch)
        if eng.scheduler.pending() or eng.active_count():
            with span("decode_window"):
                eng.run_until_drained(max_steps=sync_every)
            t = time.perf_counter() - t0
            book.observe(t)
            book.depth.append((t, eng.scheduler.pending()))
            if not open_loop and not eng.scheduler.pending() and \
                    "backlog_empty_s" not in marks:
                marks["backlog_empty_s"] = t
        else:
            due = arrivals[nxt].due_s if nxt < len(arrivals) else seconds
            with span("await_arrival"):
                time.sleep(max(0.0, min(due, seconds) - now))
        if tw is not None and tw.on and \
                time.perf_counter() - t0 >= min(TRACE_S, seconds):
            _stop_trace(tw, eng, book, marks)
    if tw is not None and tw.on:
        _stop_trace(tw, eng, book, marks)
    if open_loop:
        # requests due before the close that the loop had not reached: they
        # count, submitted late
        now = time.perf_counter() - t0
        late = []
        while nxt < len(arrivals) and arrivals[nxt].due_s <= seconds:
            r = _request(arrivals[nxt])
            book.add(Record(arrivals[nxt], r, now))
            late.append(r)
            nxt += 1
        if late:
            eng.submit(late)
        marks["submitted_after_close"] = len(late)
    if tw is not None:
        run.trace_path = tw.path
    marks["window_end"] = time.perf_counter()
    if open_loop:
        with span("drain"):
            while book.unfinished(seconds) and \
                    time.perf_counter() - t0 < seconds + DRAIN_S:
                eng.run_until_drained(max_steps=sync_every)
                book.observe(time.perf_counter() - t0)
    hooks.on_admit = None
    marks["t0"] = t0
    return marks


def _stop_trace(tw, eng, book: Book, marks: dict) -> None:
    """End the traced part of the window, keeping the engine's counters and
    the tokens seen as of then: the readers set the traced device time
    against the work of that part alone."""
    marks["trace_end"] = tw.stop()
    marks["trace_stats"] = eng.serve_stats()
    marks["trace_tokens"] = (book.window_tokens, book.ctx_tokens)


def _percentile(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def _mean_context(tokens: int, ctx_tokens: int) -> float:
    return ctx_tokens / max(tokens, 1)


def end_to_end(book: Book, seconds: float, open_loop: bool) -> dict:
    """The cell's end-to-end numbers, and attempted/failed counts."""
    out = {"output_tokens_per_s": book.window_tokens / seconds}
    if open_loop:
        due = [r for r in book.records if r.arrival.due_s <= seconds]
        failed = [r for r in due if r.first_s is None or r.req.degraded]
        cutoff = seconds + DRAIN_S
        ttft = [1e3 * ((r.first_s if r.first_s is not None else cutoff)
                       - r.arrival.due_s) for r in due]
        # the mean gap between tokens seen after the first one (the
        # harness sees the first token when its admission returns and the
        # rest once per decode window)
        tpot = [1e3 * (r.last_s - r.first_s) / (r.seen - r.seen_first)
                for r in due
                if r.first_s is not None and r.seen > r.seen_first]
        late = [1e3 * (r.submitted_s - r.arrival.due_s) for r in due]
        out.update(ttft_p95_ms=_percentile(ttft, 95),
                   ttft_p50_ms=_percentile(ttft, 50),
                   tpot_p95_ms=_percentile(tpot, 95),
                   tpot_p50_ms=_percentile(tpot, 50),
                   generator_late_p95_ms=_percentile(late, 95),
                   attempted=len(due), failed=len(failed))
    else:
        started = [r for r in book.records if r.first_s is not None
                   and r.first_s <= seconds]
        out.update(attempted=len(started),
                   failed=sum(r.req.degraded for r in started),
                   finished=sum(r.req.done for r in started))
    return out


def run(run, open_loop: bool) -> None:
    """One serving run: set-up, window, readings, correctness."""
    import jax

    s = model.sizes(run.conf)
    params, profiles, eng = build(run)
    arrivals = traffic.generate(run.mix, run.seed, run.seconds, s["V"])
    hooks = Hooks(eng)
    run.info["warm_waves"] = warm_up(eng, run.mix,
                                     run.mix["profiles"]["count"], run.seed,
                                     s["V"])
    hot = prewarm_cache(eng, run.mix, run.conf,
                        run.cell["engine"]["cache_bytes"], run.seed,
                        s["V"])
    run.info["prewarmed_profiles"] = len(hot)
    eng.reset_stats()
    hooks.log.clear()
    gc.collect()
    book = Book(run.seconds)
    run.setup_s = time.perf_counter() - run.t_start
    run.clock.reset()
    if run.gc_clock is not None:
        run.gc_clock.reset()
    marks = window(run, eng, arrivals, book, open_loop, hooks)
    # a backlog that empties before the window closes measured an idle tail
    run.info["backlog_empty_s"] = marks.get("backlog_empty_s")
    run.info["longest_loop_s"] = marks["longest_loop_s"]
    run.info["submitted_after_close"] = marks.get("submitted_after_close")
    run.info.update(device.window_facts(run))
    jax.block_until_ready(eng.cache)
    run.device["memory_peak_bytes"] = device.peak_bytes()
    run.e2e = end_to_end(book, run.seconds, open_loop)
    run.attempted, run.failed = run.e2e["attempted"], run.e2e["failed"]
    run.stats = eng.serve_stats()
    run.extra.update(
        admissions=[(t - marks["t0"], a) for t, a in hooks.log],
        trace_span_s=(0.0, marks["trace_end"] - marks["t0"])
        if run.trace else None,
        trace_stats=marks.get("trace_stats"),
        trace_mean_context=_mean_context(*marks["trace_tokens"])
        if run.trace else None,
        window_tokens=book.window_tokens,
        mean_context=_mean_context(book.window_tokens, book.ctx_tokens),
        slots=eng.n_slots, sync_every=eng.sync_every)
    finished = [r for r in book.records if r.req.done and not r.req.degraded
                and len(r.req.generated) > 0]
    del eng, hooks
    gc.collect()
    run.checks = check.serving(run, params, profiles, finished,
                               control=run.control)
