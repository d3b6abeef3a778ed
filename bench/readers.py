"""What the per-layer metric readers share: finding a program's steps and
kernels in a reduced trace and setting their time against the work in
``bench/work/``. Every function returns None where the trace holds nothing
to read."""
from __future__ import annotations

from bench import model
from bench.work import (decode_step, fused_adapter, gang_step,
                        least_seconds, mask_aggregate, prefill)

# jitted programs and kernels by name: ``jit_<function>`` modules, and the
# Pallas kernels' function names as they appear on the device's op line
DECODE_MODULE = "jit_step_impl"
PREFILL_MODULE = "jit__prefill_traced"
GANG_MODULE = "jit_step("
FUSED_ADAPTER = ("fused_adapter_batched",)
MASK_AGGREGATE = ("mask_aggregate_batched",)


def _inside(events, spans) -> list:
    """Events that start inside any of ``spans`` (sorted [start, dur])."""
    spans = sorted((s[1], s[1] + s[2]) for s in spans)
    out, j = [], 0
    for e in sorted(events, key=lambda e: e[1]):
        while j < len(spans) and spans[j][1] <= e[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= e[1]:
            out.append(e)
    return out


def decode_steps(run) -> list:
    r = run.reduced
    return [] if r is None else r.modules_named(DECODE_MODULE)


def decode_step_ms(run):
    steps = decode_steps(run)
    if not steps:
        return None
    return 1e3 * sum(e[2] for e in steps) / 1e9 / len(steps)


def active_rows(run):
    """Mean rows per decode step that held a request, over the traced part
    of the window (the engine's slot-step counters as the trace stopped);
    None where no step ran."""
    st = run.extra.get("trace_stats") or {}
    if not st.get("device_steps"):
        return None
    return st["slot_occupancy"] * run.extra["slots"]


def step_mfu(run):
    """Model FLOPs of the traced decode steps over their device time at
    peak, in %. Tokens per step: the rows that held a request in the
    traced part; context: the mean of the tokens seen in that part."""
    steps = decode_steps(run)
    rows = active_rows(run)
    if not steps or not rows:
        return None
    s = model.sizes(run.conf)
    busy = sum(e[2] for e in steps) / 1e9
    flops = len(steps) * rows * decode_step.flops_per_token(
        s, run.extra["trace_mean_context"])
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])


# the fused adapter's per-row Â, B̂ and LN affine, by operand number
ADAPTER_OPERANDS = (1, 2, 3, 4)


def fused_adapter_roofline(run):
    """The decode step's fused-adapter launches against their least time,
    in %: each launch needs the rows that held a request (T = 1), not the
    padded slots. A launch's time includes the ops that staged its
    per-row Â, B̂ and LN affine into vector memory (``Reduced.staged``):
    the kernel reads them there, so the HBM reads its work needs happen in
    those ops, and the kernel's time alone leaves them out."""
    steps = decode_steps(run)
    rows = active_rows(run)
    if not steps or not rows:
        return None
    calls = _inside(run.reduced.kernels(FUSED_ADAPTER), steps)
    if not calls:
        return None
    s = model.sizes(run.conf)
    f, nb = fused_adapter.work(rows, 1, s["d"], s["b"])
    least = len(calls) * least_seconds(f, nb, run.peaks)
    return 100.0 * least / (sum(run.reduced.staged(calls, ADAPTER_OPERANDS))
                            / 1e9)


def mask_aggregate_roofline(run):
    """The admission aggregate's launches in the traced span against their
    least time, in %: each aggregating admission needs one Â and one B̂
    aggregation over (the distinct profiles it missed) x L rows; the
    kernel runs over that count padded to a power of two, and the padding
    counts as time, not work. A launch's time includes any op that staged
    its operands into vector memory."""
    agg = [a for a in _traced_admissions(run) if a.get("path") == "sparse"]
    calls = run.reduced.kernels(MASK_AGGREGATE) if agg else []
    if not calls:
        return None
    s = model.sizes(run.conf)
    least = 0.0
    for a in agg:
        f, nb = mask_aggregate.work(
            a["missed_profiles"] * s["L"], s["k"], s["d"], s["b"])
        least += 2 * least_seconds(f, nb, run.peaks)
    return 100.0 * least / (sum(run.reduced.staged(calls, (0, 1, 2)))
                            / 1e9)


def _traced_admissions(run) -> list:
    if run.reduced is None or run.extra.get("trace_span_s") is None:
        return []
    lo, hi = run.extra["trace_span_s"]
    return [a for t, a in run.extra["admissions"] if lo <= t < hi]


def admit_mfu(run):
    """Model FLOPs of admission (the prompts' prefill and the k-sparse
    aggregation of the distinct profiles it missed, unpadded) over the
    device time of the prefill programs and the aggregate kernel, at peak,
    in %."""
    adm = _traced_admissions(run)
    pre = run.reduced.modules_named(PREFILL_MODULE) if adm else []
    if not pre:
        return None
    s = model.sizes(run.conf)
    flops = 0.0
    for a in adm:
        flops += sum(prefill.flops(s, T) for T in a["prompt_lengths"])
        if a.get("path") == "sparse":
            flops += 2 * mask_aggregate.work(
                a["missed_profiles"] * s["L"], s["k"], s["d"],
                s["b"])[0]
    agg = run.reduced.kernels(MASK_AGGREGATE)
    busy = (sum(e[2] for e in pre) + sum(e[2] for e in agg)) / 1e9
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])


def gang_steps(run) -> list:
    r = run.reduced
    return [] if r is None else r.modules_named(GANG_MODULE)


def gang_step_ms(run):
    steps = gang_steps(run)
    if not steps:
        return None
    return 1e3 * sum(e[2] for e in steps) / 1e9 / len(steps)


def gang_step_mfu(run):
    """Model FLOPs of the traced gang steps (``bench/work/gang_step.py``)
    over their device time at peak, in %."""
    steps = gang_steps(run)
    if not steps:
        return None
    s = model.sizes(run.conf)
    flops = len(steps) * gang_step.flops(s, run.extra["examples"],
                                         run.extra["seq_len"])
    busy = sum(e[2] for e in steps) / 1e9
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])


def device_idle(run):
    r = run.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)


def hit_rate(run):
    pc = run.stats.get("profile_cache", {})
    n = pc.get("hits", 0) + pc.get("misses", 0)
    return 100.0 * pc["hits"] / n if n else None


def slot_occupancy(run):
    occ = run.stats.get("slot_occupancy")
    return None if not run.stats.get("device_steps") else 100.0 * occ
