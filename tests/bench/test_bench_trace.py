"""The reduction from a device trace to busy time, idle gaps attributed to
host spans, and per-op time: on hand-made events and on a trace recorded
on the chip."""
import gzip
import json
import os

import pytest

from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_chat_small.json.gz")


def ev(name, start, dur):
    return [name, start, dur]


def test_busy_is_the_union_of_op_intervals():
    ops = [ev("a", 10, 10), ev("b", 15, 10), ev("c", 40, 5),
           ev("d", 0, 3), ev("e", 98, 10)]
    # [10, 25) + [40, 45) + [98, 100) inside [5, 100); [0, 3) is outside
    assert tracing.busy_ns(ops, 5, 100) == 15 + 5 + 2
    assert tracing.idle_gaps(ops, 5, 100) == [(5, 10), (25, 40), (45, 98)]
    assert tracing.idle_gaps([], 0, 7) == [(0, 7)]


def test_gaps_go_to_the_innermost_host_span():
    gaps = [(0, 10), (20, 30), (40, 50)]
    host = [["bench_window", 0, 100], ["decode_window", 15, 40],
            ["admit", 18, 14]]
    got = tracing.attribute(gaps, host)
    # (0, 10): no span but the window; (20, 30): admit inside decode_window;
    # (40, 50): decode_window alone
    assert got == {"host": 10, "admit": 10, "decode_window": 10}


def test_reduced_window_and_breakdown():
    tr = {"host": [["bench_window", 100, 1000], ["await_arrival", 500, 200]],
          "device": {tracing.OPS_LINE: [
              ev("%while.3 = (s32[], bf16[4]{0}) while(%tuple.1), body=b",
                 100, 300),
              ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
                 150, 100),
              ev("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %q), kind=kLoop",
                 800, 100),
              ev("%fused_adapter_batched.7 = bf16[2,1,8]{2,1,0} custom-call("
                 "bf16[2,1,8]{2,1,0} %fusion.2), custom_call_target=\"tpu\"",
                 420, 50)],
                     tracing.MODULES_LINE: [ev("jit_step_impl", 100, 400),
                                            ev("jit_step_impl", 800, 100),
                                            ev("jit_other", 0, 50)]},
          "device_plane": "/device:TPU:0"}
    r = tracing.Reduced(tr)
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(450e-9)
    assert [e[2] for e in r.modules_named("jit_step_impl")] == [400, 100]
    b = r.breakdown()
    # self time: the while op less the fusion nested in it
    assert dict(b["device_ops"]) == {
        "fusion bf16[8]": pytest.approx(200e-9),
        "while (s32[],": pytest.approx(200e-9),
        "fused_adapter_batched bf16[2,1,8]": pytest.approx(50e-9)}
    assert [tracing.op_name(e) for e in r.kernels(
        ("fused_adapter_batched",))] == ["fused_adapter_batched.7"]
    assert tracing.op_kind(r.ops[0]) == "while"
    names = dict(b["idle_gaps"])
    # the gap [470, 800) has its midpoint in await_arrival [500, 700)
    assert names["await_arrival"] == pytest.approx(330e-9)
    assert sum(names.values()) == pytest.approx(r.window_s - r.busy_s)


def test_recorded_chip_trace():
    with gzip.open(RECORDED, "rt") as f:
        tr = json.load(f)
    lo, hi = tr["window"]
    ops = tr["device"][tracing.OPS_LINE]
    busy = tracing.busy_ns(ops, lo, hi)
    gaps = tracing.idle_gaps(ops, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy + sum(e - s for s, e in gaps) == hi - lo
    idle = tracing.attribute(gaps, tr["host"])
    assert sum(idle.values()) == hi - lo - busy
    assert set(idle) <= set(tracing.SPANS) | {"host"}


def test_recorded_kernel_launches_and_their_staging():
    """qwen1.5-0.5b decode at 16 slots: 24 fused-adapter launches per step,
    each reading per-row Â/B̂ that a fusion sliced out of the [L, B, d, b]
    adapter tensor in HBM into vector memory (``S(1)``) just before it."""
    from bench.work import fused_adapter, least_seconds

    with gzip.open(RECORDED, "rt") as f:
        tr = json.load(f)
    lo, hi = tr["window"]
    tr["host"].append(["bench_window", lo, hi - lo])
    r = tracing.Reduced(tr)
    k = r.kernels(("fused_adapter_batched",))
    assert len(k) == 72                       # 3 decode steps x 24 layers
    args = tracing.operands(k[0])
    assert len(args) == 5 and args[1].startswith("dynamic-slice")
    # every operand of every launch sits in vector memory
    assert all(all(tracing.operands_in_vmem(e)) for e in k)
    # the Â/B̂ producers read HBM: their own operand (the whole adapter
    # tensor) has no S(1); each launch has a producer of its own
    by_name = {}
    for e in r.ops:
        by_name.setdefault(tracing.op_name(e), []).append(e)
    for i in (1, 2):
        prod = by_name[args[i]]
        assert len(prod) == len(k)
        assert not any(tracing.operands_in_vmem(prod[0]))
        assert "[24,16," in prod[0][0].split(" fusion(", 1)[1][:40]
    staged = r.staged(k, (1, 2, 3, 4))
    assert all(t > e[2] for t, e in zip(staged, k))
    f, b = fused_adapter.work(16, 1, 1024, 64)
    least = least_seconds(f, b, {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9})
    share = least * len(k) / (sum(staged) / 1e9)
    assert 0.2 < share < 0.4                  # 29% on the chip
    # the kernel's own time leaves out the HBM reads of Â/B̂ (done by
    # their producers); it alone would read 71%
    assert 0.6 < least * len(k) / (sum(e[2] for e in k) / 1e9) < 0.8


def test_operands_in_hbm_add_no_staging():
    """An operand the kernel reads from HBM itself (no ``S(1)``) adds no
    producer time."""
    tr = {"host": [["bench_window", 0, 1000]],
          "device": {tracing.OPS_LINE: [
              ev("%fusion.1 = bf16[8]{0} fusion(bf16[9]{0} %p)", 10, 100),
              ev("%fusion.2 = bf16[8]{0:S(1)} fusion(bf16[9]{0} %q)",
                 120, 30),
              ev("%k.3 = bf16[8]{0} custom-call(bf16[8]{0} %fusion.1, "
                 "bf16[8]{0:S(1)} %fusion.2), custom_call_target=\"tpu\"",
                 200, 50)]},
          "device_plane": "/device:TPU:0"}
    r = tracing.Reduced(tr)
    launch = r.ops[2]
    assert tracing.operands(launch) == ["fusion.1", "fusion.2"]
    assert tracing.operands_in_vmem(launch) == [False, True]
    assert r.staged([launch], (0, 1)) == [50 + 30]
