"""The readers of the program's own spans and scopes
(``bench/program_trace.py``): on hand-made events, on an XSpace file
recorded here on the CPU (the wire-format reading of the compiled
modules' HLO), and on a trace recorded on the chip."""
import glob
import gzip
import json
import os
import types

import pytest

from bench import program_trace as PT
from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_chat_program.json.gz")
RECORDED_ONBOARD = os.path.join(HERE, "data",
                                "trace_onboard_program.json.gz")


def _reduced(host, ops, modules, t0=0, t1=1000):
    return tracing.Reduced({
        "host": [["bench_window", t0, t1 - t0]] + host,
        "device": {tracing.OPS_LINE: ops, tracing.MODULES_LINE: modules},
        "device_plane": "/device:TPU:0"})


def _run(r, pt, stats=None):
    return types.SimpleNamespace(reduced=r, trace_path="mem",
                                 stats=stats or {},
                                 extra={"program_trace": pt})


# two decode steps; ops nested in a loop; ops outside any step
S = "jit(step_impl)/"
OPS = [
    ["%gather.1 = bf16[2]{0} fusion()", 100, 40, S + "kv_dense_view/gather"],
    ["%while.2 = (s32[]) while()", 140, 40, S + "while"],
    ["%fusion.3 = bf16[2]{0} fusion()", 150, 10,
     S + "while/body/kv_cache_update/scatter"],
    ["%fusion.4 = bf16[2]{0} fusion()", 165, 10, S + "while/body/adapter/dot"],
    ["%scatter.5 = bf16[2]{0} scatter()", 180, 20, S + "kv_writeback/scatter"],
    ["%copy.6 = bf16[2]{0} copy()", 300, 50, ""],
    ["%gather.1 = bf16[2]{0} fusion()", 500, 40, S + "kv_dense_view/gather"],
    ["%copy.7 = bf16[2]{0} copy()", 540, 60, ""],
    ["%fusion.9 = bf16[2]{0} fusion()", 700, 30, "jit(_prefill_traced)/x"],
]
MODULES = [["jit_step_impl(7)", 100, 100], ["jit_step_impl(7)", 500, 100],
           ["jit__prefill_traced(9)", 700, 30]]


def test_paging_share_over_the_decode_steps_self_time():
    r = _reduced([], OPS, MODULES)
    pt = {"spans": [], "ops": OPS}
    by = PT.decode_by_scope(r, pt)
    # the loop's own time is its 40 less the 20 nested in it
    assert by == {"kv_dense_view": 80, "": 20 + 60, "kv_cache_update": 10,
                  "adapter": 10, "kv_writeback": 20}
    assert PT.paging_share(r, pt) == pytest.approx(100 * 100 / 200)
    assert PT.kv_paging_share(_run(r, pt)) == pytest.approx(50.0)
    # a program without the scopes reads nothing
    bare = [o[:3] + [""] for o in OPS]
    assert PT.paging_share(r, {"spans": [], "ops": bare}) is None
    assert PT.scope_of("jit(f)/kv_writeback/x/adapter/y") == "kv_writeback"
    assert PT.scope_of("jit(f)/kv_writebacks/y") == ""


SPANS = [
    ["serve.admit_wave", 10, 80, {"admitted": 2, "missed": 1}],
    ["serve.sync", 12, 20, {"fill": 4}],
    ["serve.fetch", 12, 10, {}],
    ["serve.prefill", 40, 30, {"rows": 2}],
    ["serve.sync", 300, 100, {"fill": 8}],
    ["serve.distribute", 330, 60, {}],
    ["serve.refresh_window", 392, 6, {}],
    ["serve.admit_wave", 900, 60, {"admitted": 1}],
    ["serve.admit_wave", 2000, 50, {}],           # past the window
]


def test_span_readers_on_hand_made_events():
    # device busy [0, 15), [20, 300), [395, 900); idle gaps:
    # [15, 20) mid 17 (fetch in sync), [300, 395) mid 347 (distribute in
    # sync), [900, 1000) mid 950 (admit_wave)
    ops = [["a", 0, 15, ""], ["b", 20, 280, ""], ["c", 395, 505, ""]]
    r = _reduced([], ops, [])
    pt = {"spans": SPANS, "ops": ops}
    run = _run(r, pt)
    assert PT.admit_wave_ms(run) == pytest.approx(1e3 * 70 / 1e9)
    assert PT.idle_under(r, pt, "serve.sync") == 5 + 95
    assert PT.sync_idle_ms(run) == pytest.approx(100 / 1e6 / 2)
    by = PT.idle_by_span(r, pt)
    assert by == {"serve.fetch": 5, "serve.distribute": 95,
                  "serve.admit_wave": 100}
    assert PT.graduation_idle_ms(run) is None     # no graduation here
    assert PT.spans_named(r, pt, "serve.admit_wave")[-1][1] == 900


def test_graduation_idle_per_profile():
    ops = [["a", 0, 100, ""], ["b", 400, 600, ""]]
    r = _reduced([], ops, [])
    spans = [["train.flush", 90, 20, {}],
             ["train.poll", 110, 300, {}],
             ["train.metrics_fetch", 110, 10, {}],
             ["train.graduate", 130, 100, {"profile": 1}],
             ["train.graduate", 230, 100, {"profile": 2}],
             ["train.fill", 330, 60, {"admitted": 2}]]
    run = _run(r, {"spans": spans, "ops": ops})
    # one gap [100, 400), midpoint 250 inside the poll: 300 ns, two
    # profiles graduated
    assert PT.graduation_idle_ms(run) == pytest.approx(300 / 1e6 / 2)


def test_queue_wait_reads_the_traced_scheduler_counters():
    def run(sch):
        return types.SimpleNamespace(
            stats={"scheduler": {"queue_wait_s": 99.0, "waited": 3}},
            extra={"trace_stats": {"scheduler": sch}})

    # the counters as the trace stopped, not the whole run's
    assert PT.queue_wait_ms(run({"queue_wait_s": 1.5, "waited": 3})) == \
        pytest.approx(500.0)
    # a program without the counters, or no admission, reads nothing
    assert PT.queue_wait_ms(run({"submitted": 3})) is None
    assert PT.queue_wait_ms(types.SimpleNamespace(stats={}, extra={})) \
        is None


def test_untraced_run_reads_nothing():
    run = types.SimpleNamespace(reduced=None, trace_path=None, stats={},
                                extra={})
    for f in (PT.kv_paging_share, PT.admit_wave_ms, PT.sync_idle_ms,
              PT.graduation_idle_ms):
        assert f(run) is None


def test_scopes_from_the_recorded_modules_hlo(tmp_path):
    """The compiled module's HLO in the XSpace's metadata plane maps each
    instruction to the scope it was traced under."""
    import jax
    import jax.numpy as jnp

    def step_impl(pool, x):
        with jax.named_scope("kv_dense_view"):
            y = jnp.sin(pool) @ x
        with jax.named_scope("kv_writeback"):
            return jnp.cos(y) + 1.0

    f = jax.jit(step_impl)
    a = jnp.ones((64, 64))
    f(a, a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f(a, a).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    protos = PT.hlo_protos(path)
    name = next(k for k in protos if k.startswith("jit_step_impl("))
    scopes = PT.op_scopes(protos[name])
    by = {PT.scope_of(v) for v in scopes.values()}
    assert {"kv_dense_view", "kv_writeback"} <= by
    # an op takes its instruction's scope in the module execution it
    # starts in; outside every module it has none
    inst = next(k for k, v in scopes.items()
                if PT.scope_of(v) == "kv_dense_view")
    ops = [[f"%{inst} = f32[64,64]{{1,0}} x()", 10, 5],
           [f"%{inst} = f32[64,64]{{1,0}} x()", 50, 5]]
    got = PT._with_scopes(ops, [[name, 0, 20]], protos)
    assert PT.scope_of(got[0][3]) == "kv_dense_view"
    assert got[1][3] == ""


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | 0x80 if n else b])
        if not n:
            return out


def _f(field, value):
    """One protobuf field: a varint, or length-delimited bytes."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _ins(iid, name, opcode, path="", operands=(), calls=()):
    return (_f(1, name) + _f(2, opcode)
            + (_f(7, _f(2, path)) if path else b"") + _f(35, iid)
            + b"".join(_f(36, o) for o in operands)
            + b"".join(_f(38, c) for c in calls))


def _comp(cid, name, *instructions):
    return _f(1, name) + b"".join(_f(2, i) for i in instructions) \
        + _f(5, cid)


def test_scopes_reach_the_ops_xla_inserts():
    """Layout copies of an argument take the scope of the op they feed
    (or, failing that, that fed them); ops of a loop XLA built from a
    scoped op take its scope; the program's own unscoped ops stay
    unscoped."""
    J = "jit(step_impl)/"
    entry = _comp(
        1, "main",
        _ins(1, "cache_k", "parameter", "cache['k']"),
        _ins(2, "copy.1", "copy", "", [1]),
        _ins(3, "while.1", "while", J + "kv_dense_view/gather", [2], [2]),
        _ins(4, "copy.2", "copy", "cache['k']", [1]),
        _ins(5, "fusion.1", "fusion", "", [4], [3]),
        _ins(6, "copy.3", "copy", "", [5]),
        _ins(7, "while.2", "while", J + "while", [], [4]),
        _ins(8, "dus.1", "dynamic-update-slice",
             J + "while/body/dynamic_update_slice", [7]))
    gather_loop = _comp(2, "gather_body",
                        _ins(20, "dus.2", "dynamic-update-slice"),
                        _ins(21, "broadcast.1", "broadcast"))
    fused = _comp(3, "fused",
                  _ins(30, "scatter.1", "scatter", J + "kv_writeback/s"))
    scan_body = _comp(
        4, "scan_body",
        _ins(40, "fusion.2", "fusion",
             J + "while/body/kv_cache_update/scatter"),
        _ins(41, "add.1", "add", J + "while/body/adapter/add"),
        _ins(42, "copy.4", "copy", "", [41]))
    module = _f(1, "jit_step_impl(7)") + b"".join(
        _f(3, c) for c in (entry, gather_loop, fused, scan_body))
    got = PT.op_scopes(_f(1, module))
    assert got == {
        "cache_k": "", "copy.1": "kv_dense_view",
        "while.1": "kv_dense_view", "copy.2": "kv_writeback",
        "fusion.1": "kv_writeback", "copy.3": "kv_writeback",
        "while.2": "", "dus.1": "",
        "dus.2": "kv_dense_view", "broadcast.1": "kv_dense_view",
        "scatter.1": "kv_writeback", "fusion.2": "kv_cache_update",
        "add.1": "adapter", "copy.4": "adapter"}


def _recorded(path):
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    lo, hi = tr["window"]
    r = _reduced(tr["host"], tr["device"][tracing.OPS_LINE],
                 tr["device"][tracing.MODULES_LINE], lo, hi)
    return r, {"spans": tr["spans"], "ops": tr["device"][tracing.OPS_LINE]}


def test_recorded_chat_admission_and_decode_steps():
    """qwen1.5-0.5b chat-zipf on the chip: an admission wave that missed
    one profile, nested in the harness's ``admit`` span, and three
    decode steps whose device time the scopes divide."""
    r, pt = _recorded(RECORDED)
    [wave] = PT.spans_named(r, pt, "serve.admit_wave")
    assert wave[3]["admitted"] == 1 and wave[3]["missed"] == 1
    assert wave[3]["path"] == "sparse" and wave[3]["aggregated"] == 1
    inside = [s[0] for s in pt["spans"]
              if wave[1] <= s[1] and s[1] + s[2] <= wave[1] + wave[2]]
    assert inside == ["serve.admit_wave", "serve.probe", "serve.hydrate",
                      "serve.aggregate", "serve.scatter_masks",
                      "serve.prefill", "serve.slot_admit",
                      "serve.refresh_window"]
    [admit] = [h for h in r.raw["host"] if h[0] == "admit"]
    assert admit[1] <= wave[1] and wave[1] + wave[2] <= admit[1] + admit[2]
    assert len(r.modules_named("jit_step_impl")) == 3
    by = PT.decode_by_scope(r, pt)
    assert set(PT.SCOPES) <= set(by)
    # the whole window read 71.4% on the chip
    assert 60 < PT.paging_share(r, pt) < 85
    # every idle nanosecond goes to one program span or to 'host'
    idle = PT.idle_by_span(r, pt)
    assert sum(idle.values()) == sum(e - s for s, e in r.gaps)
    assert set(idle) <= {s[0] for s in pt["spans"]} | {"host"}
    assert idle["serve.hydrate"] > 0 and idle["serve.aggregate"] > 0
    # the harness's own attribution of the same gaps is unchanged
    assert set(tracing.attribute(r.gaps, r.raw["host"])) <= \
        set(tracing.SPANS) | {"host"}


def test_recorded_onboard_graduation():
    """qwen1.5-0.5b onboard on the chip: one poll graduating the four
    slots and refilling them, nested in the harness's ``lifecycle``."""
    r, pt = _recorded(RECORDED_ONBOARD)
    [poll] = PT.spans_named(r, pt, "train.poll")
    grads = PT.spans_named(r, pt, "train.graduate")
    assert [g[3]["slot"] for g in grads] == [0, 1, 2, 3]
    assert all(g[3]["steps"] == 20 for g in grads)
    assert all(poll[1] <= g[1] < poll[1] + poll[2] for g in grads)
    [fill] = PT.spans_named(r, pt, "train.fill")
    assert fill[3]["admitted"] == 4
    idle = PT.idle_under(r, pt, "train.poll")
    # the poll idles the chip for nearly all of its length
    assert 0.8 * poll[2] < idle <= poll[2]
    assert tracing.attribute(r.gaps, r.raw["host"])["lifecycle"] == idle
    run = types.SimpleNamespace(reduced=r, trace_path="x",
                                extra={"program_trace": pt})
    assert PT.graduation_idle_ms(run) == pytest.approx(idle / 1e6 / 4)
