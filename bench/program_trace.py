"""The program's own part of a traced run, re-read from the run's XSpace
file (``run.trace_path``): its host spans (``serve.*``, ``train.*``, from
``repro.obs.trace``) with their args, and each device op's named-scope
path. ``bench/tracing.py`` keeps only the harness's spans and the ops'
names; this module reads the rest and reuses its arithmetic (``attribute``
for idle gaps, ``self_times`` for ops nested in loops).

A span is ``[name, start_ns, duration_ns, args]``, a device op
``[name, start_ns, duration_ns, scope]`` where ``scope`` is the first of
``SCOPES`` its HLO instruction belongs to, '' for none (``op_scopes``).
The device's op events carry no scope; the compiled modules' HLO, which
the profiler keeps in the trace's ``/host:metadata`` plane, names in each
instruction's ``op_name`` metadata the ``jax.named_scope``s it was traced
under (``jit(step_impl)/kv_writeback/scatter``). It is read from the
file's protobuf wire format directly (no generated proto classes are
needed).

Every reader returns None where the trace holds nothing to read: a program
that writes no such span or scope reads None, not 0.

    python bench/program_trace.py TRACE.xplane.pb
        prints idle by program span, the decode step's device time by
        scope, and the spans per second of the traced window;
    python bench/program_trace.py TRACE.xplane.pb --small OUT.json.gz \\
        --at serve.admit_wave [--containing CHILD] [--ms 250]
        writes the slice of the trace from just before the first such
        span, as the tests read it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import readers, tracing  # noqa: E402

PREFIXES = ("serve.", "train.")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# the scopes the program puts on the decode step, outermost first; an op
# counts under the first of them on its path
SCOPES = ("kv_dense_view", "kv_writeback", "kv_cache_update", "adapter")
PAGING = ("kv_dense_view", "kv_writeback")


# ------------------------------------------------- protobuf wire format
def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of a serialized message: ints for
    varints, memoryviews for length-delimited and fixed-width fields."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt in (1, 5):
            w = 8 if wt == 1 else 4
            v, i = b[i:i + w], i + w
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield key >> 3, v


def _get(msg, field: int, default=None):
    return next((v for f, v in _fields(msg) if f == field), default)


def hlo_protos(path: str) -> dict:
    """Serialized ``HloProto`` of each compiled module in an XSpace file,
    by the module's name (``jit_step_impl(<program id>)``)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for f, plane in _fields(space):                 # XSpace.planes = 1
        if f != 1 or bytes(_get(plane, 2, b"")) != METADATA_PLANE.encode():
            continue
        stat_id = None
        for g, entry in _fields(plane):             # XPlane.stat_metadata
            if g == 5:
                sm = _get(entry, 2)
                if bytes(_get(sm, 2, b"")) == HLO_STAT.encode():
                    stat_id = _get(sm, 1)
        for g, entry in _fields(plane):             # XPlane.event_metadata
            if g != 4:
                continue
            em = _get(entry, 2)
            name = bytes(_get(em, 2, b"")).decode()
            for h, stat in _fields(em):             # XEventMetadata.stats
                if h == 5 and _get(stat, 1) == stat_id:
                    out[name] = _get(stat, 6)       # XStat.bytes_value
    return out


# opcodes that only move or lay out data: where XLA inserted one (a layout
# copy, a buffer set-up) it carries no scope of its own, and it takes the
# scope of the op it serves
MOVES = ("copy", "copy-start", "copy-done", "bitcast", "broadcast",
         "transpose", "reshape", "slice", "dynamic-slice",
         "dynamic-update-slice", "get-tuple-element", "tuple")


def op_scopes(hlo_proto) -> dict:
    """Instruction name -> the first of ``SCOPES`` it belongs to ('' for
    none), over every computation of one module. An instruction belongs
    to a scope named on its ``op_name`` path; failing that, a fusion to
    one of its fused instructions' scopes; an instruction in a loop body
    or fused computation XLA built from a scoped op (a gather turned into
    a loop) to its caller's; and a data move XLA inserted (``MOVES``, with
    no traced path of its own: a layout copy of an argument) to the scope
    of the ops it feeds or, failing that, of the ops that feed it (the
    first of them in ``SCOPES`` where they differ)."""
    module = _get(hlo_proto, 1)                     # HloProto.hlo_module
    # id -> [name, opcode, scope, operands, called computations, inserted]
    ins = {}
    comp_of, members = {}, {}
    for f, comp in _fields(module):                 # computations = 3
        if f != 3:
            continue
        cid = _get(comp, 5)
        members[cid] = []
        for g, msg in _fields(comp):                # instructions = 2
            if g != 2:
                continue
            iid = _get(msg, 35)
            meta = _get(msg, 7)
            path = bytes(_get(meta, 2, b"")).decode() \
                if meta is not None else ""
            op = bytes(_get(msg, 2, b"")).decode()
            ins[iid] = [bytes(_get(msg, 1, b"")).decode(), op,
                        scope_of(path), _ids(msg, 36), _ids(msg, 38),
                        op in MOVES and "/" not in path]
            comp_of[iid] = cid
            members[cid].append(iid)
    caller = {c: i for i, v in ins.items() for c in v[4]}
    for v in ins.values():                          # fusions
        if not v[2] and v[1] == "fusion":
            v[2] = _first(ins[m][2] for c in v[4] for m in members.get(c, ()))
    for i, v in ins.items():                        # loop and fusion bodies
        c = comp_of[i]
        while not v[2] and c in caller:
            v[2] = ins[caller[c]][2]
            c = comp_of[caller[c]]
    users = {}
    for i, v in ins.items():
        for o in v[3]:
            users.setdefault(o, []).append(i)
    changed = True
    while changed:                                  # data moves
        changed = False
        for i, v in ins.items():
            if v[2] or not v[5]:
                continue
            for near in (users.get(i, ()), v[3]):
                v[2] = _first(ins[n][2] for n in near if n in ins)
                if v[2]:
                    changed = True
                    break
    return {v[0]: v[2] for v in ins.values()}


def _ids(msg, field: int) -> list:
    """A repeated int64 field, packed or not."""
    out = []
    for f, v in _fields(msg):
        if f != field:
            continue
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _first(scopes) -> str:
    """The first of ``SCOPES`` among ``scopes``, '' if none is."""
    have = set(scopes)
    return next((s for s in SCOPES if s in have), "")


def extract(path: str) -> dict:
    """Program spans from the host plane, and the first device plane's ops
    with the scope paths of their instructions."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops, modules, first = [], [], [], None
    for plane in pd.planes:
        if plane.name.startswith(tracing.DEVICE_PLANE_PREFIX):
            if first is None:
                first = plane.name
            elif plane.name != first:
                continue
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    ops = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                           for ev in line.events]
                elif line.name == tracing.MODULES_LINE:
                    modules = [[ev.name, int(ev.start_ns),
                                int(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith(tracing.HOST_PLANE_PREFIX):
            for line in plane.lines:
                spans.extend([ev.name, int(ev.start_ns), int(ev.duration_ns),
                              dict(ev.stats)] for ev in line.events
                             if ev.name.startswith(PREFIXES))
    scoped = _with_scopes(ops, modules, hlo_protos(path))
    return {"spans": spans, "ops": scoped}


def _with_scopes(ops: list, modules: list, protos: dict) -> list:
    """Each op as ``[name, start, dur, scope]``: the instruction's scope in
    the module whose execution the op starts in. A module name the
    metadata lacks falls back to the one module of its base name."""
    base = {}
    for k in protos:
        base.setdefault(k.split("(", 1)[0], []).append(k)
    cache = {}

    def scopes(mod: str) -> dict:
        if mod not in cache:
            same = base.get(mod.split("(", 1)[0], [])
            key = mod if mod in protos else \
                (same[0] if len(same) == 1 else None)
            cache[mod] = op_scopes(protos[key]) if key else {}
        return cache[mod]

    mods = sorted(modules, key=lambda e: e[1])
    out, j = [], 0
    for e in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] <= e[1]:
            j += 1
        inside = j < len(mods) and mods[j][1] <= e[1]
        scope = scopes(mods[j][0]).get(tracing.op_name(e), "") \
            if inside else ""
        out.append(e + [scope])
    return out


def of(run):
    """The program's part of a traced run's trace, read once per run (the
    readers of several metrics share it); None for an untraced run."""
    if run.reduced is None or not run.trace_path:
        return None
    if "program_trace" not in run.extra:
        run.extra["program_trace"] = extract(run.trace_path)
    return run.extra["program_trace"]


def _in_window(events: list, r) -> list:
    return [e for e in events if r.t0 <= e[1] < r.t1]


def spans_named(r, pt: dict, name: str) -> list:
    """Program spans called ``name`` that start in the traced window."""
    return [s for s in _in_window(pt["spans"], r) if s[0] == name]


def scope_of(path: str) -> str:
    """The first of ``SCOPES`` on an op's scope path, '' if none is."""
    parts = path.split("/")
    return next((s for s in SCOPES if s in parts), "")


# ------------------------------------------------------------- reductions
def decode_by_scope(r, pt: dict) -> dict:
    """Device self time (ns) of the decode step's ops by scope ('' for
    none), over the decode steps (``jit_step_impl``) of the window."""
    steps = r.modules_named(readers.DECODE_MODULE)
    ops = readers._inside(_in_window(pt["ops"], r), steps)
    out = {}
    for e, t in tracing.self_times(ops):
        k = scope_of(e[3])
        out[k] = out.get(k, 0) + t
    return out


def paging_share(r, pt: dict):
    """% of the decode steps' device self time in ops under the paging
    scopes; None where no op of the trace carries one."""
    if not any(scope_of(e[3]) in PAGING for e in pt["ops"]):
        return None
    by = decode_by_scope(r, pt)
    total = sum(by.values())
    if not total:
        return None
    return 100.0 * sum(by.get(s, 0) for s in PAGING) / total


def mean_span_ms(r, pt: dict, name: str):
    sp = spans_named(r, pt, name)
    return 1e3 * sum(s[2] for s in sp) / 1e9 / len(sp) if sp else None


def idle_under(r, pt: dict, name: str) -> int:
    """Device-idle ns of the window whose gap's midpoint lies inside a
    program span called ``name`` (so its innermost program span is that
    span or one nested in it)."""
    return tracing.attribute(r.gaps, spans_named(r, pt, name),
                             ignore=()).get(name, 0)


def idle_by_span(r, pt: dict) -> dict:
    """Device-idle ns of the window by the innermost program span that
    covers each gap ('host' where none does)."""
    return tracing.attribute(r.gaps, _in_window(pt["spans"], r), ignore=())


# ---------------------------------------------------------------- readers
def kv_paging_share(run):
    pt = of(run)
    return None if pt is None else paging_share(run.reduced, pt)


def admit_wave_ms(run):
    pt = of(run)
    return None if pt is None else mean_span_ms(run.reduced, pt,
                                                "serve.admit_wave")


def queue_wait_ms(run):
    """Mean seconds, in ms, from submit to the start of the admitting
    wave, over the requests admitted in the traced part of the window
    (the engine's counters as the trace stopped: stopping the profiler
    stalls the harness's loop for seconds, and the requests due meanwhile
    would wait that stall out)."""
    sch = (run.extra.get("trace_stats") or {}).get("scheduler") or {}
    if not sch.get("waited"):
        return None
    return 1e3 * sch["queue_wait_s"] / sch["waited"]


def sync_idle_ms(run):
    """Device-idle ms inside the decode syncs, per sync."""
    pt = of(run)
    if pt is None:
        return None
    n = len(spans_named(run.reduced, pt, "serve.sync"))
    if not n:
        return None
    return idle_under(run.reduced, pt, "serve.sync") / 1e6 / n


def graduation_idle_ms(run):
    """Device-idle ms inside the onboarding polls, per profile
    graduated."""
    pt = of(run)
    if pt is None:
        return None
    n = len(spans_named(run.reduced, pt, "train.graduate"))
    if not n:
        return None
    return idle_under(run.reduced, pt, "train.poll") / 1e6 / n


# -------------------------------------------------------------------- CLI
def summary(path: str) -> dict:
    r = tracing.Reduced(tracing.extract(path))
    pt = extract(path)
    spans = _in_window(pt["spans"], r)
    counts = {}
    for s in spans:
        counts[s[0]] = counts.get(s[0], 0) + 1
    by = decode_by_scope(r, pt)
    return {"window_s": r.window_s, "busy_s": r.busy_s,
            "idle_by_program_span_s": dict(tracing.top(
                idle_by_span(r, pt), 30)),
            "decode_by_scope_s": {k or "(none)": v / 1e9
                                  for k, v in by.items()},
            "decode_steps": len(r.modules_named(readers.DECODE_MODULE)),
            "kv_paging_share": paging_share(r, pt),
            "spans": counts, "spans_per_s": len(spans) / r.window_s}


def small(path: str, at: str, containing: str = "", ms: float = 250.0):
    """The trace from 20 ms before the first span ``at`` (holding a span
    ``containing``, if given) for ``ms``: what the tests read."""
    tr = tracing.extract(path)
    pt = extract(path)
    lo0, hi0 = tracing.window_of(tr["host"])

    def holds(s):
        return not containing or any(
            c[0] == containing and s[1] <= c[1] < s[1] + s[2]
            for c in pt["spans"])

    first = next(s for s in sorted(pt["spans"], key=lambda s: s[1])
                 if s[0] == at and lo0 <= s[1] < hi0 and holds(s))
    lo = first[1] - 20_000_000
    hi = lo + int(ms * 1e6)

    def overlap(evs):
        return [e for e in evs if e[1] < hi and e[1] + e[2] > lo]

    return {"window": [lo, hi],
            "host": [e for e in overlap(tr["host"])
                     if e[0] != "bench_window"],
            "spans": overlap(pt["spans"]),
            "device": {tracing.OPS_LINE: overlap(pt["ops"]),
                       tracing.MODULES_LINE: overlap(
                           tr["device"].get(tracing.MODULES_LINE, []))},
            "device_plane": tr["device_plane"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--small", default="")
    ap.add_argument("--at", default="serve.admit_wave")
    ap.add_argument("--containing", default="")
    ap.add_argument("--ms", type=float, default=250.0)
    args = ap.parse_args(argv)
    if args.small:
        with gzip.open(args.small, "wt") as f:
            json.dump(small(args.trace, args.at, args.containing, args.ms),
                      f)
    else:
        print(json.dumps(summary(args.trace), default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
