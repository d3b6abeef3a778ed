"""Profiler capture and the reduction from a device trace to busy time, idle
gaps attributed to host spans, and per-operation device time.

``Window`` keeps the profiler's XSpace file; ``extract`` turns it into a
small plain structure (``{"device": {line: [events]}, "host": [spans]}``,
times in nanoseconds on the profiler's clock), and everything after that
is arithmetic on lists, tested on a recorded trace in ``tests/bench/``.

An event is ``[name, start_ns, duration_ns]``; a device op's name is its HLO
instruction (``%copy.42 = bf16[...] copy(...)``), a module's is
``jit_<function>(<fingerprint>)``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time

# host spans the harness writes around its calls into the program
SPANS = ("bench_window", "admit", "decode_window", "await_arrival",
         "drain", "gang_step", "lifecycle")
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Window:
    """A traced stretch of a run: the profiler on (Python tracer off), and
    the harness's ``bench_window`` span around the stretch, so the
    reduction knows its bounds on the profiler's clock. After ``stop()``
    (which returns the host time it stopped at), ``path`` is the XSpace
    file."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.path = None
        self.on = False
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = span("bench_window")
        self._span.__enter__()
        self.on = True

    def stop(self) -> float:
        import jax

        self._span.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False
        files = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.path = files[-1] if files else None
        return t


def extract(path: str, spans=SPANS) -> dict:
    """Device lines and the harness's host spans from an XSpace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    first = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if first is None:
                first = plane.name
            elif plane.name != first:
                continue          # one chip: the first device plane
            for line in plane.lines:
                device.setdefault(line.name, []).extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events if ev.name in spans)
    return {"device": device, "host": host, "device_plane": first}


# --------------------------------------------------------------- reduction
def window_of(host: list, name: str = "bench_window") -> tuple:
    """(start_ns, end_ns) of the traced window: the harness's span."""
    w = [e for e in host if e[0] == name]
    if not w:
        raise ValueError(f"no {name!r} span in the trace")
    return w[0][1], w[0][1] + w[0][2]


def intervals(events: list, t0: int, t1: int) -> list:
    """Sorted, merged [start, end) intervals of events, clipped to
    [t0, t1)."""
    iv = sorted((max(e[1], t0), min(e[1] + e[2], t1)) for e in events
                if e[1] < t1 and e[1] + e[2] > t0)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: list, t0: int, t1: int) -> int:
    return sum(e - s for s, e in intervals(events, t0, t1))


def idle_gaps(events: list, t0: int, t1: int) -> list:
    """[start, end) stretches of [t0, t1) in which no event runs."""
    out, cur = [], t0
    for s, e in intervals(events, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def attribute(gaps: list, host: list, ignore=("bench_window",)) -> dict:
    """Idle nanoseconds by the innermost host span (the shortest one) that
    covers each gap's midpoint; ``host`` if no span covers it."""
    spans = sorted((e for e in host if e[0] not in ignore),
                   key=lambda e: e[2])
    out = {}
    for s, e in gaps:
        mid = (s + e) // 2
        name = next((sp[0] for sp in spans if sp[1] <= mid < sp[1] + sp[2]),
                    "host")
        out[name] = out.get(name, 0) + (e - s)
    return out


def op_name(ev) -> str:
    """The HLO instruction name of a device op event (``%fusion.12 = ...``
    -> ``fusion.12``; module events keep their name)."""
    head = ev[0].split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def op_kind(ev) -> str:
    """The HLO opcode of a device op event (``custom-call``, ``copy``,
    ``fusion``, ``while``, ...), or '' where the name carries none."""
    if " = " not in ev[0]:
        return ""
    rhs = ev[0].split(" = ", 1)[1]
    # result type, then the opcode before the operand list
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rhs[i + 1:].split("(", 1)[0]
    return ""


def _operand_list(ev) -> list:
    """The operand list of a device op, split at its top-level commas:
    each entry ``type{layout} %name``."""
    if " = " not in ev[0] or "(" not in ev[0].split(" = ", 1)[1]:
        return []
    body = ev[0].split(" = ", 1)[1]
    head = op_kind(ev)
    args = body.split(head + "(", 1)[1] if head else ""
    depth, out, cur = 0, [], ""
    for ch in args:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append(cur.strip())
            cur = ""
            continue
        cur += ch
    if cur.strip():
        out.append(cur.strip())
    return out


def operands(ev) -> list:
    """Instruction names of a device op's operands, in order."""
    out = []
    for a in _operand_list(ev):
        m = re.search(r"%([\w.\-]+)\s*$", a)
        if m:
            out.append(m.group(1))
    return out


def operands_in_vmem(ev) -> list:
    """For each operand, whether its layout puts it in the TPU's vector
    memory (memory space 1, ``S(1)``): an operand there was read from HBM
    by the op that produced it, not by this op."""
    return [bool(re.search(r"\{[^{}]*S\(1\)[^{}]*\}", a.rsplit("%", 1)[0]))
            for a in _operand_list(ev) if "%" in a]


def op_group(ev) -> str:
    """Breakdown key: the instruction name without its number, and its
    result type (``copy bf16[24,16,1024,16,64]``)."""
    base = re.sub(r"(\.\d+|\.clone)+$", "", op_name(ev))
    rhs = ev[0].split(" = ", 1)[1] if " = " in ev[0] else ""
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return (base + " " + shape).strip()[:120]


def self_times(events: list) -> list:
    """``[(event, self_ns)]``: each event's duration less the time of the
    events nested inside it on the same line (a ``while`` op holds its
    body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for e in evs:
        while stack and stack[-1][0][1] + stack[-1][0][2] <= e[1]:
            stack.pop()
        rec = [e, e[2]]
        if stack:
            stack[-1][1] -= e[2]
        out.append(rec)
        stack.append(rec)
    return [(e, max(t, 0)) for e, t in out]


def top(totals: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]`` of the ``n`` largest, largest first."""
    return [[k, v / 1e9] for k, v in sorted(totals.items(),
                                            key=lambda kv: -kv[1])[:n]]


class Reduced:
    """A traced window, reduced: what the per-layer readers read."""

    def __init__(self, tr: dict):
        self.raw = tr
        self.t0, self.t1 = window_of(tr["host"])
        self.ops = tr["device"].get(OPS_LINE, [])
        self.modules = tr["device"].get(MODULES_LINE, [])
        self.window_s = (self.t1 - self.t0) / 1e9
        self.busy_s = busy_ns(self.ops, self.t0, self.t1) / 1e9
        self.gaps = idle_gaps(self.ops, self.t0, self.t1)

    def in_window(self, events: list) -> list:
        return [e for e in events if self.t0 <= e[1] < self.t1]

    def modules_named(self, prefix: str) -> list:
        """Executions of jitted programs whose name starts with ``prefix``
        (``jit_<python function name>``)."""
        return [e for e in self.in_window(self.modules)
                if e[0].startswith(prefix)]

    def breakdown(self) -> dict:
        """Device self time by op group, and idle time by host span."""
        own = {}
        for e, t in self_times(self.in_window(self.ops)):
            k = op_group(e)
            own[k] = own.get(k, 0) + t
        return {"device_ops": top(own),
                "idle_gaps": top(attribute(self.gaps, self.raw["host"]))}

    def staged(self, kernels: list, which) -> list:
        """For each kernel launch, its device time plus that of the ops
        that produced its operands number ``which`` into vector memory
        (``operands_in_vmem``): the HBM reads of those operands happen in
        their producer (on the TPU, a fusion that slices a per-row record
        out of HBM into VMEM), so the launch's whole cost is the kernel's
        time and theirs. An operand's producer is the last execution of
        its instruction that started before the launch. Operands the
        kernel reads from HBM itself add nothing."""
        import bisect

        by_name = {}
        for e in self.ops:
            by_name.setdefault(op_name(e), []).append(e)
        for evs in by_name.values():
            evs.sort(key=lambda e: e[1])
        starts = {k: [e[1] for e in v] for k, v in by_name.items()}
        out = []
        for k in kernels:
            ns = k[2]
            args, vmem = operands(k), operands_in_vmem(k)
            for i in which:
                if i >= len(args) or not vmem[i] or args[i] not in by_name:
                    continue
                j = bisect.bisect_right(starts[args[i]], k[1]) - 1
                if j >= 0:
                    ns += by_name[args[i]][j][2]
            out.append(ns)
        return out

    def kernels(self, names) -> list:
        """Pallas kernel launches: custom calls whose instruction is named
        after one of ``names`` (the jitted wrapper of the kernel)."""
        return [e for e in self.in_window(self.ops)
                if op_kind(e) == "custom-call"
                and re.sub(r"\.\d+$", "", op_name(e)) in names]
