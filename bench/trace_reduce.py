"""Reduce a `jax.profiler` trace (`.xplane.pb`) to the benchmark's device
numbers.

What it gives, over the window the harness marked with its `bench.window`
annotation:

* the device's busy time: the union of the intervals in which an XLA
  operation ran on a device, averaged over the devices seen;
* device time per operation, grouped by the operation's JAX name path
  (`op_name` metadata, e.g. `jit(s1_fn)/jit(ring_matmul)/dot_general`);
* the device time, bytes and operations of the ring products: every
  operation whose name path holds the kernel marker (`ring_matmul`), with
  the work of the exact ring product it contains (`work.ring_matmul_work`);
* the longest idle gaps, each labelled by the host spans that covered it:
  the harness's own `bench.*` annotations and, where given, the program's
  `obs` spans put on the same clock.

Two trace layouts are read. On a TPU, a `/device:TPU:n` plane holds an
`XLA Ops` line whose events are named by the HLO instruction's text
(`%fusion.50 = (s8[..]..) fusion(...)`), and an `XLA Modules` line with one
event per program launch. On the CPU, operations are host events that carry
`hlo_op` and `hlo_module` statistics. Either way the name path comes from
the compiled programs' HLO text, which the caller passes in: the trace
itself does not carry it.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import work

WINDOW_SPAN = "bench.window"

_META = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def _split_instr(text: str):
    """'%name = <shape> opcode(args...)...' -> (name, shape, opcode, rest).
    The shape is a tuple in parentheses or one token; None when `text` is
    not an instruction."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    m = re.match(r"%?([\w.\-]+) = ", text)
    if not m:
        return None
    rest = text[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    m2 = re.match(r"([\w\-]+)\(", rest)
    if not m2:
        return None
    return m.group(1), shape, m2.group(1), rest[m2.end() - 1:]


def _dims(shape: str) -> list:
    """Element counts of each array in a shape text ('s8[8,512]{..}' or a
    tuple of them)."""
    out = []
    for dims in re.findall(r"[a-z]+\d*\[([\d,]*)\]", shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n)
    return out


def _operands(rest: str) -> list:
    """Operand names of an instruction's argument list."""
    depth, i = 0, 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
    return re.findall(r"%([\w.\-]+)", rest[1:i])


class HloIndex:
    """Name path and ring-product work of every instruction of some
    compiled programs, keyed two ways: by (name, shape, opcode), as a TPU
    trace names its events, and by (module, name), as a CPU trace does."""

    def __init__(self, texts=(), marker: str = "ring_matmul"):
        self.marker = marker
        self.by_text: dict = {}
        self.by_module: dict = {}
        for t in texts:
            self.add(t)

    def add(self, text: str) -> None:
        module = None
        # computation -> [(name, shape, opcode, rest, name path)]
        comps: dict = {}
        cur = None
        for line in text.splitlines():
            s = line.strip()
            if s.startswith("HloModule "):
                module = s.split()[1].rstrip(",")
                continue
            if cur is None:
                m = _COMP.match(s)
                if m and "=" not in s.split("(")[0]:
                    cur = m.group(1)
                    comps[cur] = []
                continue
            if s == "}":
                cur = None
                continue
            parts = _split_instr(s)
            if parts is None:
                continue
            meta = _META.search(s)
            comps[cur].append(parts + (meta.group(1) if meta else "",))
        fused = set()
        for instrs in comps.values():
            for name, shape, op, rest, path in instrs:
                if op == "fusion":
                    fused.update(_CALLS.findall(rest))

        def comp_work(comp: str, seen=()) -> work.Work:
            total = work.Work()
            shapes = {i[0]: i[1] for i in comps.get(comp, [])}
            for name, shape, op, rest, path in comps.get(comp, []):
                total += instr_work(name, shape, op, rest, path, shapes,
                                    seen + (comp,))
            return total

        def instr_work(name, shape, op, rest, path, shapes, seen):
            if op == "fusion":
                return sum((comp_work(c, seen) for c in _CALLS.findall(rest)
                            if c not in seen), work.Work())
            if op in ("dot", "convolution") and self.marker in path:
                ins = _operands(rest)
                if len(ins) >= 2 and ins[0] in shapes and ins[1] in shapes:
                    o, lhs, rhs = (_dims(shape), _dims(shapes[ins[0]]),
                                   _dims(shapes[ins[1]]))
                    if len(o) == len(lhs) == len(rhs) == 1:
                        return work.ring_matmul_from_digit_dot(
                            lhs[0], rhs[0], o[0])
            return work.Work()

        for comp, instrs in comps.items():
            if comp in fused:
                continue
            shapes = {i[0]: i[1] for i in instrs}
            for name, shape, op, rest, path in instrs:
                w = instr_work(name, shape, op, rest, path, shapes, (comp,))
                entry = (path, w)
                self.by_text[(name, shape, op)] = entry
                if module is not None:
                    self.by_module[(module, name)] = entry

    def lookup(self, event_name: str, stats: dict):
        """(name path, Work) of one device event, or ('', Work()) when the
        programs given do not hold it."""
        if "hlo_op" in stats:
            key = (stats.get("hlo_module"), stats["hlo_op"])
            return self.by_module.get(key, ("", work.Work()))
        parts = _split_instr(event_name)
        if parts is None:
            return "", work.Work()
        return self.by_text.get(parts[:3], ("", work.Work()))


def _stats(event) -> dict:
    return {str(k): v for k, v in event.stats}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def read_trace(pd):
    """(device op events, host spans, profile start in epoch ns).

    A device op event is (device, start_ns, end_ns, name, stats, module);
    a host span is (name, start_ns, end_ns, thread)."""
    ops, spans, t0_epoch = [], [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if str(k) == "profile_start_time":
                    t0_epoch = int(v)
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and any(
                ln.name == "XLA Ops" for ln in lines):
            modules = []
            for ln in lines:
                if ln.name == "XLA Modules":
                    modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name.split("(")[0])
                                     for e in ln.events)
            starts = [m[0] for m in modules]
            for ln in lines:
                if ln.name != "XLA Ops":
                    continue
                for e in ln.events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = modules[i][2] if i >= 0 \
                        and modules[i][1] >= e.start_ns else ""
                    ops.append((plane.name, e.start_ns,
                                e.start_ns + e.duration_ns, e.name,
                                _stats(e), mod))
            continue
        if plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns, ln.name))
                        continue
                    if e.duration_ns <= 0:
                        continue
                    st = _stats(e)
                    if "hlo_op" in st:
                        ops.append(("cpu:" + str(st.get("device_ordinal", 0)),
                                    e.start_ns, e.start_ns + e.duration_ns,
                                    e.name, st, st.get("hlo_module", "")))
    return ops, spans, t0_epoch


def _innermost(spans, t) -> str:
    """Name of the latest-starting span that covers time t, or '-'."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "-"


def reduce(pd, hlo: HloIndex, peaks: dict | None = None,
           obs_spans=(), top: int = 10) -> dict:
    """The window's device numbers; see the module docstring.

    `peaks` is the device's row of peaks.json (None: no roofline).
    `obs_spans` are the program's own spans as dicts with epoch-µs `ts`
    and `dur` (obs.trace Tracer events); they label gaps when the trace
    states its start time."""
    ops, spans, t0_epoch = read_trace(pd)
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s[1] for s in win), max(s[2] for s in win)
    window_ns = hi - lo
    devices = sorted({o[0] for o in ops})
    per_dev = defaultdict(list)
    for o in ops:
        per_dev[o[0]].append((o[1], o[2]))
    busy_ns = 0.0
    unions = {}
    for dev, iv in per_dev.items():
        unions[dev] = _union(_clip(iv, lo, hi))
        busy_ns += sum(e - s for s, e in unions[dev])
    busy_ns /= max(1, len(devices))

    per_op = defaultdict(float)
    ring = {"seconds": 0.0, "bytes": 0.0, "ops": 0.0, "roofline_s": 0.0,
            "events": 0, "bytes_bound": 0, "ops_bound": 0}
    for dev, s, e, name, st, mod in ops:
        if e <= lo or s >= hi:
            continue
        dur = (min(e, hi) - max(s, lo)) * 1e-9
        path, w = hlo.lookup(name, st)
        if not path:
            parts = _split_instr(name)
            path = f"{mod}/{st.get('hlo_op') or (parts[0] if parts else name)}"
        per_op[path] += dur
        if hlo.marker in path or w.ops:
            ring["seconds"] += dur
            ring["events"] += 1
            ring["bytes"] += w.bytes
            ring["ops"] += w.ops
            if peaks is not None and w.ops:
                tb = w.bytes / peaks["hbm_bytes_per_s"]
                to = w.ops / peaks["int8_ops_per_s"]
                ring["roofline_s"] += max(tb, to)
                ring["bytes_bound" if tb >= to else "ops_bound"] += 1

    # idle gaps of the first device, each labelled by the innermost harness
    # span and the innermost program span over its middle
    bench_spans = [(n, s, e) for n, s, e, _t in spans if n != WINDOW_SPAN]
    prog_spans = []
    if t0_epoch is not None:
        for ev in obs_spans:
            if ev.get("ph") == "X":
                s = ev["ts"] * 1000 - t0_epoch
                prog_spans.append((ev["name"], s, s + ev["dur"] * 1000))
    gaps = []
    if devices:
        u = unions[devices[0]]
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        label = "|".join(_innermost(group, mid)
                         for group in (bench_spans, prog_spans))
        labelled.append([label, (e - s) * 1e-9])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "devices": devices,
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": labelled,
        "ring": ring,
    }
