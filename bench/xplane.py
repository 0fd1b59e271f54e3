"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy time and idle share over the window,
device time per operation, Pallas kernel time, the NTT kernels' time
and required bytes, idle time by what the host was doing, and by name
the device time of each `he.*` scope, the host time of each `nshedb.*`
span and the time and bytes of the collectives.

Device operations are the events of the "XLA Ops" line of each device
plane (`/device:TPU:<i>`), each named by its HLO instruction text, and
the "XLA Modules" line says which jitted program ran each; on a plane
without those lines (the CPU test trace) the events that carry an
`hlo_op` stat name op and program.  Host spans are the events of the
host thread that holds the benchmark's "window" annotation.  Everything
is clipped to that window.

An op's scope path (its HLO `op_name`, `jit(f)/he.keyswitch/he.ntt/...`)
is the `tf_op` stat of its event's metadata in a chip's trace, which
`jax.profiler.ProfileData` does not show, so it is read from the raw
XSpace proto.  A CPU trace carries none: its `scope_s` is empty.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import math
import os
import re

from .roofline import ntt_required_bytes

WINDOW = "window"
SCOPE = "he."             # device scopes (jax.named_scope in the program)
SPAN = "nshedb."          # host spans (the program's TraceAnnotations)
TF_OP = "tf_op"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_INSTR = re.compile(r"%([\w.-]+) = (\S+?)(?:\{[^}]*\})? ([\w-]+)\(")
_OPCODE = re.compile(r"%([\w.-]+) = (.*?) ([\w-]+)\(")   # tuple results too
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16}


@dataclasses.dataclass
class Summary:
    window_s: float                  # length of the window span
    busy_s: float                    # device busy (union), mean over chips
    devices: int
    op_s: dict                       # device seconds per op, mean over chips
    kernel_s: float                  # Pallas (tpu_custom_call) seconds
    ntt_s: float                     # NTT kernel seconds
    ntt_bytes: int                   # bytes the NTT calls must move
    ntt_calls: int
    idle_s: dict                     # idle seconds by the host span active
    scope_s: dict = dataclasses.field(default_factory=dict)
                                     # device s per he.* scope, mean over chips
    span_s: dict = dataclasses.field(default_factory=dict)
                                     # host s per nshedb.* span name
    collective_s: float = 0.0        # collective ops' device s, mean over chips
    collective_bytes: int = 0        # their result bytes, mean over chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        rank = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(self.op_s), "idle_gaps": rank(self.idle_s)}


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{trace_dir}: {len(files)} xplane files")
    return files[0]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _shapes(text: str) -> list:
    """Array shapes in an HLO instruction's text, result first."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _SHAPE.finditer(text)]


def ntt_call(text: str) -> tuple[int, int, int] | None:
    """(rows, n, limbs) of an NTT kernel call, read from its HLO text:
    the result is (rows, R, L) residues and one operand is the
    (limbs, log2 n, R, L) stage-twiddle table.  None for other ops."""
    shapes = _shapes(text)
    if not shapes or len(shapes[0]) != 3:
        return None
    rows, r, lanes = shapes[0]
    n = r * lanes
    for s in shapes[1:]:
        if len(s) == 4 and s[2:] == (r, lanes) and 1 << s[1] == n:
            return rows, n, s[0]
    return None


def collective(text: str) -> int | None:
    """Result bytes of a collective op from its HLO instruction text, or
    None where its opcode is no collective.  An asynchronous pair counts
    its bytes once: the `-done` half's result, and 0 for the `-start`."""
    m = _OPCODE.match(text)
    if m is None:
        return None
    op = m.group(3)
    if op.removesuffix("-start").removesuffix("-done") not in COLLECTIVES:
        return None
    if op.endswith("-start"):
        return 0
    return sum(_ITEM_BYTES.get(t, 0) * math.prod(int(d) for d in dims.split(",") if d)
               for t, dims in _ARRAY.findall(m.group(2)))


def scopes(op_name: str, text: str = "") -> list:
    """The `he.*` scopes on an op's path, outermost first, each once.  A
    Pallas call is named after its innermost scope (`%he.ntt.1`): where
    its path names none, it counts toward that scope."""
    out = []
    for part in op_name.split("/"):
        if part.startswith(SCOPE) and part not in out:
            out.append(part)
    m = _OPCODE.match(text)
    if not out and m and m.group(1).startswith(SCOPE):
        out.append(re.sub(r"\.\d+$", "", m.group(1)))
    return out


def _tf_op(metadata, stat_names: dict) -> str:
    """The op_name an event's metadata carries as its `tf_op` stat
    (`<op_name>:`), or ""."""
    for st in metadata.stats:
        if stat_names.get(st.metadata_id) == TF_OP:
            value = st.str_value or stat_names.get(st.ref_value, "")
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def _named_ops(path: str, device_prefix: str) -> list:
    """Per device plane, [(start, end, HLO text, op_name)] of its "XLA
    Ops" line, read from the raw XSpace proto; none on a CPU trace."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith(device_prefix):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        named, ops = {}, []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.metadata_id not in named:
                    md = plane.event_metadata[ev.metadata_id]
                    named[ev.metadata_id] = (md.name, _tf_op(md, stat_names))
                start = line.timestamp_ns + ev.offset_ps / 1000
                ops.append((start, start + ev.duration_ps / 1000,
                            *named[ev.metadata_id]))
        out.append(ops)
    return out


def _span_seconds(spans: list, w0: float, w1: float) -> dict:
    """Host seconds per `nshedb.*` span name inside the window: the union
    of its occurrences, so a span counts in each span around it and a
    name nested in itself counts once."""
    by_name = collections.defaultdict(list)
    for s, e, name in spans:
        s, e = max(s, w0), min(e, w1)
        if name.startswith(SPAN) and e > s:
            by_name[name].append((s, e))
    return {name: sum(e - s for s, e in _union(iv)) / 1e9
            for name, iv in by_name.items()}


def _host_spans(planes) -> tuple[tuple[int, int], list]:
    for plane in planes:
        for line in plane.lines:
            evs = list(line.events)
            win = [e for e in evs if e.name == WINDOW]
            if win:
                w = win[0]
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in evs if e is not w]
                return (w.start_ns, w.start_ns + w.duration_ns), spans
    raise ValueError(f"no host span named {WINDOW!r} in the trace")


def _attribute(spans: list, points: list) -> list:
    """Name of the innermost host span containing each point ("window"
    where none does).  Spans of one thread nest, so one sweep with a
    stack does it."""
    order = sorted(range(len(points)), key=points.__getitem__)
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, i = [WINDOW] * len(points), [], 0
    for j in order:
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if stack:
            out[j] = stack[-1][2]
    return out


def _device_ops(plane) -> list:
    """[(start, end, op name, HLO text)] of one device plane."""
    lines = {ln.name: ln for ln in plane.lines}
    out = []
    if OPS_LINE in lines:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(")[0])
                      for e in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        ops = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in lines[OPS_LINE].events)
        i = 0
        for s, e, text in ops:
            while i < len(mods) and mods[i][1] < s:
                i += 1
            module = mods[i][2] if i < len(mods) and mods[i][0] <= s else ""
            m = _INSTR.match(text)
            op = f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else text[:80]
            out.append((s, e, f"{module}/{op}" if module else op, text))
        return out
    for ln in plane.lines:
        for e in ln.events:
            st = _stats(e)
            if "hlo_op" in st:
                module = str(st.get("hlo_module", "")).split("(")[0]
                out.append((e.start_ns, e.start_ns + e.duration_ns,
                            f"{module}/{st['hlo_op']}",
                            " ".join(str(v) for v in st.values())))
    return out


def reduce(path: str, device_prefix: str = "/device:TPU:") -> Summary:
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    (w0, w1), spans = _host_spans(planes)
    devices = [p for p in planes if p.name.startswith(device_prefix)]
    if not devices:
        raise ValueError(f"no plane named {device_prefix}* in the trace")
    busy = kernel = ntt_s = 0.0
    ntt_bytes = ntt_calls = 0
    op_s = collections.defaultdict(float)
    idle = collections.defaultdict(float)
    for plane in devices:
        ops = []
        for s, e, name, text in _device_ops(plane):
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ops.append((s, e))
            sec = (e - s) / 1e9
            op_s[name] += sec
            if KERNEL in text:
                kernel += sec
                call = ntt_call(text)
                if call is not None:
                    ntt_s += sec
                    ntt_bytes += ntt_required_bytes(*call)
                    ntt_calls += 1
        merged = _union(ops)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        names = _attribute(spans, [(s + e) / 2 for s, e in gaps])
        for (s, e), name in zip(gaps, names):
            idle[name] += (e - s) / 1e9
    scope_s = collections.defaultdict(float)
    coll_s, coll_bytes = 0.0, 0
    for ops in _named_ops(path, device_prefix):
        for s, e, text, op_name in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            sec = (e - s) / 1e9
            for scope in scopes(op_name, text):
                scope_s[scope] += sec
            nbytes = collective(text)
            if nbytes is not None:
                coll_s += sec
                coll_bytes += nbytes
    nd = len(devices)
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy / nd, devices=nd,
        op_s={k: v / nd for k, v in op_s.items()}, kernel_s=kernel / nd,
        ntt_s=ntt_s / nd, ntt_bytes=ntt_bytes // nd, ntt_calls=ntt_calls // nd,
        idle_s={k: v / nd for k, v in idle.items()},
        scope_s={k: v / nd for k, v in scope_s.items()},
        span_s=_span_seconds(spans, w0, w1),
        collective_s=coll_s / nd, collective_bytes=coll_bytes // nd)
