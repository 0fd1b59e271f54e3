"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy time and idle share over the window,
device time per operation, Pallas kernel time, the NTT kernels' time
and required bytes, and idle time by what the host was doing.

Device operations are the events of the "XLA Ops" line of each device
plane (`/device:TPU:<i>`), each named by its HLO instruction text, and
the "XLA Modules" line says which jitted program ran each; on a plane
without those lines (the CPU test trace) the events that carry an
`hlo_op` stat name op and program.  Host spans are the events of the
host thread that holds the benchmark's "window" annotation.  Everything
is clipped to that window.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

from .roofline import ntt_required_bytes

WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_INSTR = re.compile(r"%([\w.-]+) = (\S+?)(?:\{[^}]*\})? ([\w-]+)\(")


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
    nd = len(devices)
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy / nd, devices=nd,
        op_s={k: v / nd for k, v in op_s.items()}, kernel_s=kernel / nd,
        ntt_s=ntt_s / nd, ntt_bytes=ntt_bytes // nd, ntt_calls=ntt_calls // nd,
        idle_s={k: v / nd for k, v in idle.items()})
