"""Reduce a JAX profiler trace to what the per-layer metrics read.

A traced run brackets its measured window with the host annotation
``chipbench.window``.  From the ``.xplane.pb`` the profiler writes this
module takes, for each chip the cell uses:

  * busy time: the union of the intervals in which an operation ran on
    the chip ("XLA Ops" line), clipped to the window;
  * each operation's self time (its duration less the operations nested
    in it, e.g. a ``while`` around its body), summed by short name;
  * every kernel event (a custom call) with the HBM bytes its operands
    and results need, read from the shapes in the event's HLO text;
  * the idle gaps between busy intervals, each put down to the innermost
    host event that spans its middle.

Host and device events share the profiler's clock, so the program's own
spans, mirrored into the trace as annotations, line up with the device.
"""
from __future__ import annotations

import glob
import heapq
import os
import re

OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "chipbench.window"
# gaps at least this long are listed one by one (stall attribution)
LONG_GAP_S = 0.05

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def short_name(text: str) -> str:
    """``%eval_population_kernel.1 = u32[...] custom-call(...)`` →
    ``eval_population_kernel``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def custom_call_bytes(text: str) -> "int | None":
    """Least HBM traffic of one kernel call: every operand read once and
    every result written once, at the shapes in the op's HLO text.
    None where the text is not a custom call."""
    head, sep, rest = text.partition("custom-call(")
    if not sep:
        return None
    results = _SHAPE.findall(head.split("=", 1)[-1])
    depth, end = 1, 0
    for end, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    operands = _SHAPE.findall(rest[:end])
    return sum(_shape_bytes(*s) for s in results + operands)


def start(trace_dir: str) -> None:
    """Start the profiler: device activity and host annotations, but not
    every Python call, which would slow the host it measures."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """(name, self_ns) for properly nested events on one line."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    selfs = [dur for _, dur, _ in events]
    stack = []  # indices of open events
    for i, (start, dur, _) in enumerate(events):
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        stack.append(i)
    return [(events[i][2], max(selfs[i], 0)) for i in range(len(events))]


def _window(profile):
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_ANNOTATION:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def reduce_trace(path: str, n_chips: int) -> dict:
    """Summary of one traced window; see the module docstring."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    window = _window(profile)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_ANNOTATION!r} annotation")
    w0, w1 = window
    host = []  # (start, end, name) of every host event in the window
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > w0 and s < w1 and ev.name != WINDOW_ANNOTATION:
                    host.append((s, e, ev.name))
    chips, op_self, kernels, gaps = [], {}, {}, []
    for i in range(n_chips):
        plane = next((p for p in profile.planes
                      if p.name == f"/device:TPU:{i}"), None)
        line = None if plane is None else next(
            (ln for ln in plane.lines if ln.name == OPS_LINE), None)
        events = []
        for ev in (line.events if line is not None else ()):
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                events.append((s, e - s, ev.name))
        busy = _union((s, s + d) for s, d, _ in events)
        busy_ns = sum(e - s for s, e in busy)
        chips.append({"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9})
        for text, self_ns in _self_times(events):
            name = short_name(text)
            op_self[name] = op_self.get(name, 0) + self_ns
        for _, dur, text in events:
            nbytes = custom_call_bytes(text)
            if nbytes is not None:
                k = kernels.setdefault(short_name(text),
                                       {"count": 0, "seconds": 0.0,
                                        "bytes": 0})
                k["count"] += 1
                k["seconds"] += dur / 1e9
                k["bytes"] += nbytes
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((gs, ge, i))
    gaps_by_host, long_gaps = {}, []
    host.sort()
    active, j = [], 0  # heap of (length, end, name) of host events begun
    for gs, ge, chip in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (gs + ge) / 2
        while j < len(host) and host[j][0] <= mid:
            s, e, name = host[j]
            heapq.heappush(active, (e - s, e, name))
            j += 1
        while active and active[0][1] < mid:
            heapq.heappop(active)  # ended before this gap: never again
        name = active[0][2] if active else "no host span"
        gaps_by_host[name] = gaps_by_host.get(name, 0) + (ge - gs) / n_chips
        if (ge - gs) / 1e9 >= LONG_GAP_S:
            long_gaps.append({
                "chip": chip, "at_s": (gs - w0) / 1e9,
                "length_s": (ge - gs) / 1e9,
                "host": sorted({n for _, e, n in active if e >= mid}),
            })
    top = lambda d: [[k, v / 1e9] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(c["busy_s"] for c in chips) / n_chips,
        "chips": chips,
        "kernels": kernels,
        "device_ops": top(op_self),
        "idle_gaps": top(gaps_by_host),
        "long_gaps": long_gaps,
        "host_events_over": [
            {"name": name, "at_s": (s - w0) / 1e9, "length_s": (e - s) / 1e9}
            for s, e, name in host if (e - s) / 1e9 >= LONG_GAP_S
        ],
    }
