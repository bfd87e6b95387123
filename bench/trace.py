"""Reduce a `--trace 1` profile to what the per-layer metrics read.

The profile is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`. Device planes are named `/device:<KIND>:<n>`;
their "XLA Ops" line holds one event per operation run on the device.
Host planes hold the benchmark's `TraceAnnotation`s (names starting with
`bench.`) and JAX's own host events. All event times share one clock.

The window is the host span named `bench.window`. Out of it come:
- busy seconds: the union of device-op intervals inside the window,
  averaged over the chips the cell uses (a chip that ran nothing counts
  as idle throughout);
- device self time per operation (an operation's time less the
  operations nested in it on its line, as a loop's body ops are in the
  loop), keyed `<program>:<op>`: the enclosing event of the "XLA Modules"
  line without its `(<hash>)`, and the op's HLO name without the
  trailing `.<n>` that XLA gives each instance;
- the intervals of operations whose name starts with a given prefix (a
  kernel's events);
- the idle gaps (window minus busy), each named by the innermost `bench.`
  host span over its midpoint and, after `>`, the innermost other host
  event there on the same thread.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTANCE = re.compile(r"\.\d+$")
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # mean over devices used
    devices: int
    op_seconds: dict = field(default_factory=dict)     # base name -> s
    op_events: dict = field(default_factory=dict)      # base name -> count
    idle_gaps: list = field(default_factory=list)      # [(name, s)]

    def kernel_seconds(self, prefix: str) -> float:
        return sum(s for n, s in self.op_seconds.items()
                   if n.rpartition(":")[2].startswith(prefix))

    def kernel_events(self, prefix: str) -> int:
        return sum(c for n, c in self.op_events.items()
                   if n.rpartition(":")[2].startswith(prefix))

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_seconds.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        total = defaultdict(float)
        for name, s in self.idle_gaps:
            total[name] += s
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]


def base_name(op: str) -> str:
    """`%fusion.12 = f32[8] fusion(...)` or `fusion.12` -> `fusion`."""
    if op.startswith("%"):
        op = op[1:].split(" = ", 1)[0]
    return _INSTANCE.sub("", op)


def self_times(events) -> list:
    """(name, start, end, self time) of each event of one line: its time
    less the part that later-starting events inside it cover."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s]
        if stack:
            top = stack[-1]
            top[3] -= min(e, top[2]) - s
            if e > top[2]:              # overlaps its end: not nested
                stack.pop()
        stack.append(rec)
        out.append(rec)
    return [(n, s, e, t) for n, s, e, t in out]


def union(intervals) -> list:
    """Merge (start, end) intervals; returns sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(trace_dir))


def reduce(profile, chips: int = 1) -> TraceSummary:
    host_lines, device_planes = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            host_lines.extend(list(_events(line)) for line in plane.lines)
    window = [(line, s, e) for line in host_lines for n, s, e in line
              if n == WINDOW]
    if not window:
        raise ValueError(f"no host span {WINDOW!r} in the trace")
    thread, lo, hi = window[0]          # the benchmark's own thread

    op_seconds, op_events = defaultdict(float), defaultdict(int)
    busy_per_device = []
    for plane in device_planes:
        lines = {line.name: list(_events(line)) for line in plane.lines}
        modules = sorted((s, e, _HASH.sub("", n))
                         for n, s, e in lines.get(MODULES_LINE, ()))
        spans = []
        for name, s, e, own in self_times(
                [ev for ev in lines.get(OPS_LINE, ())
                 if _clip(ev[1], ev[2], lo, hi)]):
            c = _clip(s, e, lo, hi)
            spans.append(c)
            mod = _enclosing(modules, s)
            key = base_name(name) if mod is None else \
                f"{mod}:{base_name(name)}"
            op_seconds[key] += own * (c[1] - c[0]) / (e - s) * 1e-9
            op_events[key] += 1
        if spans:
            busy_per_device.append(union(spans))
    busy = [sum(e - s for s, e in u) for u in busy_per_device]
    gaps = []                           # (start, end) of each idle stretch
    idle = max(chips - len(busy_per_device), 0)
    for u in busy_per_device + [[]] * max(idle, not busy_per_device):
        t = lo
        for s, e in u + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
    n = max(len(busy_per_device), chips)
    names = _gap_names(thread, [(s + e) / 2 for s, e in gaps])
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=sum(busy) / n * 1e-9,
                        devices=len(busy_per_device),
                        op_seconds=dict(op_seconds),
                        op_events=dict(op_events),
                        idle_gaps=[(g, (e - s) / n * 1e-9)
                                   for g, (s, e) in zip(names, gaps)])


def _enclosing(modules, t: float):
    """The program (module event) running at device time `t`."""
    for s, e, name in modules:
        if s <= t < e:
            return name
        if s > t:
            return None
    return None


def _gap_names(line, mids) -> list:
    """Name each time in `mids` by the host events of `line` (one thread,
    so its events nest) that contain it: the innermost `bench.` span and,
    after `>`, the innermost other event inside that span."""
    events = sorted(line, key=lambda x: (x[1], -x[2]))
    names = [None] * len(mids)
    stack, i = [], 0
    for k in sorted(range(len(mids)), key=mids.__getitem__):
        t = mids[k]
        while i < len(events) and events[i][1] <= t:
            while stack and stack[-1][2] <= events[i][1]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        open_ = [ev for ev in stack if ev[2] > t]
        bench = [ev for ev in open_
                 if ev[0].startswith("bench.") and ev[0] != WINDOW]
        if not bench:
            names[k] = "outside bench spans"
            continue
        inner = open_[-1]
        names[k] = bench[-1][0] if inner[0].startswith("bench.") \
            else f"{bench[-1][0]} > {inner[0]}"
    return names
