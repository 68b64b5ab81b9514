"""From a profiler trace to the numbers the per-layer metrics read.

A traced part of a run is bracketed by the host span ``bench.window``.
The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX's own ``ProfileData``:

- device planes (``/device:TPU:<n>``): the line of XLA ops gives each
  operation's interval on that chip (loops appear there too, around the
  ops they run: sums and shares use the ops that contain no other), the
  line of XLA modules each program's run;
- the host plane: the benchmark's ``bench.*`` spans (TraceAnnotation), on
  the same clock.

From these: busy time (the union of op intervals inside the window,
averaged over chips), idle gaps (holes in that union, each labelled by the
innermost ``bench.*`` span open over it), summed device time of the ops
whose names match (kernels), each module run with the ops inside it, and
the exposed time of collectives (a collective running while no compute op
does on that chip).
"""
from __future__ import annotations

import pathlib
import re
import shutil
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
# HLO collectives by op name (the async halves included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|ppermute|send|recv)", re.I)


def start(tdir: pathlib.Path) -> None:
    import jax
    shutil.rmtree(tdir, ignore_errors=True)
    tdir.mkdir(parents=True)
    jax.profiler.start_trace(str(tdir))


def stop(tdir: pathlib.Path) -> "Reduced":
    import jax
    jax.profiler.stop_trace()
    try:
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {tdir}")
        return reduce_file(files[-1])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


@dataclass
class Event:
    name: str         # the HLO instruction as the trace prints it
    start: int        # ns
    end: int

    @property
    def op(self) -> str:
        """The instruction's own name: ``fusion.3`` of ``%fusion.3 = ...``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Device:
    index: int
    ops: list          # [Event] that contain no other op, sorted by start
    modules: list      # [Event]
    all_ops: list = field(default_factory=list)   # containers (loops) too


@dataclass
class Reduced:
    window: tuple                      # (start_ns, end_ns)
    devices: list                      # [Device]
    spans: list                        # host [Event] named bench.*

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # --- busy and idle -------------------------------------------------
    def busy_s(self) -> float:
        """Seconds some op ran, inside the window, averaged over chips."""
        if not self.devices:
            return 0.0
        return sum(_length(union(_clip(d.all_ops, self.window)))
                   for d in self.devices) / len(self.devices) / 1e9

    def idle_gaps(self, top: int = 10) -> list:
        """Longest holes in chip 0's busy union inside the window, as
        [label, seconds], labelled by the innermost host span over each."""
        if not self.devices:
            return []
        busy = union(_clip(self.devices[0].all_ops, self.window))
        gaps, t = [], self.window[0]
        for s, e in busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label(s, e), (e - s) / 1e9] for s, e in gaps[:top]]

    def label(self, s: int, e: int) -> str:
        """The innermost bench span covering most of [s, e)."""
        best, key = "none", None
        for sp in self.spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov <= 0:
                continue
            k = (ov, -(sp.end - sp.start))
            if key is None or k > key:
                best, key = sp.name[len(SPAN_PREFIX):], k
        return best

    # --- ops -------------------------------------------------------------
    def op_seconds(self, match) -> float:
        """Summed device time of ops whose name or stats match, inside the
        window, averaged over chips.  ``match(event) -> bool``."""
        if not self.devices:
            return 0.0
        tot = 0
        for d in self.devices:
            tot += sum(e.end - e.start for e in _clip(d.ops, self.window)
                       if match(e))
        return tot / len(self.devices) / 1e9

    def top_ops(self, top: int = 10) -> list:
        """[instruction, seconds] of the ops (not loops around them) that
        took most device time on chip 0; an instruction is named with the
        start of its HLO text, its shape included."""
        if not self.devices:
            return []
        agg: dict = {}
        for e in _clip(self.devices[0].ops, self.window):
            k = e.name[:120]
            agg[k] = agg.get(k, 0) + (e.end - e.start)
        items = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in items]

    def module_runs(self, device: int = 0) -> list:
        """[(module Event, [op Events inside it])] inside the window."""
        d = self.devices[device]
        ops = _clip(d.ops, self.window)
        out, j = [], 0
        for m in _clip(d.modules, self.window):
            while j < len(ops) and ops[j].start < m.start:
                j += 1
            inside, k = [], j
            while k < len(ops) and ops[k].start < m.end:
                inside.append(ops[k])
                k += 1
            out.append((m, inside))
        return out

    # --- collectives -----------------------------------------------------
    def collective_exposed_s(self, is_collective=None) -> float:
        """Seconds per chip in which a collective runs and no other op
        does, inside the window, averaged over chips."""
        is_collective = is_collective or (
            lambda e: bool(COLLECTIVE.match(e.op)))
        if not self.devices:
            return 0.0
        tot = 0
        for d in self.devices:
            ops = _clip(d.ops, self.window)
            coll = union([e for e in ops if is_collective(e)])
            comp = union([e for e in ops if not is_collective(e)])
            tot += _length(coll) - _length(intersect(coll, comp))
        return tot / len(self.devices) / 1e9


def _clip(events, window):
    s0, e0 = window
    out = []
    for e in events:
        s, t = max(e.start, s0), min(e.end, e0)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events) -> list:
    """Sorted disjoint [(start, end)] covering the events' intervals."""
    iv = sorted((e.start, e.end) if isinstance(e, Event) else e
                for e in events)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(iv) -> int:
    return sum(e - s for s, e in iv)


def _events(line):
    out = []
    for e in line.events:
        s = int(e.start_ns)
        out.append(Event(e.name, s, s + int(e.duration_ns)))
    out.sort(key=lambda ev: ev.start)
    return out


def split_nested(events) -> tuple:
    """(leaves, all): the trace's op line nests ops inside the loops and
    calls that run them; a leaf is an op that contains no other."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    container, stack = set(), []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end:
            container.add(stack[-1])
        stack.append(i)
    leaves = [e for i, e in enumerate(order) if i not in container]
    return leaves, order


def reduce_planes(planes) -> Reduced:
    """Reduce ``ProfileData.planes`` (or objects shaped like them)."""
    devices, spans = [], []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            leaves, every = split_nested(ops)
            mods = (_events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [])
            devices.append(Device(int(m.group(1)), leaves, mods, every))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                spans += [e for e in _events(ln)
                          if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.index)
    win = [s for s in spans if s.name == SPAN_PREFIX + "window"]
    if not win:
        raise ValueError("trace has no bench.window span")
    w = max(win, key=lambda s: s.end - s.start)
    return Reduced(window=(w.start, w.end), devices=devices,
                   spans=[s for s in spans if s is not w])


def reduce_file(path) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)
