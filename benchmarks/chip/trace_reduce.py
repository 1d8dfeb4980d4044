"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

A TPU trace has a plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per executed HLO instruction, named by the
instruction's text (``%fusion.12 = f32[...] fusion(...)``), and an ``XLA
Modules`` line with one event per program run. On the CPU the ops are
events on the host's XLA threads, with the instruction and program in the
``hlo_op`` and ``hlo_module`` stats. Neither carries the ``named_scope``
path: that sits in the compiled program's HLO text (``metadata={op_name=
"jit(round_fn)/round.client_grads/..."}``), so the reduction maps each op of
the named program to its scope path through that text. A fusion without
metadata of its own takes the most common path of the instructions it
calls.

Host spans (``jax.profiler.TraceAnnotation``) are the events of the
``/host:CPU`` thread line that holds the window, the first span named
``window_span``; device ops are clipped to it. Busy time is the union of
the op intervals, so overlapping ops count once. An idle gap is named by
the program run it falls inside (a TPU program can leave its chip idle
between ops), or else by the innermost host span open at its middle.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import jax

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass
class Op:
    name: str       # HLO instruction name
    module: str     # program name (jit_<function>)
    start: float    # ns
    end: float


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(program name, instruction name -> op_name path) of compiled HLO."""
    module = ""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    inside: dict[str, list[str]] = collections.defaultdict(list)
    current = ""
    for line in hlo_text.splitlines():
        if not module and (m := _MODULE.match(line)):
            module = m.group(1)
            continue
        if m := _COMPUTATION.match(line):
            current = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        if op := _OP_NAME.search(line):
            own[name] = op.group(1)
            inside[current].append(op.group(1))
        if c := _CALLS.search(line):
            calls[name] = c.group(1)
    paths = dict(own)
    for name, comp in calls.items():
        if name not in paths and inside.get(comp):
            paths[name] = collections.Counter(inside[comp]).most_common(1)[0][0]
    return module, paths


def _stats(event) -> dict:
    return dict(event.stats)


def _module_of(name: str) -> str:
    return name.split("(", 1)[0]


class TraceView:
    """Device ops and host spans of one traced window."""

    def __init__(self, ops: list[Op], spans: list[Span], window: tuple[float, float],
                 scopes: dict[str, str], program: str, runs: list[Span] = ()):
        self.window = window
        self.ops = ops
        self.spans = spans
        self.scopes = scopes
        self.program = program
        self.runs = list(runs)      # program runs on the device

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self) -> list[tuple[float, float]]:
        merged: list[list[float]] = []
        for op in sorted(self.ops, key=lambda o: o.start):
            if merged and op.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], op.end)
            else:
                merged.append([op.start, op.end])
        return [(a, b) for a, b in merged]

    def busy_ns(self) -> float:
        return sum(b - a for a, b in self.busy_intervals())

    def idle_gaps(self) -> list[tuple[float, float]]:
        gaps, t = [], self.window[0]
        for a, b in self.busy_intervals():
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return gaps

    def scope_of(self, op: Op) -> str:
        return self.scopes.get(op.name, "") if op.module == self.program else ""

    def scope_ns(self, *scopes: str) -> float:
        """Device time of the program's ops whose scope path names any of
        ``scopes``."""
        return sum(op.end - op.start for op in self.ops
                   if any(s in self.scope_of(op) for s in scopes))

    def span_ns(self, name: str) -> list[float]:
        return [s.end - s.start for s in self.spans if s.name == name]

    def host_label(self, t: float) -> str:
        """Innermost host span open at time ``t``."""
        open_ = [s for s in self.spans if s.start <= t < s.end]
        return max(open_, key=lambda s: s.start).name if open_ else "no host span open"

    def gap_label(self, t: float) -> str:
        for run in self.runs:
            if run.start <= t < run.end:
                return f"inside {run.name}, no op running"
        return self.host_label(t)

    def breakdown(self, top: int = 10) -> dict:
        per_op: dict[str, float] = collections.defaultdict(float)
        for op in self.ops:
            scope = self.scope_of(op)
            label = f"{op.module}:{op.name}" + (f" [{scope}]" if scope else "")
            per_op[label[:200]] += (op.end - op.start) * 1e-9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.gap_label((a + b) / 2), (b - a) * 1e-9]
                              for a, b in gaps]}


def load(trace_dir: str, hlo_text: str = "", window_span: str = "bench.window") -> TraceView:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    program, scopes = hlo_scopes(hlo_text) if hlo_text else ("", {})

    ops: list[Op] = []
    runs: list[Span] = []
    threads: dict[str, list[Span]] = {}
    device_planes = [p for p in data.planes if p.name.startswith("/device:")
                     and any(line.name == "XLA Ops" for line in p.lines)]
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((Span(_module_of(e.name), e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines["XLA Modules"].events) if "XLA Modules" in lines else [],
                      key=lambda r: r.start)
        runs += mods
        i = 0
        for e in sorted(lines["XLA Ops"].events, key=lambda e: e.start_ns):
            while i + 1 < len(mods) and mods[i].end <= e.start_ns:
                i += 1
            module = mods[i].name if mods and mods[i].start <= e.start_ns < mods[i].end else ""
            m = _EVENT_INSTR.match(e.name)
            ops.append(Op(m.group(1) if m else e.name, module,
                          e.start_ns, e.start_ns + e.duration_ns))
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st:      # an op the CPU backend ran on this thread
                    if not device_planes:
                        ops.append(Op(str(st["hlo_op"]), str(st.get("hlo_module", "")),
                                      e.start_ns, e.start_ns + e.duration_ns))
                else:
                    threads.setdefault(line.name, []).append(
                        Span(e.name, e.start_ns, e.start_ns + e.duration_ns))

    spans = next((t for t in threads.values() if any(s.name == window_span for s in t)),
                 threads.get("python", []))
    marks = [s for s in spans if s.name == window_span]
    if marks:
        window = (marks[0].start, marks[0].end)
    elif ops:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    else:
        window = (0.0, 0.0)
    a, b = window
    ops = [Op(o.name, o.module, max(o.start, a), min(o.end, b))
           for o in ops if o.end > a and o.start < b]
    spans = [s for s in spans if s.end > a and s.start < b and s.name != window_span]
    return TraceView(ops, spans, window, scopes, program, runs)
