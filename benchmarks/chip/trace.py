"""Reduce a profiler trace of the window to per-layer device time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  On a TPU the trace has, per chip, a
plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program execution (named ``<module>(<fingerprint>)``) and whose line
``XLA Ops`` holds one event per HLO instruction executed, named by the
instruction's text (``%fusion.12 = f32[...] fusion(...)``).  Host threads
are lines of ``/host:CPU``; the harness's ``TraceAnnotation`` spans are
among them.

* An op belongs to the module execution that contains it.  Ops of the
  program under test get their layer from the compiled HLO's ``op_name``
  metadata, which carries the program's ``jax.named_scope`` /
  ``obs.phase_scope`` names: ``fabric/inject``, ``fabric/exchange``,
  ``fabric/drain``, other ``fabric/`` scopes, ``obs/`` (telemetry), and
  ``network`` for every other op of the program (ring pop, crossbar,
  neuron update, spike compaction, the scan's bookkeeping).  A fusion
  carries the metadata of its root op, so a fusion that spans a scope
  boundary is attributed to its root op's scope.  Ops of other modules
  (the harness's drive generator) are ``harness``.
* Control-flow ops (a ``while`` and its body) nest on the line; each op
  counts its self time, its duration less that of the ops nested in it.
* The traced window runs from the start of the first harness span to the
  end of the last one, on the host clock.  The profiler puts device
  events on that clock to within about a millisecond, and on a v5e the
  device's clock was seen to read behind the host's: where the first
  device op precedes the first harness span, which dispatched it, the
  device events are shifted to start with that span.
* Busy time is the union of device op intervals inside the window; the
  gaps between them are idle, each labelled by the harness span that
  overlaps it most.
* Per-step figures count the program's executions whose midpoint lies in
  the window, so a chunk still running when the trace stopped is left
  out, and each counted execution contributes all of its ops.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

LAYER_SCOPES = (("fabric/inject", "inject"), ("fabric/exchange", "exchange"),
                ("fabric/drain", "drain"), ("fabric/", "fabric_other"),
                ("obs/", "telemetry"))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_NOISE = re.compile(r"(^jit\([^)]*\)/)|((while|body|cond|closed_call|"
                    r"checkpoint|remat|pjit|jvp|vmap)(\([^)]*\))?/)")
TOP = 10


def op_names(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction: op_name metadata})`` of an HLO text."""
    module, names = "", {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return module, names


def layer_of(op_name: str) -> str:
    for scope, layer in LAYER_SCOPES:
        if scope in op_name:
            return layer
    return "network"


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_of(exec_name: str) -> str:
    """``jit_run(1234)`` → ``jit_run``."""
    return exec_name.split("(", 1)[0]


def union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """The union of ``intervals`` as sorted, disjoint ``[start, end]``."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(ops: list[tuple[float, float, str]]) -> list[float]:
    """Duration of each op less that of the ops nested directly in it
    (``ops`` sorted by start, longer first at equal starts)."""
    out = [end - start for start, end, _ in ops]
    stack: list[int] = []
    for i, (start, end, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][1]:
            out[stack[-1]] -= end - start
        stack.append(i)
    return out


def _short(op_name: str, instr: str) -> str:
    return _NOISE.sub("", op_name) or instr


def reduce(profile, program_hlo: str, chunk_steps: int,
           host_spans: tuple[str, ...]) -> dict | None:
    """Per-layer device seconds, busy and window seconds, steps covered and
    the breakdown, from a ``ProfileData``; None when the trace holds no
    device op or no harness span."""
    prog_module, prog_names = op_names(program_hlo)
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, execs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns, instruction(e.name))
                            for e in line.events]
                elif line.name == "XLA Modules":
                    execs += [(e.start_ns, e.end_ns, module_of(e.name))
                              for e in line.events]
            if ops:
                ops.sort(key=lambda o: (o[0], -o[1]))
                devices.append((ops, sorted(execs)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith(host_spans)]
    if not devices or not spans:
        return None
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    devices = [_align(ops, execs, w0) for ops, execs in devices]

    busy_ns, steps = 0.0, 0
    layer_ns, op_ns, gaps = defaultdict(float), defaultdict(float), []
    for ops, execs in devices:
        counted = {i for i, (s, e, m) in enumerate(execs)
                   if m == prog_module and w0 <= (s + e) / 2 <= w1}
        steps = max(steps, len(counted) * chunk_steps)
        starts = [s for s, _, _ in execs]
        for (s, e, instr), self_ns in zip(ops, self_times(ops)):
            k = bisect.bisect_right(starts, s) - 1
            inside = k >= 0 and e <= execs[k][1]
            if inside and execs[k][2] == prog_module:
                if k not in counted:
                    continue
                op_name = prog_names.get(instr, "")
                layer = layer_of(op_name)
                key = f"{layer}: {_short(op_name, instr)}"
            elif e <= w0 or s >= w1:
                continue
            else:
                layer = "harness"
                key = f"harness: {execs[k][2] if inside else instr}"
            layer_ns[layer] += self_ns
            op_ns[key] += self_ns
        merged = union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                        if e > w0 and s < w1])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(ge - gs, gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                 if ge > gs]
    n = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "steps": steps,
        "layer_s": {k: v / n * 1e-9 for k, v in layer_ns.items()},
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in sorted(
                op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label(spans, gs, ge), length * 1e-9]
                          for length, gs, ge in sorted(gaps, reverse=True)[:TOP]],
        },
    }


def _align(ops, execs, w0: float):
    """Shift one device's events so none starts before ``w0``, the first
    harness span (device work cannot precede its dispatch)."""
    shift = max(0.0, w0 - ops[0][0])
    if not shift:
        return ops, execs
    return ([(s + shift, e + shift, i) for s, e, i in ops],
            [(s + shift, e + shift, m) for s, e, m in execs])


def _label(spans, start: float, end: float) -> str:
    """The harness span that overlaps ``[start, end]`` most."""
    best, best_overlap = "outside any span", 0.0
    for s, e, name in spans:
        overlap = min(e, end) - max(s, start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def find_xplane(directory) -> str | None:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_dir(directory, program_hlo: str, chunk_steps: int,
               host_spans: tuple[str, ...]) -> dict | None:
    """:func:`reduce` of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    path = find_xplane(directory)
    if path is None:
        return None
    return reduce(ProfileData.from_file(path), program_hlo, chunk_steps,
                  host_spans)
