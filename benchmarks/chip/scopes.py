#!/usr/bin/env python3
"""Split the program's device time by scope, the ops XLA leaves unnamed
included, and check the trace's clock against the harness's spans.

:func:`trace.reduce` maps each op of the program to a layer by the
``op_name`` its compiled HLO carries, and counts every op that names no
``fabric/`` or ``obs/`` scope under ``network``.  Some instructions carry
no ``op_name`` at all: XLA's rewrites make them (the reduce-windows and
fusions a ``cumsum`` becomes, loop-carry copies), and no scope in the
program can put metadata back on them.  :func:`reduce` adds to
:func:`trace.reduce`'s result, and changes nothing in it:

* ``scope_s``: a partition of the same program ops that ``layer_s``
  covers (the executions ``trace.reduce`` counts, by self time), keyed by
  the first scope of :data:`SCOPES` that the ``op_name`` names, else
  ``program`` (the scan's own bookkeeping).  An instruction with no
  ``op_name`` takes the first scope of :data:`SCOPES` that all its nearest
  named producers and consumers name (their common prefix where they
  disagree; ``program`` where they share none), found by walking the
  operands of the compiled HLO within the instruction's computation.
  Neighbours outside every scope (the scan's record writes and
  bookkeeping) count only where no neighbour is in a scope, and
  constants not at all.  An instruction with no named neighbour is
  ``unscoped``.
* ``inferred``: the longest ops that inference placed, each as
  ``[instruction, scope, side, seconds]``, where side says whether the
  scope came from its producers, its consumers or both.
* ``clock``: per device, the k-th execution of the program paired with
  the k-th ``*/dispatch`` and ``*/readback`` span: the range of offsets
  (ms, added to the device's clock) that causality allows, each
  execution starting after its dispatch began and ending before its
  readback returned, beside the shift ``trace._align`` applied.

Run as a script on a machine with a TPU, it sets a cell up as
``run.py`` does, traces the first ``trace_chunks`` chunks of a short
window as ``run.py --trace 1`` does, and prints both splits, the new
per-layer readings (:data:`METRICS`), the ``inferred`` and ``clock``
lines, and last one JSON object:

    python3 benchmarks/chip/scopes.py --workload <name> --seed <n> \
        [--root <checkout>] [--keep <dir>]

``--keep`` writes the trace and the program's HLO there, gzipped
(``trace.xplane.pb.gz``, ``program.hlo.gz``), as a fixture for the tests.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import re
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace  # noqa: E402

# Innermost first: an op takes the first scope its op_name names.
SCOPES = ("snn/ring", "snn/synapse", "snn/neuron", "snn/spikes",
          "fabric/inject/route", "fabric/inject/pack", "fabric/inject",
          "fabric/exchange", "fabric/drain", "fabric/", "obs/")
PROGRAM, UNSCOPED = "program", "unscoped"
# The per-layer readers of this split (metrics/<name>.py).
METRICS = ("ring_us_per_step", "synapse_us_per_step", "neuron_us_per_step",
           "spikes_us_per_step", "route_us_per_step", "pack_us_per_step",
           "unscoped_us_per_step")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?[\w.\-]+\s*\(.*\{\s*$")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"([a-z][\w\-]*)\(")


def scope_of(op_name: str) -> str:
    for scope in SCOPES:
        if scope in op_name:
            return scope
    return PROGRAM


def graph(hlo_text: str) -> dict[str, dict]:
    """``{instruction: {"op_name", "opcode", "operands", "users"}}`` of an
    HLO text; ``op_name`` is None where the instruction carries none, and
    operands and users are instructions of the same computation."""
    nodes: dict[str, dict] = {}
    body: dict[str, list[str]] = {}

    def close():
        for name, refs in body.items():
            operands = [r for r in dict.fromkeys(refs) if r in body and r != name]
            nodes[name]["operands"] = operands
            for r in operands:
                nodes[r]["users"].append(name)
        body.clear()

    for line in hlo_text.splitlines():
        if _HEADER.match(line) or line.strip() == "}":
            close()
            continue
        m = _DEF.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op, opcode = _OP_NAME.search(rest), _OPCODE.search(rest)
        nodes[name] = {"op_name": op.group(1) if op else None,
                       "opcode": opcode.group(1) if opcode else "",
                       "operands": [], "users": []}
        body[name] = _REF.findall(_OP_NAME.sub("", rest))
    close()
    return nodes


def _nearest(nodes: dict, start: str, side: str) -> list[str]:
    """The ``op_name``s of the nearest instructions on ``side``
    (``operands`` or ``users``) that carry one, walking through those
    that do not; constants are no evidence and are passed over."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = list(dict.fromkeys(
            n for f in frontier for n in nodes[f][side]
            if n not in seen and nodes[n]["opcode"] != "constant"))
        seen.update(nxt)
        named = [nodes[n]["op_name"] for n in nxt
                 if nodes[n]["op_name"] is not None]
        if named:
            return named
        frontier = nxt
    return []


def infer(nodes: dict, instr: str) -> tuple[str, str]:
    """``(scope, side)`` of an instruction that carries no ``op_name``:
    the first scope of :data:`SCOPES` that all its nearest named
    neighbours in a scope name (``program`` where they share none, or
    where no neighbour is in a scope), and the side (``producers``,
    ``consumers`` or ``both``) those neighbours lie on."""
    sides = {"producers": _nearest(nodes, instr, "operands"),
             "consumers": _nearest(nodes, instr, "users")}
    scoped = {k: [n for n in v if scope_of(n) != PROGRAM]
              for k, v in sides.items()}
    if any(scoped.values()):
        sides = scoped
    names = sides["producers"] + sides["consumers"]
    if not names:
        return UNSCOPED, "none"
    side = "both" if all(sides.values()) else next(k for k, v in sides.items() if v)
    for scope in SCOPES:
        if all(scope in n for n in names):
            return scope, side
    return PROGRAM, side


def placer(program_hlo: str):
    """``instruction -> (scope, side)``; side is None for an instruction
    that carries its own ``op_name``, memoised per instruction."""
    nodes = graph(program_hlo)
    memo: dict[str, tuple[str, str | None]] = {}

    def place(instr: str) -> tuple[str, str | None]:
        if instr not in memo:
            node = nodes.get(instr)
            if node is None:
                memo[instr] = (UNSCOPED, "none")
            elif node["op_name"] is not None:
                memo[instr] = (scope_of(node["op_name"]), None)
            else:
                memo[instr] = infer(nodes, instr)
        return memo[instr]

    return place


def _planes(profile, host_spans: tuple[str, ...]):
    """Harness spans and, per device, (ops, executions) as
    ``trace.reduce`` reads them."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, execs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns, trace.instruction(e.name))
                            for e in line.events]
                elif line.name == "XLA Modules":
                    execs += [(e.start_ns, e.end_ns, trace.module_of(e.name))
                              for e in line.events]
            if ops:
                ops.sort(key=lambda o: (o[0], -o[1]))
                devices.append((ops, sorted(execs)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith(host_spans)]
    return sorted(spans), devices


def clock(spans, execs, module: str, shift: float) -> dict:
    """The offsets that put each execution between its dispatch and its
    readback, beside the applied ``shift`` (device-clock ns → ms)."""
    runs = [(s, e) for s, e, m in execs if m == module]
    dispatch = [s for s, _, name in spans if name.endswith("/dispatch")]
    readback = [e for _, e, name in spans if name.endswith("/readback")]
    n = min(len(runs), len(dispatch), len(readback))
    if not n:
        return {"pairs": 0, "offset_ms": None, "align_shift_ms": shift * 1e-6}
    lo = max(d - s for d, (s, _) in zip(dispatch, runs))
    hi = min(r - e for r, (_, e) in zip(readback, runs))
    return {"pairs": n, "offset_ms": [lo * 1e-6, hi * 1e-6],
            "align_shift_ms": shift * 1e-6}


def split(profile, program_hlo: str, host_spans: tuple[str, ...]) -> dict:
    """``scope_s``, ``inferred`` and ``clock`` of a ``ProfileData``, over
    the ops and executions :func:`trace.reduce` counts."""
    prog_module, _ = trace.op_names(program_hlo)
    place = placer(program_hlo)
    spans, devices = _planes(profile, host_spans)
    if not devices or not spans:
        return {"scope_s": {}, "inferred": [], "clock": []}
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    scope_ns, inferred, clocks = defaultdict(float), {}, []
    for raw_ops, raw_execs in devices:
        ops, execs = trace._align(raw_ops, raw_execs, w0)
        clocks.append(clock(spans, raw_execs, prog_module,
                            ops[0][0] - raw_ops[0][0]))
        counted = {i for i, (s, e, m) in enumerate(execs)
                   if m == prog_module and w0 <= (s + e) / 2 <= w1}
        starts = [s for s, _, _ in execs]
        for (s, e, instr), self_ns in zip(ops, trace.self_times(ops)):
            k = bisect.bisect_right(starts, s) - 1
            if not (k in counted and e <= execs[k][1]):
                continue
            scope, side = place(instr)
            scope_ns[scope] += self_ns
            if side is not None:
                prev = inferred.get(instr, (scope, side, 0.0))
                inferred[instr] = (scope, side, prev[2] + self_ns)
    n = len(devices)
    top = sorted(inferred.items(), key=lambda kv: -kv[1][2])[:trace.TOP]
    return {
        "scope_s": {k: v / n * 1e-9 for k, v in scope_ns.items()},
        "inferred": [[i, scope, side, ns / n * 1e-9]
                     for i, (scope, side, ns) in top],
        "clock": clocks,
    }


def reduce(profile, program_hlo: str, chunk_steps: int,
           host_spans: tuple[str, ...]) -> dict | None:
    """:func:`trace.reduce`'s result, unchanged, with ``scope_s``,
    ``inferred`` and ``clock`` added; None where it is None."""
    summary = trace.reduce(profile, program_hlo, chunk_steps, host_spans)
    if summary is not None:
        summary.update(split(profile, program_hlo, host_spans))
    return summary


def reduce_dir(directory, program_hlo: str, chunk_steps: int,
               host_spans: tuple[str, ...]) -> dict | None:
    """:func:`reduce` of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    path = trace.find_xplane(directory)
    if path is None:
        return None
    return reduce(ProfileData.from_file(path), program_hlo, chunk_steps,
                  host_spans)


def report(summary: dict) -> list[str]:
    """The lines that print what inference placed and the clock check."""
    return ["inferred (instruction, scope, side, s): "
            + json.dumps(summary["inferred"]),
            "clock (per device: offsets causality allows, ms; shift "
            "applied, ms): " + json.dumps(summary["clock"])]


def trace_cell(root: Path, workload: str, seed: int, seconds: float, *,
               require_tpu: bool = True, keep: Path | None = None,
               out=None) -> dict:
    """Set up ``workload`` as ``run.py`` does, trace the first
    ``trace_chunks`` chunks of a ``seconds`` window and print the splits;
    returns the last line's object."""
    out = out or sys.stdout
    from benchmarks.chip import run, spec
    from benchmarks.chip import traffic as bench_traffic

    cell = spec.Cell(root, workload)
    devices = run.check_devices(cell.chips, require_tpu)
    run.import_program(root)

    import jax
    import numpy as np

    from benchmarks.chip import data, program

    run.enable_compile_cache(root)
    # The split reads the op metadata, which the cache key leaves out by
    # default: an executable cached from a build with other scopes would
    # bring that build's names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    comm = cell.config["comm"]
    system = program.Program(cell.config, data.make(cell.config, seed))
    rates = bench_traffic.initial_rates(cell.traffic, comm)
    drive = bench_traffic.make_drive(cell.traffic, comm, seed).lower(
        np.int32(0), rates).compile()
    state0 = system.init_state()
    ext0 = drive(np.int32(0), rates)
    hlo = system.compile(state0, ext0)
    warm_state, warm_rec = system.run(state0, ext0)
    jax.device_get(warm_rec)
    program.free(warm_state, warm_rec, ext0)

    tracer = run.Tracer(root, cell.traffic["trace_chunks"], True)
    run.LOOPS[cell.traffic["loop"]](system, drive, cell.traffic, state0,
                                    rates, seconds, tracer)
    if tracer.active:       # a window shorter than trace_chunks chunks
        jax.profiler.stop_trace()
    chunk_steps = cell.traffic["chunk_steps"]
    summary = reduce_dir(root / run.TRACE_DIR, hlo, chunk_steps,
                         host_spans=("trial/", "stream/"))
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        (keep / "program.hlo.gz").write_bytes(gzip.compress(hlo.encode()))
        xplane = trace.find_xplane(root / run.TRACE_DIR)
        if xplane is not None:
            (keep / "trace.xplane.pb.gz").write_bytes(
                gzip.compress(Path(xplane).read_bytes()))
    shutil.rmtree(root / run.TRACE_DIR, ignore_errors=True)

    result = {"workload": workload, "seed": seed,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind},
              "steps": None, "layer_us_per_step": None,
              "scope_us_per_step": None, "metrics": {}}
    if summary is not None and summary["steps"]:
        per_step = 1e6 / summary["steps"]
        result["steps"] = summary["steps"]
        result["layer_us_per_step"] = {
            k: v * per_step for k, v in summary["layer_s"].items()}
        result["scope_us_per_step"] = {
            k: v * per_step for k, v in summary["scope_s"].items()}
        for line in report(summary):
            print(line, file=out)
    ctx = {"trace": summary}
    for name in METRICS:
        value = cell.reader(name).read(ctx)
        if value is not None:
            result["metrics"][name] = value
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window length; the trace covers its first "
                         "trace_chunks chunks")
    ap.add_argument("--root", type=Path, default=ROOT)
    ap.add_argument("--keep", type=Path, default=None)
    args = ap.parse_args(argv)
    from benchmarks.chip.run import RunError

    t0 = time.perf_counter()
    try:
        trace_cell(args.root.resolve(), args.workload, args.seed,
                   args.seconds, keep=args.keep)
    except RunError as e:
        print(f"scopes: {e}", file=sys.stderr)
        return e.code
    print(f"scopes: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
