#!/usr/bin/env python3
"""One run of one cell of the on-chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

(or ``python3 -m benchmarks.chip.run ...``) from the root of a checkout.

The cell, its configuration file and its traffic mix come from
``BENCHMARK.json`` by name (``spec.py``).  Set-up makes the weights and
routing tables from the seed on the device, builds the program's network
from the configuration, compiles the chunk program and the drive
generator (through the persistent compilation cache at ``.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another) and runs one chunk to
warm up.  The window then drives ``repro.snn.network.run`` chunk after
chunk, the state of one call passed into the next, for ``--seconds``
seconds: a closed loop waits for each chunk's records before it makes the
next chunk's drive from them, an open loop reads one chunk's records
while the next runs.  With ``--trace 1`` the profiler covers the first
``trace_chunks`` chunks of the window and the result carries the
per-layer metrics; otherwise the end-to-end ones.

After the window the program's state is freed and the plain reference
(``reference/``) replays every chunk from the same start with the same
drive; ``check.py`` compares the two and decides ``correct``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each compared number beside its
limit); the last lines of standard error repeat the checks.  Exit codes:
0 a result was printed; 2 the cell or its files are malformed; 3 JAX
finds no TPU or fewer chips than the cell asks for; 4 the program under
test is not in the checkout.  Nothing is printed on stdout unless 0.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

EXIT_SPEC, EXIT_NO_CHIP, EXIT_NO_PROGRAM = 2, 3, 4
TRACE_DIR = ".bench_trace"


class RunError(Exception):
    """A run that cannot produce a result; carries its exit code."""

    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code


class CompileCounter:
    """Counts traces, lowerings and compiles JAX reports."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, *_args, **_kwargs) -> None:
        if event.startswith("/jax/core/compile/"):
            self.count += 1


class Tracer:
    """The profiler over the first ``n_chunks`` chunks of the window."""

    def __init__(self, root: Path, n_chunks: int, enabled: bool):
        self.dir = root / TRACE_DIR
        self.n_chunks = n_chunks if enabled else 0
        self.active = False

    def start(self) -> None:
        import jax

        if self.n_chunks:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self.active = True

    def chunks_done(self, done: int) -> None:
        import jax

        if self.active and done >= self.n_chunks:
            jax.profiler.stop_trace()
            self.active = False


def check_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise RunError(EXIT_NO_CHIP, f"no TPU: JAX found {len(devices)} "
                       f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise RunError(EXIT_NO_CHIP, f"the cell needs {chips} chips, JAX "
                       f"found {len(devices)}")
    return devices


def enable_compile_cache(root: Path) -> str:
    """The persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def import_program(root: Path) -> None:
    src = root / "src"
    if not (src / "repro" / "snn" / "network.py").is_file():
        raise RunError(EXIT_NO_PROGRAM,
                       f"no program under test: {src}/repro is missing")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def closed_loop(system, drive, traffic, state, rates, seconds, tracer):
    """Trials: dispatch, wait for the records, make the next drive from
    them.  Returns (window summary, chunks, final state)."""
    import jax
    import numpy as np

    from benchmarks.chip import traffic as bench_traffic

    chunks, latencies = [], []
    tracer.start()
    w0 = end = time.perf_counter()
    j = 0
    while j == 0 or end - w0 < seconds:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("trial/dispatch"):
            state, rec = system.run(state, drive(np.int32(j), rates))
        with jax.profiler.TraceAnnotation("trial/readback"):
            host = jax.device_get(rec)
        end = time.perf_counter()
        latencies.append(end - t0)
        with jax.profiler.TraceAnnotation("trial/drive"):
            chunks.append((rates, host))
            rates = bench_traffic.next_rates(traffic, rates, system.spikes(host))
        j += 1
        tracer.chunks_done(j)
    return {"loop": "closed", "seconds": end - w0,
            "latencies_s": latencies}, chunks, state


def open_loop(system, drive, traffic, state, rates, seconds, tracer):
    """A stream: dispatch chunk j, then read chunk j - 1's records while j
    runs.  Returns (window summary, chunks, final state)."""
    import jax
    import numpy as np

    del traffic
    chunks = []
    tracer.start()
    w0 = end = time.perf_counter()
    pending = None
    j = 0
    while j == 0 or end - w0 < seconds:
        with jax.profiler.TraceAnnotation("stream/dispatch"):
            state, rec = system.run(state, drive(np.int32(j), rates))
        if pending is not None:
            with jax.profiler.TraceAnnotation("stream/readback"):
                chunks.append((rates, jax.device_get(pending)))
            tracer.chunks_done(len(chunks))
        pending = rec
        j += 1
        end = time.perf_counter()
    with jax.profiler.TraceAnnotation("stream/readback"):
        chunks.append((rates, jax.device_get(pending)))
    end = time.perf_counter()
    tracer.chunks_done(len(chunks))
    return {"loop": "open", "seconds": end - w0, "latencies_s": []}, chunks, state


LOOPS = {"closed": closed_loop, "open": open_loop}


def verify(cell, arrays, system, drive, chunks, final):
    """Replay every chunk on the plain reference and compare."""
    import jax
    import numpy as np

    from benchmarks.chip import check
    from benchmarks.chip.program import Reference

    reference = Reference(cell.config, arrays)
    rstate = reference.init_state()
    reference.compile(rstate, drive(np.int32(0), chunks[0][0]))
    tally = check.Tally()
    for j, (rates, host) in enumerate(chunks):
        rstate, rrec = reference.run(rstate, drive(np.int32(j), rates))
        want = reference.neutral_records(jax.device_get(rrec))
        tally.add(check.compare(system.neutral_records(host), want,
                                check.FLOAT_RECORDS))
    tally.add(check.compare(final, reference.final(rstate), check.FLOAT_FINAL),
              chunk=False)
    return tally


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, system_cls=None,
             out=None, err=None) -> int:
    """One run; prints the result line and returns 0, or raises
    :class:`RunError`.  ``system_cls`` puts another system in the
    program's place (the control), ``require_tpu=False`` lets the tests
    drive a run on the CPU."""
    out = out or sys.stdout
    err = err or sys.stderr
    from benchmarks.chip import spec

    from benchmarks.chip import traffic as bench_traffic

    try:
        cell = spec.Cell(root, workload)
        comm = cell.config["comm"]
        bench_traffic.check(cell.traffic, comm)
    except (KeyError, OSError, ValueError) as e:
        raise RunError(EXIT_SPEC, f"cell {workload!r}: {e}") from e
    devices = check_devices(cell.chips, require_tpu)
    import_program(root)

    import jax
    import numpy as np

    from benchmarks.chip import data, program
    from benchmarks.chip import trace as bench_trace

    cache = enable_compile_cache(root)
    counter = CompileCounter()

    arrays = data.make(cell.config, seed)
    system = (system_cls or program.Program)(cell.config, arrays)
    drive_fn = bench_traffic.make_drive(cell.traffic, comm, seed)
    rates = bench_traffic.initial_rates(cell.traffic, comm)
    drive = drive_fn.lower(np.int32(0), rates).compile()
    state0 = system.init_state()
    ext0 = drive(np.int32(0), rates)
    hlo = system.compile(state0, ext0)
    warm_state, warm_rec = system.run(state0, ext0)
    jax.device_get(warm_rec)
    program.free(warm_state, warm_rec, ext0)
    setup_s = time.perf_counter() - _T_START
    print(f"setup: {setup_s:.3f} s, compile cache {cache}, "
          f"{counter.count} trace/compile events", file=out, flush=True)

    tracer = Tracer(root, cell.traffic["trace_chunks"], trace)
    before = counter.count
    window, chunks, state = LOOPS[cell.traffic["loop"]](
        system, drive, cell.traffic, state0, rates, seconds, tracer)
    compiles = counter.count - before
    steps = len(chunks) * cell.traffic["chunk_steps"]
    window["steps"] = steps
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    totals = dict.fromkeys(("delivered", "sent", "overflow", "merge_dropped",
                            "expired"), 0)
    for _, host in chunks:
        rec = system.neutral_records(host)
        for key in totals:
            totals[key] += int(rec[key].sum())
    print(f"window: {len(chunks)} chunks, {steps} steps in "
          f"{window['seconds']:.3f} s, {compiles} trace/compile events inside, "
          f"{totals['delivered'] / window['seconds']:.1f} delivered events/s; "
          f"event totals {totals}", file=out, flush=True)

    summary = None
    if trace:
        summary = bench_trace.reduce_dir(
            root / TRACE_DIR, hlo, cell.traffic["chunk_steps"],
            host_spans=("trial/", "stream/"))
        shutil.rmtree(root / TRACE_DIR, ignore_errors=True)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]

    final = system.final(state)
    program.free(state, state0)
    tally = verify(cell, arrays, system, drive, chunks, final)

    ctx = {"setup_s": setup_s, "window": window, "trace": summary}
    result = {"correct": tally.correct(len(chunks)), "attempted": len(chunks),
              "failed": tally.failed + (len(chunks) - tally.compared),
              "metrics": cell.read_metrics(trace, ctx), "device": device}
    if summary is not None:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = tally.report()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except RunError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code


if __name__ == "__main__":
    sys.exit(main())
