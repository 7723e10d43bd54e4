"""The benchmark's full-scheme configuration (``bss2-wafer-merge.json``)
against the plain reference, on the CPU at a tiny size.

The configuration's ``comm`` group is kept (full mode, a pool of 4
buckets per destination renamed by 4-step deadline windows, superstep 4)
and only shrunk to 4 chips of 32 neurons and 16 inputs, E 24, bucket
capacity 4, with a merge rate below the offered load: the queue fills
and drops, as it does at the wafer module's widths.  The program
(``benchmarks.chip.program.Program``) and the plain reference
(``Reference``) are driven chunk after chunk by the ``stream`` traffic's
generator and compared as the benchmark compares them.
"""

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, data, program  # noqa: E402
from benchmarks.chip import traffic as bench_traffic  # noqa: E402
from repro.core import buckets as bk  # noqa: E402
from repro.core import events as ev  # noqa: E402

BENCH = ROOT / "benchmarks" / "chip"
TINY = {"n_chips": 4, "neurons_per_chip": 32, "n_inputs_per_chip": 16,
        "event_capacity": 24, "bucket_capacity": 4,
        "merge_rate": 4, "merge_depth": 8}
CHUNKS, CHUNK_STEPS = 3, 32


def _tiny_config() -> dict:
    config = json.loads((BENCH / "configs" / "bss2-wafer-merge.json").read_text())
    comm = config["comm"]
    assert (comm["mode"], comm["buckets_per_chip"], comm["time_window"],
            comm["superstep"]) == ("full", 4, 4, 4)
    comm.update(TINY)
    return config


def _stream() -> dict:
    traffic = json.loads((BENCH / "traffic" / "stream.json").read_text())
    assert traffic["loop"] == "open" and traffic["feedback"] is None
    return dict(traffic, chunk_steps=CHUNK_STEPS)


@pytest.mark.parametrize("seed", [2147483931, 5])
def test_tiny_full_scheme_matches_reference(seed):
    config, traffic = _tiny_config(), _stream()
    comm = config["comm"]
    arrays = data.make(config, seed)
    drive_fn = bench_traffic.make_drive(traffic, comm, seed)
    rates = bench_traffic.initial_rates(traffic, comm)

    system = program.Program(config, arrays)
    reference = program.Reference(config, arrays)
    state, rstate = system.init_state(), reference.init_state()
    system.compile(state, drive_fn(np.int32(0), rates))
    reference.compile(rstate, drive_fn(np.int32(0), rates))

    tally = check.Tally()
    dropped = offered = 0
    for j in range(CHUNKS):
        ext = drive_fn(np.int32(j), rates)
        state, rec = system.run(state, ext)
        rstate, rrec = reference.run(rstate, ext)
        got = system.neutral_records(jax.device_get(rec))
        tally.add(check.compare(
            got, reference.neutral_records(jax.device_get(rrec)),
            check.FLOAT_RECORDS))
        dropped += int(got["merge_dropped"].sum())
        offered += int(got["traffic"].sum())
    final = system.final(state)
    tally.add(check.compare(final, reference.final(rstate), check.FLOAT_FINAL),
              chunk=False)

    assert tally.correct(CHUNKS), tally.report()
    # Offered above the rate: the queue fills and drops.
    steps = CHUNKS * CHUNK_STEPS
    assert offered > comm["merge_rate"] * comm["n_chips"] * steps
    assert dropped > 0
    queue = final["queue"]
    valid = queue >= 0
    assert valid.any(), "the final queue is empty"
    # The pool index each queued word was shipped under: deadlines span
    # every window, so the renaming uses buckets beyond the first two.
    pool = np.asarray(bk.dynamic_bucket_ids(
        np.zeros_like(queue), ev.word_time(queue), n_chips=comm["n_chips"],
        pool_per_chip=comm["buckets_per_chip"], window=comm["time_window"]))
    assert pool[valid].max() >= 2
