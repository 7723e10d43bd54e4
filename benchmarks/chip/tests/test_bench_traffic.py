"""The traffic generator: the same seed gives the same drive, volleys hit
the stated number of chips in full, feedback stays inside its clip."""

import numpy as np

from benchmarks.chip import traffic

COMM = {"n_chips": 6, "n_inputs_per_chip": 5, "superstep": 4}
VOLLEY = {"loop": "open", "chunk_steps": 8, "background": {"rate": 0.0},
          "feedback": None, "volley": {"period": 4, "chips": 2},
          "trace_chunks": 1}
TRIALS = {"loop": "closed", "chunk_steps": 8, "background": {"rate": 0.2},
          "feedback": {"target_spikes_per_neuron_step": 0.05,
                       "min_rate": 0.02, "max_rate": 0.3},
          "volley": None, "trace_chunks": 1}


def test_same_seed_same_drive():
    rates = traffic.initial_rates(TRIALS, COMM)
    a = traffic.make_drive(TRIALS, COMM, 2**33 + 5)(np.int32(3), rates)
    b = traffic.make_drive(TRIALS, COMM, 2**33 + 5)(np.int32(3), rates)
    c = traffic.make_drive(TRIALS, COMM, 5)(np.int32(3), rates)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_volleys_fire_whole_chips_on_period():
    drive = traffic.make_drive(VOLLEY, COMM, 9)
    x = np.asarray(drive(np.int32(1), traffic.initial_rates(VOLLEY, COMM)))
    per_step = x.all(axis=2).sum(axis=1)          # chips fully on per step
    assert per_step.tolist() == [2, 0, 0, 0, 2, 0, 0, 0]
    assert x.sum() == 2 * 2 * COMM["n_inputs_per_chip"]


def test_feedback_moves_rates_toward_target_within_clip():
    rates = traffic.initial_rates(TRIALS, COMM)
    spikes = np.zeros((8, 6, 10), bool)
    spikes[:, 0] = True                            # chip 0 far above target
    new = traffic.next_rates(TRIALS, rates, spikes)
    assert new[0] == np.float32(0.02)              # clipped low
    assert np.all(new[1:] == np.float32(0.3))      # silent chips: clipped high
    assert traffic.next_rates(VOLLEY, rates, spikes) is rates


def test_check_refuses_chunks_off_the_superstep():
    bad = dict(TRIALS, chunk_steps=6)
    try:
        traffic.check(bad, COMM)
    except ValueError as e:
        assert "superstep" in str(e)
    else:
        raise AssertionError("accepted a chunk that splits a superstep")
