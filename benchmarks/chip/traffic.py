"""The one traffic generator: external drive for every simulated chip,
made from the seed on the device, with its parameters read from a traffic
file (``traffic/<name>.json``).

A traffic file holds:

* ``loop``: ``"closed"`` — the host waits for each chunk's records before
  it computes the next chunk's drive (an in-the-loop experiment);
  ``"open"`` — chunks stream back to back, the records of one chunk read
  while the next runs;
* ``chunk_steps``: simulated steps per call of the program;
* ``background``: ``{"rate": p}``, each external input of each chip
  spikes with probability ``p`` in every step (Bernoulli);
* ``feedback`` (closed loop only, or null): after each chunk, each chip's
  rate is scaled by target over observed spikes and clipped —
  ``{"target_spikes_per_neuron_step", "min_rate", "max_rate"}``;
* ``volley`` (or null): ``{"period", "chips"}`` — every ``period`` steps,
  all inputs of ``chips`` chips, drawn from the seed for each volley,
  spike in the same step;
* ``trace_chunks``: chunks the profiler covers in a ``--trace 1`` run.

Chunk ``j`` of a run covers steps ``[j * chunk_steps, (j + 1) *
chunk_steps)`` and its drive depends only on the seed, ``j`` and the
per-chip rates, so the reference can make the same drive again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import data as bench_data

LOOPS = ("closed", "open")


def check(traffic: dict, comm: dict) -> None:
    """Refuse a traffic file this generator cannot run with ``comm``."""
    if traffic["loop"] not in LOOPS:
        raise ValueError(f"loop {traffic['loop']!r} is not one of {LOOPS}")
    if traffic["chunk_steps"] % comm["superstep"]:
        raise ValueError(
            f"chunk_steps {traffic['chunk_steps']} is no multiple of the "
            f"superstep {comm['superstep']}")
    if traffic.get("feedback") and traffic["loop"] != "closed":
        raise ValueError("feedback needs the closed loop")
    volley = traffic.get("volley")
    if volley and volley["chips"] > comm["n_chips"]:
        raise ValueError(f"a volley of {volley['chips']} chips, the "
                         f"configuration has {comm['n_chips']}")


def make_drive(traffic: dict, comm: dict, seed: int):
    """``drive(chunk, rates) -> f32[chunk_steps, chips, inputs]``, jitted,
    with ``chunk`` an int32 scalar and ``rates`` f32[chips]."""
    steps, c, i = traffic["chunk_steps"], comm["n_chips"], comm["n_inputs_per_chip"]
    base = bench_data.seed_key(seed)
    drive_key = jax.random.fold_in(base, bench_data.DRIVE_STREAM)
    volley_key = jax.random.fold_in(base, bench_data.VOLLEY_STREAM)
    volley = traffic.get("volley")

    @jax.jit
    def drive(chunk, rates):
        key = jax.random.fold_in(drive_key, chunk)
        spikes = jax.random.uniform(key, (steps, c, i)) < rates[None, :, None]
        if volley:
            t = chunk * steps + jnp.arange(steps)
            due = t % volley["period"] == 0

            def chips(index):
                perm = jax.random.permutation(
                    jax.random.fold_in(volley_key, index), c)
                return jnp.zeros((c,), bool).at[perm[:volley["chips"]]].set(True)

            hit = jax.vmap(chips)(t // volley["period"]) & due[:, None]
            spikes = spikes | hit[:, :, None]
        return spikes.astype(jnp.float32)

    return drive


def initial_rates(traffic: dict, comm: dict) -> np.ndarray:
    return np.full((comm["n_chips"],), traffic["background"]["rate"], np.float32)


def next_rates(traffic: dict, rates: np.ndarray, spikes: np.ndarray) -> np.ndarray:
    """Closed-loop feedback: the next chunk's per-chip rates from this
    chunk's spike counts (``spikes [T, chips, neurons]``)."""
    fb = traffic.get("feedback")
    if not fb:
        return rates
    steps, _, neurons = spikes.shape
    observed = spikes.sum(axis=(0, 2), dtype=np.float64)
    target = fb["target_spikes_per_neuron_step"] * neurons * steps
    scaled = rates.astype(np.float64) * target / np.maximum(observed, 1.0)
    return np.clip(scaled, fb["min_rate"], fb["max_rate"]).astype(np.float32)
