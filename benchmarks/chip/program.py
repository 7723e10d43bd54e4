"""The systems a window can drive, behind one interface.

:class:`Program` is the system under test: ``repro.snn.network.run`` on
the local transport, built from the configuration file's ``comm`` and
``network`` groups.  It sets no implementation choice (``use_pallas``,
``pipeline``, ``telemetry``): those stay at the program's defaults.

:class:`Reference` puts the plain reference in the program's place; the
control runs it at a lower crossbar precision.

Both return records that :func:`neutral_records` / ``final`` turn into
the comparison's common form: numpy arrays keyed by name.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from benchmarks.chip.reference import network as ref

INT_STATS = ("sent", "overflow", "merge_dropped", "expired", "stalled",
             "wire_bytes", "traffic", "lost_to_failure")
# Keys of the program's configuration that choose an implementation, not
# a deployment: a configuration file may not set them.
IMPLEMENTATION_KEYS = ("use_pallas", "pipeline", "telemetry")


def _sorted_queue(words: np.ndarray) -> np.ndarray:
    """A merge queue as the multiset of its valid words, sorted, then
    empty lanes (-1) — independent of how the queue orders them."""
    big = np.iinfo(np.int32).max
    out = np.sort(np.where(words >= 0, words, big), axis=-1)
    return np.where(out == big, ref.SENTINEL, out).astype(np.int32)


def _ring_by_deadline(ring: np.ndarray, t: int) -> np.ndarray:
    """``ring [C, D, I]`` re-indexed so slot j holds the counts due at
    step ``t + j``."""
    d = ring.shape[1]
    return ring[:, (t + np.arange(d)) % d, :]


class Program:
    """``repro.snn.network.run`` over chunks, the state carried between
    calls."""

    def __init__(self, config: dict, arrays: dict):
        from repro.core import pulse_comm as pc
        from repro.core import routing as rt
        from repro.snn import network as net
        from repro.snn import neuron as nr
        from repro.snn import synapse as sy

        for group in ("comm", "network"):
            bad = set(config[group]) & set(IMPLEMENTATION_KEYS)
            if bad:
                raise ValueError(f"configuration sets {sorted(bad)}: an "
                                 "implementation choice, not a deployment")
        network = dict(config["network"])
        network.pop("crossbar_precision")   # stated, and pinned by the program
        self.superstep = config["comm"]["superstep"]
        self.cfg = net.NetworkConfig(comm=pc.PulseCommConfig(**config["comm"]),
                                     **network)
        self.params = net.NetworkParams(
            crossbar=sy.Crossbar(w=arrays["w"]),
            neuron=nr.AdExParams(**arrays["neuron"]),
            table=rt.RoutingTable(**arrays["table"]))
        self._init = jax.jit(functools.partial(net.init_state, self.cfg))
        self._run_fn = jax.jit(functools.partial(net.run, self.cfg))
        self._run = None

    def init_state(self):
        return self._init(self.params)

    def compile(self, state, ext):
        """Compile the chunk program for these shapes; returns its HLO."""
        compiled = self._run_fn.lower(self.params, state, ext).compile()
        self._run = compiled
        return compiled.as_text()

    def run(self, state, ext):
        return self._run(self.params, state, ext)

    @staticmethod
    def spikes(host_rec) -> np.ndarray:
        return np.asarray(host_rec.spikes) > 0.5

    def neutral_records(self, host_rec) -> dict:
        st = host_rec.stats
        out = {"spikes": self.spikes(host_rec),
               "voltage": np.asarray(host_rec.voltage),
               "delivered": np.asarray(host_rec.delivered),
               "utilization": np.asarray(st.utilization)}
        for name in INT_STATS:
            out[name] = np.asarray(getattr(st, name))
        b = self.superstep
        for name in ("link_words", "link_backlog"):
            x = np.asarray(getattr(st, name)).sum(axis=-1)     # over ports
            out[name] = x.reshape((x.shape[0] // b, b) + x.shape[1:]).sum(axis=1)
        return out

    def final(self, state) -> dict:
        state = jax.device_get(state)
        t = int(state.t)
        out = {"v": np.asarray(state.neuron.v), "w": np.asarray(state.neuron.w),
               "refrac": np.asarray(state.neuron.refrac),
               "ring": _ring_by_deadline(np.asarray(state.ring.ring), t),
               "ring_clock": np.asarray(state.ring.now), "t": np.asarray(t)}
        if state.merge is not None:
            out["queue"] = _sorted_queue(np.asarray(state.merge.words))
        return out


class Reference:
    """The plain reference behind the same interface, at ``precision``."""

    def __init__(self, config: dict, arrays: dict, precision: str = "highest"):
        self.rc = ref.RefConfig.from_comm(config["comm"])
        self.arrays = arrays
        self._run_fn = jax.jit(functools.partial(ref.run_chunk, self.rc,
                                                 precision=precision))
        self._run = None

    def init_state(self):
        return jax.jit(functools.partial(ref.init_state, self.rc))(
            self.arrays["neuron"])

    def compile(self, state, ext):
        compiled = self._run_fn.lower(self.arrays, state, ext).compile()
        self._run = compiled
        return compiled.as_text()

    def run(self, state, ext):
        return self._run(self.arrays, state, ext)

    @staticmethod
    def spikes(host_rec) -> np.ndarray:
        return np.asarray(host_rec["spikes"])

    @staticmethod
    def neutral_records(host_rec) -> dict:
        return {k: np.asarray(v) for k, v in host_rec.items()}

    @staticmethod
    def final(state) -> dict:
        state = jax.device_get(state)
        t = int(state["t"])
        out = {"v": np.asarray(state["v"]), "w": np.asarray(state["w"]),
               "refrac": np.asarray(state["refrac"]),
               "ring": _ring_by_deadline(np.asarray(state["ring"]), t),
               "ring_clock": np.full((state["ring"].shape[0],), t, np.int32),
               "t": np.asarray(t)}
        if "queue" in state:
            out["queue"] = _sorted_queue(np.asarray(state["queue"]))
        return out


def free(*trees) -> None:
    """Delete the device buffers of ``trees`` now."""
    for leaf in jax.tree.leaves(trees):
        if isinstance(leaf, jax.Array):
            leaf.delete()

