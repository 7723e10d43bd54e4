"""Fault-tolerant training runtime: checkpoint/restart, failure injection,
straggler detection, elastic restart, chip-failure recovery.

Design notes (see README.md §Fault tolerance and the CHANGES.md entries
for PR 6; earlier revisions cited a DESIGN.md that never landed):

* **Restart determinism.**  All run state = (params, optimizer state, EF
  residuals, step counter); the data stream is a pure function of
  (seed, step).  ``TrainRunner.run`` therefore survives kill -9 at any
  point: on restart it restores the newest COMMITTED checkpoint and
  replays — property-tested to produce bitwise-identical parameters to an
  uninterrupted run (tests/test_fault.py).
* **Failure domains.**  On a real pod, a host failure surfaces as a NCCL/ICI
  timeout -> the job scheduler restarts the slice; our FailureInjector
  simulates that by raising at a chosen step.  Elasticity: restore with a
  *different* mesh (checkpoints are mesh-agnostic full arrays per leaf;
  ``resume_or(..., shardings=...)`` reshards-on-load onto whatever mesh
  the restarted job has — e.g. 8 -> 6 healthy chips with a spare row
  blocked off; tests/test_fault.py pins this).
* **Straggler mitigation.**  StepTimer keeps an EWMA of step wall-time and
  flags steps > ``threshold``x the mean.  At the framework level the
  mitigations are (a) prefetch depth (data stragglers are absorbed by the
  queue — repro.data.Prefetcher), (b) synchronous SPMD makes compute
  stragglers a hardware-health signal -> the runner records them for the
  scheduler to evict the host at the next restart boundary.
* **Fabric wiring (chip failure).**  :class:`ResilientRunner` closes the
  loop with the pulse fabric (:mod:`repro.core.resilience`): the per-step
  detector (heartbeat / credit watch) reports the surviving chip set; on
  a new death the runner freezes the schedule via :class:`ChipFailure`,
  restores the newest committed checkpoint, rebuilds the step function on
  the degraded mesh (``PulseFabric.degrade`` recompiles routes around the
  dead chips), and replays forward — in-flight events ride along in the
  checkpointed retransmit ``SendQueue`` and are re-offered on the first
  replayed step, with traffic to dead chips culled into
  ``CommStats.lost_to_failure``.  The replayed trajectory is
  bitwise-equal to an uninterrupted run on the degraded topology started
  from the same checkpoint (tests/test_resilience.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple

import jax

from repro import checkpoint as ckpt


class InjectedFailure(RuntimeError):
    """Simulated node failure (for tests/drills)."""


@dataclasses.dataclass
class FailureInjector:
    fail_at_step: int | None = None
    fired: bool = False

    def check(self, step: int) -> None:
        if (self.fail_at_step is not None and step == self.fail_at_step
                and not self.fired):
            self.fired = True
            raise InjectedFailure(f"injected node failure at step {step}")


@dataclasses.dataclass
class StepTimer:
    ewma: float = 0.0
    beta: float = 0.9
    threshold: float = 2.0
    stragglers: list = dataclasses.field(default_factory=list)
    _last: float = 0.0

    def start(self) -> None:
        self._last = time.monotonic()

    def stop(self, step: int) -> float:
        dt = time.monotonic() - self._last
        if self.ewma == 0.0:
            self.ewma = dt
        if dt > self.threshold * self.ewma:
            self.stragglers.append((step, dt, self.ewma))
        self.ewma = self.beta * self.ewma + (1 - self.beta) * dt
        return dt


@dataclasses.dataclass
class TrainRunner:
    """Generic checkpointed step loop.

    step_fn(state, step) -> state;  state is any pytree.
    """

    step_fn: Callable[[Any, int], Any]
    ckpt_dir: str
    ckpt_every: int = 10
    keep: int = 3
    async_ckpt: bool = True
    injector: FailureInjector | None = None
    timer: StepTimer = dataclasses.field(default_factory=StepTimer)

    def resume_or(self, init_state: Any, *,
                  shardings: Any = None) -> tuple[Any, int]:
        """Restore the newest committed checkpoint, or fall back to
        ``init_state``.  ``shardings`` (optional pytree matching the
        state) reshards each leaf on load — this is what lets a job
        restarted on a *smaller* mesh (dead chips blocked off) consume
        checkpoints written by the full mesh."""
        last = ckpt.latest_step(self.ckpt_dir)
        if last is None:
            return init_state, 0
        state = ckpt.restore(self.ckpt_dir, last, init_state,
                             shardings=shardings)
        return state, last + 1

    def run(self, init_state: Any, n_steps: int) -> Any:
        state, start = self.resume_or(init_state)
        writer = ckpt.AsyncCheckpointer(self.ckpt_dir) if self.async_ckpt else None
        try:
            for step in range(start, n_steps):
                if self.injector is not None:
                    self.injector.check(step)
                self.timer.start()
                state = self.step_fn(state, step)
                self.timer.stop(step)
                if (step + 1) % self.ckpt_every == 0 or step == n_steps - 1:
                    if writer is not None:
                        writer.save(state, step)
                    else:
                        ckpt.save(state, self.ckpt_dir, step)
        finally:
            if writer is not None:
                writer.close()
            ckpt.gc_old(self.ckpt_dir, keep=self.keep)
        return state


class ChipFailure(RuntimeError):
    """A chip death was detected mid-run.  Carries the step it was
    detected at and the surviving healthy chip set; raised by the
    detector inside :class:`ResilientRunner`'s step wrapper to unwind
    out of the checkpointed loop to the recovery boundary."""

    def __init__(self, step: int, surviving: tuple):
        self.step = int(step)
        self.surviving = tuple(surviving)
        super().__init__(
            f"chip failure detected at step {self.step}; "
            f"{len(self.surviving)} chips surviving")


class RecoveryEvent(NamedTuple):
    """One completed recovery: failure detected at ``detected_at``,
    resumed from step ``resumed_from`` (== newest committed checkpoint
    step + 1, or 0) on the surviving ``healthy`` chip set."""

    detected_at: int
    resumed_from: int
    healthy: tuple


@dataclasses.dataclass
class ResilientRunner:
    """Chip-failure recovery loop on top of :class:`TrainRunner`.

    freeze -> restore -> recompile -> replay -> resume:

    * ``make_step(healthy)`` builds the per-step function for a given
      healthy chip set — rebuilding is where routes get recompiled
      (``PulseFabric.degrade`` / ``NetworkConfig.healthy``).  It returns
      ``step_fn(state, step) -> (state, record)``; records land in
      ``self.records[step]`` and are pruned for replayed steps so the
      final record stream is exactly the degraded-run stream.
    * ``detect(state, step, healthy)`` inspects the post-step state
      (heartbeat / credit watch observables from
      :mod:`repro.core.resilience`) and returns the surviving chip
      tuple, or ``None`` for "no change".  A strict shrink raises
      :class:`ChipFailure`.
    * On failure: unwind, restore the newest committed checkpoint,
      rebuild the step function on the surviving mesh, and replay
      forward.  In-flight events replay from the checkpointed retransmit
      SendQueue; traffic to dead chips is culled into
      ``CommStats.lost_to_failure``.  Checkpointing is synchronous here:
      the recovery boundary must only ever see committed state.
    * **Flight recorder.**  When ``flight_of`` and ``flight_dir`` are
      set, every :class:`ChipFailure` snapshots the telemetry flight
      ring (``flight_of(state)`` extracts a
      :class:`repro.obs.FlightRing` — e.g. ``lambda s:
      s.metrics.flight``) from the *failing* state and dumps it, with
      the recovery log so far, as a structured JSONL post-mortem
      artifact ``flight_dir/flight_<step>.jsonl`` (paths collected in
      ``self.flight_dumps``).  The dump happens before the
      ``max_recoveries`` give-up check, so the terminal failure is
      post-mortemed too.
    """

    make_step: Callable[[tuple], Callable[[Any, int], tuple]]
    detect: Callable[[Any, int, tuple], tuple | None]
    ckpt_dir: str
    n_chips: int
    ckpt_every: int = 10
    keep: int = 3
    max_recoveries: int = 4
    flight_of: Callable[[Any], Any] | None = None
    flight_dir: str | None = None
    records: dict = dataclasses.field(default_factory=dict)
    recoveries: list = dataclasses.field(default_factory=list)
    flight_dumps: list = dataclasses.field(default_factory=list)
    _last_state: Any = dataclasses.field(default=None, repr=False)

    def _dump_flight(self, failure: "ChipFailure") -> None:
        if (self.flight_of is None or self.flight_dir is None
                or self._last_state is None):
            return
        from repro.obs import dump_flight
        flight = self.flight_of(self._last_state)
        if flight is None:
            return
        with jax.profiler.TraceAnnotation("fabric/recovery_dump"):
            path = (f"{self.flight_dir}/flight_{failure.step:06d}"
                    f"_{len(self.flight_dumps)}.jsonl")
            dump_flight(path, flight, recoveries=self.recoveries,
                        failure=failure,
                        meta={"n_steps_detected_at": failure.step,
                              "recoveries_so_far": len(self.recoveries)})
            self.flight_dumps.append(path)

    def run(self, init_state: Any, n_steps: int,
            healthy: tuple | None = None) -> tuple:
        """Run to ``n_steps``, recovering from chip deaths along the way.

        Returns ``(final_state, healthy)`` — the surviving chip set the
        run finished on.  Raises the final :class:`ChipFailure` if more
        than ``max_recoveries`` recoveries are needed.
        """
        healthy = (tuple(range(self.n_chips)) if healthy is None
                   else tuple(sorted(healthy)))
        while True:
            inner = self.make_step(healthy)

            def step_fn(state, step, _inner=inner, _healthy=healthy):
                state, record = _inner(state, step)
                self.records[step] = record
                self._last_state = state
                surviving = self.detect(state, step, _healthy)
                if surviving is not None:
                    surviving = tuple(sorted(surviving))
                    if surviving != _healthy:
                        raise ChipFailure(step, surviving)
                return state

            runner = TrainRunner(
                step_fn=step_fn, ckpt_dir=self.ckpt_dir,
                ckpt_every=self.ckpt_every, keep=self.keep,
                async_ckpt=False)
            try:
                return runner.run(init_state, n_steps), healthy
            except ChipFailure as failure:
                self._dump_flight(failure)
                if len(self.recoveries) >= self.max_recoveries:
                    raise
                last = ckpt.latest_step(self.ckpt_dir)
                resume_at = 0 if last is None else last + 1
                for s in [s for s in self.records if s >= resume_at]:
                    del self.records[s]
                healthy = failure.surviving
                self.recoveries.append(RecoveryEvent(
                    detected_at=failure.step, resumed_from=resume_at,
                    healthy=healthy))
