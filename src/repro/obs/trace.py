"""Phase tracing: jit-safe named scopes for device profiles plus a
lightweight host-side span timer for benchmark drivers.

``phase_scope(name)`` stacks two annotations:

* :func:`jax.named_scope` — threads the name into XLA op metadata so a
  device profile (or an HLO dump) attributes time to fabric stages.
  It adds *metadata only*: op counts, scheduling, and numerics are
  untouched, so the one-collective-per-block HLO pins keep holding.
* :class:`jax.profiler.TraceAnnotation` — marks the host timeline when
  a profiler session is active; a silent no-op otherwise.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

import jax


@contextlib.contextmanager
def phase_scope(name: str) -> Iterator[None]:
    """Annotate a fabric phase for device + host profiles (no-op cost)."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


class SpanTimer:
    """Host-side wall-clock span accumulator for benchmark/serve drivers.

    Not for in-jit use — this times host-visible phases (staging,
    dispatch, block_until_ready boundaries).  Spans nest freely; each
    named span accumulates count/total and tracks the max.
    """

    def __init__(self) -> None:
        self._spans: dict[str, dict[str, float]] = {}

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            with phase_scope(name):
                yield
        finally:
            dt_ms = (time.perf_counter() - t0) * 1e3
            s = self._spans.setdefault(
                name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0})
            s["count"] += 1
            s["total_ms"] += dt_ms
            s["max_ms"] = max(s["max_ms"], dt_ms)

    def summary(self) -> dict[str, dict[str, float]]:
        """name -> {count, total_ms, mean_ms, max_ms}."""
        out = {}
        for name, s in self._spans.items():
            out[name] = {
                "count": int(s["count"]),
                "total_ms": s["total_ms"],
                "mean_ms": s["total_ms"] / max(1, s["count"]),
                "max_ms": s["max_ms"],
            }
        return out

    def report(self) -> str:
        lines = [f"{'span':<28} {'count':>6} {'mean_ms':>9} "
                 f"{'max_ms':>9} {'total_ms':>10}"]
        for name, s in sorted(self.summary().items()):
            lines.append(f"{name:<28} {s['count']:>6d} {s['mean_ms']:>9.3f} "
                         f"{s['max_ms']:>9.3f} {s['total_ms']:>10.3f}")
        return "\n".join(lines)
