"""Benchmark harness — one module per paper evaluation axis.

  aggregation  — throughput / wire-efficiency / overflow vs bucket capacity,
                 merge congestion, message-rate scaling (paper §3.1 + the
                 Extoll bandwidth/message-rate axes), with before/after
                 comparison against the pre-word-format three-array exchange
  topology     — dense vs torus vs switch-tree routed fabric: us/step,
                 wire words per link, max link occupancy (paper §2.1's
                 switched network / arXiv:2111.15296's switch hierarchy)
  resilience   — healthy vs one-chip-dead fabric step, recovery-boundary
                 route recompile cost, and the two-level pod composition
  latency      — ISI-doubling demo timing + per-hop latency (paper §4)
  loss_budget  — event loss vs axonal-delay budget (paper §3.1 expiry)
  lm_roofline  — per-(arch x shape) roofline terms from the dry-run
  telemetry    — overhead of the in-scan MetricsCarry (gated <= 1.05x)

Prints ``name,us_per_call,wire_bytes,derived`` CSV; ``--json PATH``
additionally writes the rows as machine-readable JSON.  Each JSON row
is ``{name, us_per_call, wire_bytes, derived, backend}`` — ``derived``
is a structured dict (the modules' packed ``k=v;k=v`` strings are
parsed here; values coerced to int/float where they parse) and
``backend`` tags the JAX backend the row was measured on (``cpu`` /
``tpu`` / ``gpu``; rows measured under ``REPRO_FORCE_INTERPRET`` are
tagged ``interpret``) so ``benchmarks/compare.py`` can refuse
cross-backend comparisons.  ``--smoke`` shrinks every sweep to a tiny
cell for the CI smoke step; ``--only MODULE[,MODULE...]`` runs a subset
(CI's metrics-smoke uses ``--only telemetry``).
"""

from __future__ import annotations

import argparse
import json
import os

from benchmarks.compare import parse_derived


def measurement_backend() -> str:
    """The backend tag for rows measured in this process."""
    import jax

    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return "interpret"
    return jax.default_backend()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", metavar="PATH", default=None,
                   help="also write rows as JSON (e.g. BENCH_fabric.json)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny sweeps only (CI benchmark smoke)")
    p.add_argument("--only", metavar="MODULES", default=None,
                   help="comma-separated subset of benchmark modules")
    args = p.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (aggregation, latency, lm_roofline, loss_budget,
                            pipeline, resilience, telemetry, topology)

    modules = {
        "aggregation": aggregation, "topology": topology,
        "pipeline": pipeline, "resilience": resilience,
        "latency": latency, "loss_budget": loss_budget,
        "lm_roofline": lm_roofline, "telemetry": telemetry,
    }
    if args.only:
        wanted = [m.strip() for m in args.only.split(",")]
        unknown = [m for m in wanted if m not in modules]
        if unknown:
            p.error(f"unknown module(s) {unknown}; "
                    f"choose from {sorted(modules)}")
        selected = [modules[m] for m in wanted]
    else:
        selected = list(modules.values())

    print("name,us_per_call,wire_bytes,derived")
    rows = []
    for mod in selected:
        rows.extend(mod.main(csv=True, smoke=args.smoke))

    if args.json:
        backend = measurement_backend()
        payload = [
            {"name": name, "us_per_call": us, "wire_bytes": wire,
             "derived": parse_derived(derived), "backend": backend}
            for name, us, wire, derived in rows
        ]
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload)} rows to {args.json} "
              f"(backend={backend})")


if __name__ == "__main__":
    main()
