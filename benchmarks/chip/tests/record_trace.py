#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces, and
make the scratch checkouts of the benchmark's tests.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Run on a machine with a TPU, from the root of a checkout.  It traces
three calls of a small scanned program with a ``fabric/inject`` scope (a
sort) and a ``fabric/drain`` scope (a matmul), each call wrapped in
``trial/dispatch`` and ``trial/readback`` spans, and writes its HLO beside
the profile.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def scratch_root(root: Path, tiny: Path) -> Path:
    """A checkout at ``tiny`` holding the test cells, the benchmark's code
    and a link to the program under test."""
    shutil.rmtree(tiny, ignore_errors=True)
    shutil.copytree(root / "benchmarks" / "chip", tiny / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in (DATA / "traffic").iterdir():
        shutil.copy(f, tiny / "benchmarks" / "chip" / "traffic" / f.name)
    shutil.copy(DATA / "BENCHMARK.json", tiny / "BENCHMARK.json")
    os.symlink(root / "src", tiny / "src")
    return tiny


def toy_program():
    """The toy: 8 scan steps over a [256, 512] carry."""
    import jax
    import jax.numpy as jnp

    def body(c, x):
        with jax.named_scope("fabric/inject"):
            y = jnp.sort(c + x, axis=-1)
        with jax.named_scope("fabric/drain"):
            z = y @ y.T
        return c + y * 1e-6, z.sum()

    def f(c, xs):
        return jax.lax.scan(body, c, xs)

    return jax.jit(f), ((256, 512), (8, 256, 512))


def record_toy(out: Path) -> int:
    import jax
    import jax.numpy as jnp

    f, (c_shape, xs_shape) = toy_program()
    c, xs = jnp.ones(c_shape), jnp.ones(xs_shape)
    compiled = f.lower(c, xs).compile()
    jax.block_until_ready(compiled(c, xs))
    jax.profiler.start_trace(str(out))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("trial/dispatch"):
            result = compiled(c, xs)
        with jax.profiler.TraceAnnotation("trial/readback"):
            jax.device_get(result)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    (out / "program.hlo").write_text(compiled.as_text())
    return 0


def main() -> int:
    out = Path(sys.argv[1]).resolve()
    out.mkdir(parents=True, exist_ok=True)
    return record_toy(out)


if __name__ == "__main__":
    sys.exit(main())
