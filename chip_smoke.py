#!/usr/bin/env python3
"""Chip smoke test: the BSS-2 wafer-module network on a TPU.

Drives ``snn.network.run`` over T=64 steps at the widths of the wafer
module in ``configs/bss2.py`` (46 simulated chips, 512 AdEx neurons and
256 inputs per chip, E=512, fanout 4, bucket capacity 32, ring depth 32)
with the superstep schedule B=4, on the local transport of one TPU chip.
Tables and weights come from ``init_params(PRNGKey(seed))``; the external
drive is a seeded Bernoulli input strong enough that every simulated chip
sends events.

Phases (one chip, the default):

  (a) the jnp chain (``use_pallas=False``): the reference; the event
      conservation identity is closed on its totals;
  (b) the same run with ``use_pallas=True``: ``bucket_pack`` and the
      fused drain compiled for the chip;
  (c) full mode with ``merge_rate=8``, ``merge_depth=256``, both ways:
      the fused drain's rate mode (bitonic sort plus the bounded queue);
      and full mode with ``merge_rate=0``: its sort mode;
  (d) ``fanout=1``, both ways: the fused inject kernel.

Each Pallas run must equal its jnp twin bit for bit — spike trains,
membrane voltages, delivered counts, ring contents and every
``CommStats`` field — and its compiled program must hold
``tpu_custom_call`` (the kernels were compiled, not interpreted).  The
jnp run of (a) is repeated on the host CPU backend, from the same
inputs: an independent check of the chip's results.  Its integer leaves
(spike trains, ring, ``CommStats``) must equal the chip's bit for bit,
its float leaves (membrane and adaptation state, voltage record) may
differ by at most ``CPU_FLOAT_TOL``.

``--chips 4`` runs only the path across chips: 4 simulated chips at the
wafer module's per-chip widths, one per device, through
``net.shard_run`` (``shard_superstep`` under ``jax.shard_map``), once on
the dense transport (``all_to_all``) and once on ``torus2d(2, 2)``
(``ppermute`` forwarding), each compared with ``net.run`` on one device.

Usage::

    python chip_smoke.py [--seed N]        # one chip, phases (a)-(d)
    python chip_smoke.py --chips 4         # four chips, shard_map path

The last line of standard output is ``{"ok": true, "device": {...}}`` on
success.  The script exits non-zero without that line when JAX finds no
TPU, when Pallas interpret mode is forced, when it is not run from a
checkout of the repository, or when any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STEPS = 64
SUPERSTEP = 4
DRIVE_RATE = 0.1      # Bernoulli rate of each external input per step
# Bound on the float leaves (membrane state, voltage record) of a run on
# the host CPU against the chip: the crossbar's summation order, the f32
# passes of a HIGHEST matmul on the TPU and the AdEx exp round differently
# and the leaky integration carries the error over steps.  The voltages
# are in units where v_peak = 1.2; on a v5e the largest difference at the
# wafer module's widths was 2.44e-4.  A bf16 crossbar would be caught.
CPU_FLOAT_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def check_device(n_chips: int):
    """Exit unless JAX sees ``n_chips`` TPU devices and kernels compile."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {len(devices)} {dev.platform} device(s)")
    if len(devices) < n_chips:
        fail(f"--chips {n_chips} needs {n_chips} TPU devices, JAX found "
             f"{len(devices)}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"run from a checkout of the repository: {ROOT / 'src'} "
             "holds no repro package")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import common

    if common.force_interpret():
        fail(f"{common.FORCE_INTERPRET_ENV} is set: kernels would not be "
             "compiled for the chip")
    if common.resolve_interpret(None):
        fail("Pallas kernels would run in interpret mode")
    return devices


def drive(seed: int, comm, steps: int):
    """Seeded Bernoulli input spikes ``[T, n_chips, n_inputs]``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    shape = (steps, comm.n_chips, comm.n_inputs_per_chip)
    return (jax.random.uniform(key, shape) < DRIVE_RATE).astype(jnp.float32)


def compile_and_run(fn, *args):
    """Compile ``fn`` for ``args``, run it twice; returns the result,
    compile seconds, steady-state seconds of the second run and the
    compiled program."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t0, compiled


def mismatches(ref, got, *, float_tol: float = 0.0) -> list[str]:
    """Paths of the leaves of ``got`` that differ from ``ref``: integer
    and bool leaves bit for bit, float leaves by more than ``float_tol``
    (0: bit for bit)."""
    import jax
    import numpy as np

    if jax.tree.structure(ref) != jax.tree.structure(got):
        return ["<tree structure>"]
    bad = []
    flat_ref = jax.tree_util.tree_flatten_with_path(ref)[0]
    for (path, a), b in zip(flat_ref, jax.tree.leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        if float_tol and np.issubdtype(a.dtype, np.floating):
            same = a.shape == b.shape and bool(
                np.all(np.abs(a - b) <= float_tol))
        else:
            same = np.array_equal(a, b)
        if not same:
            bad.append(jax.tree_util.keystr(path))
    return bad


def float_diff(ref, got) -> float:
    """Largest absolute difference over the float leaves."""
    import jax
    import numpy as np

    return max((float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got))
                if np.issubdtype(np.asarray(a).dtype, np.floating)
                and np.size(a)), default=0.0)


def conservation(final, rec):
    """Close Σ sent == delivered + ring + queued + dropped on one run."""
    import numpy as np

    from repro import obs

    delivered = (np.asarray(rec.delivered).sum()
                 + np.asarray(final.ring.ring).sum())
    queued = 0 if final.merge is None else final.merge.occupancy()
    return obs.check_conservation(rec.stats, delivered=delivered,
                                  queued=queued, strict=False)


def run_local(cfg, seed: int, steps: int, device=None):
    """``net.run`` on the local transport; with ``device``, the inputs
    (made on the default device) are moved there and the run with them."""
    import jax

    from repro.snn import network as net

    params = net.init_params(jax.random.PRNGKey(seed), cfg)
    args = (params, net.init_state(cfg, params), drive(seed, cfg.comm, steps))
    if device is not None:
        args = jax.device_put(args, device)
    return compile_and_run(functools.partial(net.run, cfg), *args)


def report(label: str, out, compile_s: float, run_s: float, steps: int,
           **extra) -> None:
    import numpy as np

    sent = int(np.asarray(out[1].stats.sent).sum())
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"{label:<34} compile_s={compile_s:.2f} "
          f"sim_steps_per_s={steps / run_s:.1f} events_sent={sent} {fields}",
          flush=True)


def one_chip(comm, seed: int, steps: int) -> list[str]:
    """Phases (a)-(d); returns the failures."""
    import numpy as np

    from repro.snn import network as net

    failures = []
    variants = [
        ("(a)/(b) simplified, fanout 4", comm),
        ("(c) full, merge_rate 8", dataclasses.replace(
            comm, mode="full", merge_rate=8, merge_depth=256)),
        ("(c) full, sort", dataclasses.replace(comm, mode="full")),
        ("(d) fanout 1", dataclasses.replace(comm, fanout=1)),
    ]
    for name, c in variants:
        cfg = net.NetworkConfig(comm=c, neuron_model="adex")
        ref, comp_s, run_s, _ = run_local(cfg, seed, steps)
        rep = conservation(*ref)
        per_chip = np.asarray(ref[1].stats.sent).sum(axis=0)
        report(f"{name} jnp", ref, comp_s, run_s, steps,
               conservation="closed" if rep.ok else f"residual={rep.residual}",
               chips_sending=f"{int((per_chip > 0).sum())}/{c.n_chips}")
        if not rep.ok:
            failures.append(f"{name}: conservation residual {rep.residual}")
        if not (per_chip > 0).all():
            failures.append(f"{name}: a simulated chip sent no events")
        if c is comm:
            failures += cross_check_cpu(cfg, seed, steps, ref)

        cfgp = dataclasses.replace(
            cfg, comm=dataclasses.replace(c, use_pallas=True))
        got, comp_s, run_s, compiled = run_local(cfgp, seed, steps)
        bad = mismatches(ref, got)
        kernels = "tpu_custom_call" in compiled.as_text()
        report(f"{name} pallas", got, comp_s, run_s, steps,
               bit_equal=not bad, tpu_custom_call=kernels)
        if bad:
            failures.append(f"{name}: pallas differs from jnp in {bad}")
        if not kernels:
            failures.append(f"{name}: no tpu_custom_call in the program")
    return failures


def cross_check_cpu(cfg, seed: int, steps: int, ref) -> list[str]:
    """The jnp run again on the host CPU backend; returns the failures."""
    import jax

    got, comp_s, run_s, _ = run_local(cfg, seed, steps,
                                      device=jax.devices("cpu")[0])
    bad = mismatches(ref, got, float_tol=CPU_FLOAT_TOL)
    report("(a) jnp on the host CPU", got, comp_s, run_s, steps,
           matches_chip=not bad, float_max_abs_diff=float_diff(ref, got))
    return [f"(a): the CPU run differs from the chip's in {bad}"
            ] if bad else []


def four_chips(comm, seed: int, steps: int, *,
               float_tol: float = 0.0) -> list[str]:
    """The shard_map path on 4 devices against net.run on one: every leaf
    bit for bit, the float leaves within ``float_tol`` if that is set."""
    import jax

    from repro.core import topology as tpo
    from repro.launch import hlo_stats
    from repro.launch.mesh import make_chip_mesh
    from repro.snn import network as net

    failures = []
    mesh = make_chip_mesh(4)
    for name, topo, collective in (
            ("dense", None, "all-to-all"),
            ("torus2d(2,2)", tpo.torus2d(2, 2), "collective-permute")):
        cfg = net.NetworkConfig(comm=comm, neuron_model="adex",
                                topology=topo)
        ref, comp_s, run_s, _ = run_local(cfg, seed, steps)
        report(f"{name} net.run one device", ref, comp_s, run_s, steps)

        params = net.init_params(jax.random.PRNGKey(seed), cfg)
        state = net.init_state(cfg, params)
        got, comp_s, run_s, compiled = compile_and_run(
            functools.partial(net.shard_run, cfg, mesh), params, state,
            drive(seed, comm, steps))
        bad = mismatches(ref, got, float_tol=float_tol)
        spread = {len(x.sharding.device_set)
                  for x in jax.tree.leaves((got[0].ring, got[1]))}
        n_coll = hlo_stats.count_collectives(compiled, collective)
        report(f"{name} shard_run 4 devices", got, comp_s, run_s, steps,
               matches=not bad, float_max_abs_diff=float_diff(ref, got),
               devices_per_output=sorted(spread),
               **{collective: n_coll})
        if bad:
            failures.append(f"{name}: shard_run differs from run in {bad}")
        if spread != {4}:
            failures.append(f"{name}: outputs on {sorted(spread)} devices, "
                            "not spread over 4")
        if not n_coll:
            failures.append(f"{name}: no {collective} in the program")
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip shard_map path")
    args = ap.parse_args(argv)

    devices = check_device(args.chips)
    from repro.configs.bss2 import CONFIG
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    comm = dataclasses.replace(CONFIG.comm, superstep=SUPERSTEP)
    if args.chips == 4:
        failures = four_chips(dataclasses.replace(comm, n_chips=4),
                              args.seed, STEPS)
    else:
        failures = one_chip(comm, args.seed, STEPS)
    if failures:
        fail("FAILED:\n  " + "\n  ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
