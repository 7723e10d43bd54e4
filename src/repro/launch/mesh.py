"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run's ``main`` asks for 512 host
devices (:func:`force_host_devices`) before first jax init, and smoke tests
must keep seeing 1 device.

Topology: TPU v5e pods, 16x16 = 256 chips per pod on the ICI torus;
multi-pod adds a leading "pod" axis over DCN.  (The Extoll analogue: the
paper's 3D torus; the "pod" axis is the inter-wafer-module tier.)
"""

from __future__ import annotations

import math
import os

import jax
import numpy as np


def force_host_devices(n: int) -> None:
    """Ask XLA's CPU backend for ``n`` virtual devices, keeping whatever
    ``XLA_FLAGS`` the caller set.  Entry points call this before JAX
    first touches a device."""
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), flag) if f)


def make_production_mesh(*, multi_pod: bool = False,
                         kv_factored: int = 0) -> jax.sharding.Mesh:
    """kv_factored=K splits the 16-way tensor tier into ("kv", "mp") =
    (K, 16//K) so GQA caches shard K ways (serving §Perf lever)."""
    if kv_factored:
        mp = 16 // kv_factored
        shape = ((2, 16, kv_factored, mp) if multi_pod
                 else (16, kv_factored, mp))
        axes = (("pod", "data", "kv", "mp") if multi_pod
                else ("data", "kv", "mp"))
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dry-run) "
            "or on a real pod"
        )
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def make_host_mesh(model_parallel: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    devices = jax.devices()
    n = len(devices)
    mp = max(1, min(model_parallel, n))
    dp = n // mp
    dev_array = np.asarray(devices[: dp * mp]).reshape(dp, mp)
    return jax.sharding.Mesh(dev_array, ("data", "model"))


def make_chip_mesh(n_chips: int | None = None) -> jax.sharding.Mesh:
    """1-D chip mesh for the SNN production path (chips = shards)."""
    devices = jax.devices()
    n = n_chips or len(devices)
    dev_array = np.asarray(devices[:n])
    return jax.sharding.Mesh(dev_array, ("chip",))
