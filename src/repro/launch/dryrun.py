"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (GSPMD partitions every op; no
    mismatched-sharding errors),
  * it fits (compiled.memory_analysis() per-device bytes),
  * and it yields the roofline inputs (cost_analysis FLOPs/bytes +
    collective operand bytes parsed from the optimized HLO).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out experiments/dryrun
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.launch import hlo_stats
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.models import lm
from repro.models import sharding as shd
from repro.optim import adamw, schedules

# ---------------------------------------------------------------------------
# Step functions (the same ones train.py / serve.py jit)
# ---------------------------------------------------------------------------


def build_train_step(cfg, rules):
    def train_step(params, opt_state, batch):
        def lf(p):
            return lm.loss_fn(cfg, p, batch, rules, remat=True)

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        if cfg.zero2 and rules is not None:
            # ZeRO-2: pin gradients to the moment sharding so the backward
            # reduction lowers to reduce-scatter (each data shard owns a
            # gradient slice) instead of a full all-reduce.
            gspecs = adamw.zero_pspecs(lm.model_spec(cfg), rules)
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, jax.sharding.NamedSharding(rules.mesh, s)),
                grads, gspecs,
            )
        lr = schedules.warmup_cosine(
            opt_state.count, peak_lr=3e-4, warmup_steps=2000,
            total_steps=100_000,
        )
        new_params, new_opt, om = adamw.update(
            grads, opt_state, params, lr=lr
        )
        metrics.update(om)
        return new_params, new_opt, metrics

    return train_step


def build_prefill_step(cfg, rules):
    def prefill_step(params, batch):
        logits, cache = lm.prefill(cfg, params, batch, rules)
        return jnp.argmax(logits, axis=-1), cache

    return prefill_step


def build_decode_step(cfg, rules):
    def decode_step(params, token, cache, pos):
        logits, new_cache = lm.decode(cfg, params, token, cache, pos, rules)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               hlo_dir: str | None = None,
               variant: dict | None = None) -> dict:
    """variant: dataclasses.replace overrides on the ArchConfig — the §Perf
    hillclimbing entry point (e.g. {"ssm_impl": "ssd"})."""
    import dataclasses as _dc

    cfg = C.get(arch_id)
    kv_factored = 0
    if variant:
        variant = dict(variant)
        kv_factored = variant.pop("_mesh_kv", 0)   # mesh-level lever
        if variant:
            cfg = _dc.replace(cfg, **variant)
    shape = C.SHAPES[shape_name]
    ok, why = C.runnable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name,
                "multi_pod": multi_pod, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod, kv_factored=kv_factored)
    rules = shd.from_mesh(mesh)
    ns = lambda tree: jax.tree.map(
        lambda p: jax.sharding.NamedSharding(mesh, p), tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )

    param_shapes = lm.param_shapes(cfg)
    param_sh = ns(lm.param_pspecs(cfg, rules))
    batch_shapes = lm.input_specs(cfg, shape)
    batch_sh = ns(lm.batch_pspecs(cfg, shape, rules))

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            spec_tree = lm.model_spec(cfg)
            opt_shapes = adamw.state_shapes(param_shapes)
            opt_sh = ns(adamw.zero_state_pspecs(spec_tree, rules))
            fn = build_train_step(cfg, rules)
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
            )
            lowered = jitted.lower(param_shapes, opt_shapes, batch_shapes)
        elif shape.kind == "prefill":
            fn = build_prefill_step(cfg, rules)
            jitted = jax.jit(
                fn, in_shardings=(param_sh, batch_sh), out_shardings=None
            )
            lowered = jitted.lower(param_shapes, batch_shapes)
        else:  # decode / long_decode
            fn = build_decode_step(cfg, rules)
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, batch_sh["token"], batch_sh["cache"],
                              batch_sh["pos"]),
                out_shardings=(None, batch_sh["cache"]),
            )
            lowered = jitted.lower(param_shapes, batch_shapes["token"],
                                   batch_shapes["cache"], batch_shapes["pos"])
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = hlo_stats.analyze(hlo)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch_id}__{shape_name}__{'mp' if multi_pod else 'sp'}"
        with open(os.path.join(hlo_dir, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)

    def _get(obj, name):
        v = getattr(obj, name, None)
        return int(v) if v is not None else None

    result = {
        "arch": arch_id,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "variant": variant or {},
        "status": "ok",
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": _get(mem, "argument_size_in_bytes"),
            "output_bytes": _get(mem, "output_size_in_bytes"),
            "temp_bytes": _get(mem, "temp_size_in_bytes"),
            "generated_code_bytes": _get(mem, "generated_code_size_in_bytes"),
        },
        # raw XLA numbers (loop bodies counted once — see hlo_stats docstring)
        "cost_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        # loop-aware per-device numbers (roofline inputs)
        "hlo": {
            "flops": stats.flops,
            "hbm_bytes": stats.hbm_bytes,
            "collective_bytes": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "collective_total": stats.total_collective_bytes,
            "unknown_trip_whiles": stats.unknown_trip_whiles,
        },
    }
    return result


def lower_snn(n_chips: int, mode: str = "simplified",
              merge_rate: int = 0, topology=None) -> dict:
    """Dry-run the PAPER'S OWN system at production scale: a BSS-2
    multi-chip network with chips as mesh shards, one full simulation step
    (neuron dynamics -> events -> routing LUT -> buckets -> all_to_all ->
    [stateful merge] -> delay rings) lowered + compiled per-shard under
    shard_map.

    n_chips=46 is one wafer module; n_chips=512 is the multi-wafer tier
    (11 modules) — the Extoll-scale deployment the paper targets.
    mode="full" with merge_rate > 0 additionally threads the persistent
    per-chip merge queue through the shard_map step (the deferred temporal
    merging of the complete scheme).  ``topology`` (a
    ``repro.core.topology.Topology``) replaces the dense exchange with the
    hop-by-hop routed fabric — the per-shard step then lowers to the
    topology's ppermute neighbor schedule instead of one all_to_all.
    """
    import dataclasses as _dc

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.bss2 import CONFIG as BSS2
    from repro.core import delays as dl
    from repro.core import merge as mg
    from repro.core.routing import RoutingTable
    from repro.snn import network as net
    from repro.snn import neuron as nr
    from repro.snn.synapse import Crossbar

    devices = jax.devices()
    if len(devices) < n_chips:
        raise RuntimeError(f"need {n_chips} devices")
    if topology is not None and topology.kind == "pod":
        # Two-level ("pod", "chip") mesh: the chip axis is the dense
        # intra-pod tier, the pod axis carries the routed pod graph.
        n_pods = topology.n_pods
        cpp = n_chips // n_pods
        if n_pods * cpp != n_chips:
            raise ValueError(f"{n_chips} chips != {n_pods} pods x {cpp}")
        mesh = Mesh(np.asarray(devices[:n_chips]).reshape(n_pods, cpp),
                    ("pod", "chip"))
        axis: str | tuple = ("pod", "chip")
    else:
        mesh = Mesh(np.asarray(devices[:n_chips]), ("chip",))
        axis = "chip"
    comm = _dc.replace(BSS2.comm, n_chips=n_chips, mode=mode,
                       merge_rate=merge_rate)
    cfg = net.NetworkConfig(comm=comm, neuron_model=BSS2.neuron_model,
                            topology=topology)

    c = comm
    f32 = jnp.float32
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    n, ni, k = c.neurons_per_chip, c.n_inputs_per_chip, c.fanout
    stacked = lambda tree: jax.tree.map(
        lambda x: sds((n_chips,) + x.shape, x.dtype), tree)
    nparams = nr.adex_params(n)
    params = net.NetworkParams(
        crossbar=Crossbar(w=sds((n_chips, ni, n), f32)),
        neuron=stacked(nparams),
        table=RoutingTable(
            dest_chip=sds((n_chips, n, k), i32),
            dest_addr=sds((n_chips, n, k), i32),
            delay=sds((n_chips, n, k), i32),
            valid=sds((n_chips, n, k), jnp.bool_),
        ),
    )
    merge_state = None
    if mode == "full" and merge_rate > 0:
        merge_state = mg.MergeBuffer(
            words=sds((n_chips, c.merge_depth), i32),
        )
    state = net.NetworkState(
        neuron=stacked(nr.adex_init(nparams)),
        ring=dl.DelayRing(ring=sds((n_chips, c.ring_depth, ni), i32),
                          now=sds((n_chips,), i32)),
        t=sds((), i32),
        merge=merge_state,
    )
    ext = sds((n_chips, ni), f32)
    step = net.shard_map_block(cfg, mesh, axis)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(step).lower(params, state, ext)
        compiled = lowered.compile()
    stats = hlo_stats.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    tag = f"{n_chips}chips" if mode == "simplified" \
        else f"{n_chips}chips-merge{merge_rate}"
    if topology is not None:
        if topology.kind == "pod":
            tag += (f"-pod{topology.n_pods}x{n_chips // topology.n_pods}"
                    f"-{topology.pod_graph.kind}")
        else:
            tag += f"-{topology.kind}"
            if topology.dims:
                tag += "x".join(str(d) for d in topology.dims)
    return {
        "arch": "bss2-snn",
        "shape": tag,
        "status": "ok",
        "n_devices": n_chips,
        "compile_s": round(time.time() - t0, 1),
        "memory": {"argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0))},
        "hlo": {
            "flops": stats.flops,
            "hbm_bytes": stats.hbm_bytes,
            "collective_bytes": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "collective_total": stats.total_collective_bytes,
        },
    }


# Per-arch optimized variants discovered by the §Perf hillclimbing
# (EXPERIMENTS.md): applied by --optimized for the beyond-paper sweep.
OPTIMIZED_VARIANTS = {
    "llama4-maverick-400b-a17b": {"head_pad": 48, "moe_dispatch": "local",
                                  "attn_q_chunk": 2048,
                                  "attn_kv_chunk": 2048},
    "granite-moe-1b-a400m": {"moe_dispatch": "local", "attn_q_chunk": 2048,
                             "attn_kv_chunk": 2048},
    "zamba2-2.7b": {"ssm_impl": "ssd", "ssd_chunk": 256},
    "mistral-nemo-12b": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "yi-9b": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "llama3-8b": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "internlm2-1.8b": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "chameleon-34b": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "whisper-medium": {"attn_q_chunk": 2048, "attn_kv_chunk": 2048},
    "falcon-mamba-7b": {"ssm_unroll": 32},
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out", default=None, help="JSONL output path")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--optimized", action="store_true",
                    help="apply per-arch §Perf variants")
    ap.add_argument("--snn", action="store_true",
                    help="dry-run the paper's BSS-2 system (46 + 512 chips)")
    ap.add_argument("--pod-only", action="store_true",
                    help="with --snn: only the 512-chip (pod x chip) cell "
                         "(the CI fault-drill smoke)")
    args = ap.parse_args()
    force_host_devices(512)

    if args.snn:
        from repro.core import topology as tpo

        # 512 chips as 8 pods x 64 chips: dense intra-pod exchange, routed
        # ring of pods — the Extoll multi-wafer tier as a two-level mesh.
        pod512 = tpo.pod(tpo.ring(8), 64)
        cells = [(46, "simplified", 0, None), (512, "simplified", 0, None),
                 (46, "full", 32, None),
                 (64, "simplified", 0, tpo.torus2d(8, 8)),
                 (512, "simplified", 0, pod512)]
        if args.pod_only:
            cells = [(512, "simplified", 0, pod512)]
        for n_chips, mode, merge_rate, topology in cells:
            r = lower_snn(n_chips, mode=mode, merge_rate=merge_rate,
                          topology=topology)
            print(f"[     ok] bss2-snn x {r['shape']} "
                  f"flops={r['hlo']['flops']:.3g} "
                  f"coll={r['hlo']['collective_total']:.3g}B "
                  f"compile={r['compile_s']}s", flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps({**r, "multi_pod": n_chips > 46}) + "\n")
        return

    cells = []
    archs = C.ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(C.SHAPES) if (args.all or args.shape is None) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in pods:
                cells.append((a, s, mp))

    results = []
    for a, s, mp in cells:
        tag = f"{a} x {s} x {'2x16x16' if mp else '16x16'}"
        variant = OPTIMIZED_VARIANTS.get(a) if args.optimized else None
        try:
            r = lower_cell(a, s, multi_pod=mp, hlo_dir=args.hlo_dir,
                           variant=variant)
        except Exception as e:
            r = {"arch": a, "shape": s, "multi_pod": mp, "status": "error",
                 "error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc()[-2000:]}
        results.append(r)
        status = r["status"]
        extra = ""
        if status == "ok":
            mb = (r["memory"]["argument_bytes"] or 0) / 2**20
            extra = (f" args={mb:.0f}MiB flops={r['hlo']['flops']:.3g}"
                     f" coll={r['hlo']['collective_total']:.3g}B"
                     f" compile={r['compile_s']}s")
        elif status == "error":
            extra = " " + r["error"][:200]
        print(f"[{status:>7s}] {tag}{extra}", flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_err} errors "
          f"out of {len(results)} cells")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
