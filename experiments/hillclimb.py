"""§Perf hillclimbing driver: lower a cell with config-variant overrides and
print the roofline-term deltas vs baseline.

  PYTHONPATH=src:. python experiments/hillclimb.py zamba2-2.7b train_4k \
      '{"ssm_impl": "ssd"}' '{"ssm_impl": "ssd", "ssd_chunk": 256}'
"""

import json
import sys

from repro.launch.dryrun import lower_cell
from repro.launch.mesh import force_host_devices
from benchmarks import roofline


def run(arch, shape, variants, out_path=None):
    rows = []
    for v in [{}] + variants:
        tag = json.dumps(v, sort_keys=True)
        try:
            r = lower_cell(arch, shape, multi_pod=False, variant=v or None)
        except Exception as e:
            print(f"[error] {tag}: {type(e).__name__}: {e}", flush=True)
            continue
        t = roofline.analyze_record(r)
        r["roofline"] = t
        rows.append(r)
        print(f"[{tag}]")
        print(f"  compute {t['compute_s']:10.4f} s   memory {t['memory_s']:10.4f} s"
              f"   collective {t['collective_s']:10.4f} s   dom={t['dominant']}")
        print(f"  useful_ratio {t['useful_ratio']:.4f}   roofline {100*t['roofline_frac']:.3f}%"
              f"   compile {r['compile_s']}s", flush=True)
        if out_path:
            with open(out_path, "a") as f:
                f.write(json.dumps(r) + "\n")
    if len(rows) >= 2:
        b, t0 = rows[0]["roofline"], rows[0]["roofline"]
        for r in rows[1:]:
            t = r["roofline"]
            print(f"\ndelta [{json.dumps(r['variant'], sort_keys=True)}]: "
                  f"mem x{b['memory_s']/max(t['memory_s'],1e-12):.2f}  "
                  f"comp x{b['compute_s']/max(t['compute_s'],1e-12):.2f}  "
                  f"coll x{b['collective_s']/max(t['collective_s'],1e-12):.2f}")
    return rows


def main() -> None:
    force_host_devices(512)
    arch, shape = sys.argv[1], sys.argv[2]
    variants = [json.loads(a) for a in sys.argv[3:]]
    run(arch, shape, variants,
        out_path=f"experiments/perf_{arch}_{shape}.jsonl")


if __name__ == "__main__":
    main()
