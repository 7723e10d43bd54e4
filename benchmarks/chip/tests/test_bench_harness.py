"""The harness finds cells, configurations, traffic mixes and metric
readers by name, and refuses to run without a TPU or without the program
under test."""

import io
import json
import os
import shutil
import subprocess
import sys

from benchmarks.chip import run

NEW_METRIC = '''"""Chunks the window completed."""


def read(ctx):
    return ctx["window"]["steps"] / 8
'''


def test_added_files_are_picked_up_by_name(tiny_root):
    bench_dir = tiny_root / "benchmarks" / "chip"
    config = json.loads((bench_dir / "tests/data/configs/tiny.json").read_text())
    config["name"] = "tiny-k1"
    config["comm"]["fanout"] = 1
    (bench_dir / "configs" / "tiny-k1.json").write_text(json.dumps(config))
    traffic = json.loads((bench_dir / "traffic" / "tiny-volley.json").read_text())
    traffic["volley"] = {"period": 2, "chips": 3}
    (bench_dir / "traffic" / "tiny-burst.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "chunks_done.py").write_text(NEW_METRIC)
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-k1", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-k1.json",
                             "reduced": ["comm"], "why": "test"})
    bench["workloads"].append({"name": "tiny-k1.burst", "config": "tiny-k1",
                               "traffic": "tiny-burst", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "chunks_done", "unit": "chunks",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny-k1.burst"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    buf = io.StringIO()
    run.run_cell(tiny_root, "tiny-k1.burst", 5, 0.3, False, require_tpu=False,
                 out=buf, err=io.StringIO())
    result = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert result["correct"], result
    assert set(result["metrics"]) == {"sim_steps_per_s", "setup_s", "chunks_done"}
    assert result["metrics"]["chunks_done"]["value"] == result["attempted"]
    assert list(result)[-1] == "checks"


def _run_command(root, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "tiny.trials",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result(tiny_root):
    proc = _run_command(tiny_root, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.EXIT_NO_CHIP
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_unknown_workload_exits_nonzero(tiny_root):
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "nope",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == run.EXIT_SPEC and proc.stdout == ""


def test_missing_program_exits_nonzero(tiny_root):
    os.unlink(tiny_root / "src")
    try:
        run.run_cell(tiny_root, "tiny.trials", 3, 0.3, False,
                     require_tpu=False, out=io.StringIO())
    except run.RunError as e:
        assert e.code == run.EXIT_NO_PROGRAM
    else:
        raise AssertionError("ran without the program under test")
    shutil.rmtree(tiny_root / "benchmarks")
