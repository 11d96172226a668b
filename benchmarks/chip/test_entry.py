"""The command's contract around a run: no result without a TPU or
without the program, and new cells, configurations, traffic and metrics
found by their names without an edit to any file that is there."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchmarks.chip import harness

ROOT = harness.ROOT
IGNORE = shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__")


def run_cmd(cwd, env_extra=None, args=("--workload", "housing.stream",
                                       "--seed", "3", "--seconds", "1",
                                       "--trace", "0")):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(proc) -> bool:
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def checkout_of_benchmark(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=IGNORE)
    return tmp_path


def test_without_a_tpu_no_result():
    proc = run_cmd(ROOT)
    assert proc.returncode != 0
    assert no_result(proc)
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    proc = run_cmd(checkout_of_benchmark(tmp_path))
    assert proc.returncode != 0
    assert no_result(proc)


NEW_FILES = {
    "configs/tiny.json": None,  # housing's, renamed (filled in below)
    "references/tiny.py": "from .housing import Reference  # noqa: F401\n",
    "traffic/trickle.json": json.dumps({"batch": 32, "read_rate": 4}),
    "metrics/answer_e2e.py": "def read(run):\n    return 42.0\n",
}


def test_new_cell_found_by_name(tmp_path):
    root = checkout_of_benchmark(tmp_path)
    chip = root / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs" / "housing.json").read_text())
    cfg["name"] = "tiny"
    for rel, text in NEW_FILES.items():
        (chip / rel).write_text(text if text is not None
                                else json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0], "name": "tiny",
                             "file": "benchmarks/chip/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                               "traffic": "trickle", "chips": 1,
                               "why": "a cell added as files"})
    bench["end_to_end"].append({"name": "answer_e2e", "unit": "count",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import sys, json\n"
        f"sys.path[:0] = [{str(root)!r}, {os.path.join(ROOT, 'src')!r}]\n"
        "from benchmarks.chip import rehearsal\n"
        "out, line = rehearsal.run('tiny.trickle', seed=9, seconds=1.5)\n"
        "print(json.dumps(line))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["answer_e2e"]["value"] == 42.0
    assert line["attempted"] > 0
