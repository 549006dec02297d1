"""The entry point refuses to run without an accelerator."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(args, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_exits_nonzero_on_a_cpu_only_host():
    proc = run(["--workload", "table1.admit", "--seed", str(2**31 + 7),
                "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode != 0 and no_result(proc)
    assert "accelerator" in proc.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(["--workload", "table1.admit", "--seed", "1",
                "--seconds", "1"], tmp_path)
    assert proc.returncode != 0 and no_result(proc)


def test_help_documents_how_to_add_a_cell():
    proc = run(["--help"], ROOT)
    assert proc.returncode == 0
    for word in ("--workload", "bench/configs/", "bench/traffic/",
                 "bench/metrics/"):
        assert word in proc.stdout


def test_every_cell_resolves_to_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert (ROOT / configs[cell["config"]]["file"]).is_file()
        mix = ROOT / "bench" / "traffic" / f"{cell['traffic']}.json"
        loop = json.loads(mix.read_text())["loop"]
        assert (ROOT / "bench" / "traffic" / f"{loop}.py").is_file()
        config = json.loads(
            (ROOT / configs[cell["config"]]["file"]).read_text())
        builder = config["tenants"]["builder"]
        assert (ROOT / "bench" / "configs" / f"{builder}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        base = m["name"].split(".")[0]
        assert (ROOT / "bench" / "metrics" / f"{base}.py").is_file(), base
