"""BENCHMARK.json and the files it names hold together: every cell finds
its configuration, traffic mix, limits and metric readers by name, and the
command refuses to run without a chip or without the program beside it."""
import json
import os
import re
import shutil
import subprocess
import sys

from bench.lib import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    m = manifest.manifest()
    e2e = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in e2e
    used = set()
    for w in m["workloads"]:
        cell = manifest.cell(w["name"])
        used.add(w["config"])
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert cell.limits["max_logit_gap"] > 0
        names = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for metric in cell.end_to_end + cell.per_layer:
            assert callable(manifest.reader(metric["name"]))
        for metric in cell.per_layer:
            assert metric["moves"] in names
        assert cell.config["reference"] == "llama"
    assert used == {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert c["file"].startswith("bench/configs/")


def test_command_needs_a_chip(tmp_path):
    root = manifest.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = manifest.manifest()["workloads"][0]["name"]
    cmd = [sys.executable, "bench/run.py", "--workload", w, "--seed", "1",
           "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    # a directory with the benchmark alone, without the program
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    assert json.loads(open(tmp_path / "BENCHMARK.json").read())
