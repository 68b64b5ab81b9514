"""The harness: found by name from BENCHMARK.json, and no result off-TPU."""
import json
import os
import subprocess
import sys

from chip.harness import CHECKOUT, HERE, Cell, load_loop, load_reader

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cell = Cell.find(w["name"], BENCH)
        assert load_loop(cell.traffic["loop"]).run
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert load_reader(m["name"]).read
            assert m["moves"] in names


def test_metric_workloads_name_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
