"""`BENCHMARK.json` and the files it names: every cell, configuration,
traffic mix, driver and metric reader is found by its name."""
import json
import re

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.fullmatch(n) for n in names)
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(NAME.fullmatch(n) for n in metrics)


def test_every_metric_has_a_reader_with_its_unit():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        reader = harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py")
        assert reader.UNIT == m["unit"]
        assert callable(reader.read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell)
    assert (harness.BENCH / "drivers" / f"{c.traffic['driver']}.py").exists()
    assert (harness.BENCH / "generators"
            / f"{c.config['generator']}.py").exists()
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for name in c.per_layer:
        moves = next(m["moves"] for m in SPEC["per_layer"]
                     if m["name"] == name)
        assert moves in c.end_to_end and moves in e2e


def test_configs_state_their_cut():
    for cfg in SPEC["configs"]:
        data = json.loads((harness.ROOT / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        assert sorted(data["reduced"]) == sorted(cfg["reduced"])
        for key in cfg["reduced"]:
            assert key in data and key in data["published"]


def test_no_tpu_means_no_result(tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == harness.NO_DEVICE
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
