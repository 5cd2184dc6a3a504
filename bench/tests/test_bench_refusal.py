"""The harness refuses to report where it cannot measure: no TPU, a
device kind missing from the peak table, or a directory without the
program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.harness import common

ROOT = Path(__file__).resolve().parents[2]
CELL = "train.granite-3-2b.b8x2048"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def test_refuses_a_cpu_device(capsys):
    assert run.main(ARGS) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_refuses_a_device_kind_missing_from_the_peak_table(monkeypatch,
                                                           capsys):
    monkeypatch.setattr(common, "device_record", lambda jax, chips: {
        "platform": "tpu", "kind": "TPU v0 imaginary", "count": 1})
    assert run.main(ARGS) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "not in bench/peaks.json" in err
    with pytest.raises(common.Refusal):
        common.peaks("TPU v0 imaginary")


def test_peak_table_has_the_v5e_row_with_its_source():
    row = common.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert row["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in row["source"]


def test_every_named_file_exists():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        plan = common.cell_plan(bm, w["name"])
        assert plan["config"]["driver"] in ("train", "serve")
        assert (ROOT / "bench" / "drivers"
                / f"{plan['config']['driver']}.py").exists()
        assert plan["limits"]
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
