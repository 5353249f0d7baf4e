"""The measurement path refuses the CPU, too few chips and unknown kinds,
and the benchmark's files are all found by name."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness

PEAKS = harness.load_json(harness.BENCH_DIR / "peaks.json")


def _devices(platform, kind, n=1):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_tpu_v5e_passes():
    d = harness.check_device(_devices("tpu", "TPU v5 lite"), 1, PEAKS)
    assert d == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_cpu_platform_fails():
    with pytest.raises(harness.NoChip):
        harness.check_device(_devices("cpu", "cpu"), 1, PEAKS)


def test_unknown_device_kind_fails():
    with pytest.raises(harness.BenchError):
        harness.check_device(_devices("tpu", "TPU v99"), 1, PEAKS)


def test_too_few_chips_fails():
    with pytest.raises(harness.NoChip):
        harness.check_device(_devices("tpu", "TPU v5 lite"), 4, PEAKS)


def test_peaks_name_their_source():
    for kind, row in PEAKS.items():
        assert row["source"] and row["hbm_bytes_per_s"] > 0, kind


def test_cli_on_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(harness.BENCH_DIR / "run.py"), "--workload",
           "wsi-morph-4k.ih", "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_every_cell_resolves_to_its_files():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    e2e_known = {"mpix_per_s", "setup_s"}
    assert {m["name"] for m in bench["end_to_end"]} <= e2e_known
    for m in bench["per_layer"]:
        assert (harness.BENCH_DIR / "layers" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (harness.BENCH_DIR / "ops" / f"{cell.config['op']}.py").is_file()
        for step in cell.traffic["steps"]:
            assert (harness.BENCH_DIR / "generators"
                    / f"{step['gen']}.py").is_file()
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        # No engine, tile or drain knob: the window runs run_op's defaults.
        knobs = {"engine", "tile", "drain_batch", "autotune", "queue_capacity"}
        params = set().union(*map(set, cell.traffic["steps"]))
        assert not knobs & (set(cell.config) | set(cell.traffic) | params)
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")
