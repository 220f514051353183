"""Tiny rehearsals of every cell on the CPU, the control's readings,
and the runs that must print no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import control
from bench import run as bench_run
from bench.cell import ROOT

TINY = dict(rows_per_tenant=3000, ops_per_tenant=4096, batch=512)
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
FOUR_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_rehearses_correct_without_device_metrics(workload):
    rc, out = bench_run.run(
        ["--workload", workload, "--seed", str(2 ** 31 + 5), "--seconds",
         "0.1", "--trace", "0"], allow_cpu=True, overrides=TINY)
    assert rc == 0
    assert out["correct"] is True, out["checks"]
    assert out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 4096 and out["failed"] == 0


FOUR_CHIP_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import run as bench_run
for trace in (0, 1):
    rc, out = bench_run.run(
        ["--workload", {workload!r}, "--seed", str(2 ** 31 + 6),
         "--seconds", "0.1", "--trace", str(trace)], allow_cpu=True,
        overrides=dict(rows_per_tenant=3000, ops_per_tenant=4096,
                       traced_ops_per_tenant=1024, batch=512))
    print("RESULT", json.dumps([rc, out["correct"], out["attempted"],
                                out["metrics"], out["checks"]]))
"""


@pytest.mark.parametrize("workload", FOUR_CHIP)
def test_four_chip_cell_rehearses_correct_on_four_devices(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIP_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                                workload=workload)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    results = [json.loads(line.split(" ", 1)[1])
               for line in proc.stdout.splitlines()
               if line.startswith("RESULT")]
    assert len(results) == 2
    for (rc, correct, attempted, metrics, checks), ops in zip(
            results, (4 * 4096, 4 * 1024)):
        assert rc == 0 and correct is True, checks
        assert attempted >= ops and metrics == {}
    assert "'mode': 'shard_map', 'devices': 4" in proc.stderr


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_fails_and_sound_passes(workload):
    seed = 2 ** 31 + 9
    sound = control.readings(workload, seed, sound=True, overrides=TINY)
    ctl = control.readings(workload, seed, overrides=TINY)
    assert all(v == 0 for v in sound.values()), sound
    assert any(v > 0 for v in ctl.values()), ctl


def _cli(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_result():
    proc = _cli(ROOT, ONE_CHIP[0])
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_exit_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path, ONE_CHIP[0])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert not (pathlib.Path(tmp_path) / "src").exists()
