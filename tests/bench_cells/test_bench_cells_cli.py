"""``python -m bench_cells.run`` off the chip: a non-zero exit code and no
result line, whatever the cell."""

import json
import os
import subprocess
import sys

import pytest

from bench_cells import manifest


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest.load_manifest()["workloads"]])
def test_no_tpu_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    proc = subprocess.run(
        [sys.executable, "-m", "bench_cells.run", "--workload", cell,
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_unknown_workload_is_refused():
    proc = subprocess.run(
        [sys.executable, "-m", "bench_cells.run", "--workload", "no.such",
         "--seed", "1", "--seconds", "1"], cwd=manifest.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no workload" in proc.stderr
