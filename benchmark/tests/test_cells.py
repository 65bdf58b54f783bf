"""CPU rehearsals of the command and of whole cells."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from cpu_cell import run_cpu

RUN = os.path.join(BENCH, "run.py")


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def test_command_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "paxos-2c3s.full", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip()


def test_paxos_cell_on_cpu():
    line = run_cpu(ROOT, "paxos-2c3s.full", seconds=0.5)
    assert line["correct"], line
    assert line["attempted"] >= 1 and line["failed"] == 0
    # The CPU reports no memory statistics, and half a second holds too few
    # checks for a tail.
    assert set(line["metrics"]) == {"setup_s", "states_per_s"}
    assert list(line)[-1] == "compared"
    assert all(c["value"] == 0 for c in line["compared"].values())


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A checkout whose benchmark gains one configuration, one traffic mix
    and one metric, by new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "stateright_tpu"), root / "stateright_tpu")
    before = _digest(root / "benchmark")

    with open(root / "benchmark/configs/2pc-rm8.json") as f:
        config = json.load(f)
    config.update(rm_count=3, spawn_xla={"frontier_capacity": 1024, "table_capacity": 4096})
    with open(root / "benchmark/configs/2pc-rm3.json", "w") as f:
        json.dump(config, f)
    with open(root / "benchmark/traffic/brief.json", "w") as f:
        json.dump({"warm_checks_max": 1, "trace_seconds": 1}, f)
    with open(root / "benchmark/metrics/levels_per_check.py", "w") as f:
        f.write("def read(run):\n"
                "    return sum(c.levels for c in run.checks) / len(run.checks)\n")
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({"name": "2pc-rm3", "source": "https://example.org",
                             "file": "benchmark/configs/2pc-rm3.json",
                             "reduced": ["rm_count"], "why": "a test"})
    bench["workloads"].append({"name": "2pc-rm3.brief", "config": "2pc-rm3",
                               "traffic": "brief", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "levels_per_check", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "host loop", "moves": "states_per_s",
                               "workloads": ["2pc-rm3.brief"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    after = _digest(root / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_added_cell_runs_with_no_file_edited(extended, trace):
    p = subprocess.run(
        [sys.executable, str(extended / "benchmark/tests/cpu_cell.py"), str(extended),
         "2pc-rm3.brief", "1", str(trace)],
        cwd=extended, env=_env(), capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"], line
    if trace:
        assert line["metrics"]["levels_per_check"]["value"] > 0
    else:
        assert "states_per_s" in line["metrics"]
