"""The harness finds a cell, its configuration, its traffic mix and its
metrics by file name alone, and refuses to measure without a TPU or
without the system under test beside it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import backlog, spec

RUN = os.path.join(spec.BENCH_DIR, "run.py")


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_cell_config_and_metric_found_by_name(tmp_path):
    root = tmp_path
    bench = root / "chipbench"
    cfg = {"name": "m", "family": "xlstm", "num_layers": 2}
    _write(str(bench / "configs" / "m.json"), json.dumps(cfg))
    traffic = {"groups": [{"lr": [1e-3], "batch": 2, "seq": 8, "steps": 4}]}
    _write(str(bench / "workloads" / "mix-a.json"), json.dumps(traffic))
    _write(str(bench / "metrics" / "added_metric.py"),
           "def read(run):\n    return 42.0\n")
    _write(str(bench / "metrics" / "only_elsewhere.py"),
           "def read(run):\n    return 1.0\n")
    _write(str(root / "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "m", "file": "chipbench/configs/m.json"}],
        "workloads": [{"name": "cell-a", "config": "m", "traffic": "mix-a",
                       "chips": 1}],
        "end_to_end": [{"name": "added_metric", "unit": "s"}],
        "per_layer": [{"name": "only_elsewhere", "unit": "%",
                       "workloads": ["cell-b"]}]}))
    cell = spec.load_cell("cell-a", root=str(root))
    assert cell.config == cfg and cell.traffic == traffic
    assert [m.name for m in cell.end_to_end] == ["added_metric"]
    assert cell.end_to_end[0].read(None) == 42.0
    assert cell.per_layer == []            # listed for another cell only
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", root=str(root))


def test_every_committed_cell_loads():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload",
                        "xlstm125m-lrgrid-short", "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "xlstm125m-lrgrid-short", "--seed", "3",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_backlog_same_seed_same_jobs_other_seed_same_sizes():
    with open(os.path.join(spec.BENCH_DIR, "workloads",
                           "xlstm125m-lrgrid-short.json")) as f:
        traffic = json.load(f)
    big = 2 ** 31 + 123456789
    a, b = backlog.expand(traffic, big), backlog.expand(traffic, big)
    c = backlog.expand(traffic, big + 1)
    assert a == b and len(a) == 4
    strip = lambda js: [(j.name, j.lr, j.batch, j.seq, j.steps)  # noqa: E731
                        for j in js]
    assert strip(a) == strip(c)
    assert [j.seed for j in a] != [j.seed for j in c]
    assert all(0 <= j.seed < 2 ** 31 for j in a + c)
    assert len({j.seed for j in a}) == len(a)
