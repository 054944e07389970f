"""The harness as data: every name in BENCHMARK.json finds its files, and a
run refuses to report without a TPU."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import cell as C

ROOT = C.ROOT
BENCH = C.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_units_follow_the_contract():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in metrics)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = C.load_cell(cell)
    assert c.config["name"] == c.config_name
    arch = C.arch_config(c.config)
    assert arch.n_layers == c.config["num_hidden_layers"]
    t = c.traffic
    assert t["sequences_per_device"] % t["local_steps"] == 0
    assert set(c.limits) <= {"loss_gap", "update_gap", "progress_gap",
                             "change_gap"}


def test_configs_list_their_cuts():
    for cfg in BENCH["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert data["source"] == cfg["source"]
        assert set(cfg["reduced"]) == set(data["published"])
        for key in cfg["reduced"]:
            assert data[key] < data["published"][key]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reducer(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric}")
    assert callable(mod.read)


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
