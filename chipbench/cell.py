"""A benchmark cell, found by name, and the system under test built for it.

``load_cell`` reads ``BENCHMARK.json`` and the cell's configuration,
traffic and limit files; the configuration names its model family
(``families/``).  ``build_task`` turns them into the program's
``make_task("qwen2_100m", arch=...)`` task, whose ``run(n)`` is the timed
entry.  The weights and the token stream are the benchmark's own, made
from the seed (``weights.py``, ``feed.py``) and handed to the built task.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

from chipbench import families

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict

    @property
    def tokens_per_round(self) -> int:
        t = self.traffic
        return t["fl_devices"] * t["sequences_per_device"] * t["seq_len"]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"choose from {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / "chipbench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads((here / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()))


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: its model
    family's keywords, the file's ``program`` dict on top."""
    from repro.configs.base import ArchConfig
    kw = families.load(config).arch_kwargs(config) | config["program"]
    return ArchConfig(name=config["name"], **kw)


def make_task(cell: Cell, seed: int):
    """The program's task for this cell, not yet built."""
    from repro.models.paper_models import make_task as program_task
    t = cell.traffic
    return program_task(
        "qwen2_100m", m_devices=t["fl_devices"], seed=seed % 2**32,
        scenario=t["scenario"], arch=arch_config(cell.config),
        sparsity=tuple(t["channel_sparsity"]), aggregate=t["uplink"],
        local_steps=t["local_steps"], local_lr=t["local_lr"],
        batch_per_device=t["sequences_per_device"], seq=t["seq_len"],
        backend=t["backend"])


def delivery_masks(cell: Cell, seed: int, rounds: int) -> list:
    """The scenario's (fl_devices, C) delivery masks of the first rounds,
    as the task draws them, without building the model."""
    import numpy as np
    task = make_task(cell, seed)
    base, ids, carry = task._mask_state()
    out = []
    for t in range(rounds):
        carry, recv = task._round_mask(base, ids, carry, t)
        out.append(np.asarray(recv))
    return out
