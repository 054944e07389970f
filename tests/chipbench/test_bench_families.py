"""Model families as files: a configuration names its family, and a family
module that is only a new file gives the harness everything it takes from
the model (the program's ArchConfig, model FLOPs, row axes and the plain
reference's loss)."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as C
from chipbench import control, counts, families, reference
from chipbench import weights as W
from tiny import tiny_cell

CELLS = [w["name"] for w in C.load_benchmark()["workloads"]]


@pytest.fixture
def copied_family(name, tmp_path, monkeypatch):
    """The family module of cell ``name`` again, as a file under a new
    name outside the package's directory."""
    own = pathlib.Path(families.load(C.load_cell(name).config).__file__)
    copy = f"{own.stem}_copy"
    (tmp_path / f"{copy}.py").write_text(own.read_text())
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    yield copy
    sys.modules.pop(f"{families.__name__}.{copy}", None)


@pytest.mark.parametrize("name", CELLS)
def test_a_family_in_a_new_file_reads_as_dense(name, copied_family):
    cell = tiny_cell(name)
    own = cell.config
    other = dict(own, family=copied_family)
    assert families.load(other).__name__.endswith(copied_family)
    assert C.arch_config(other) == C.arch_config(own)
    assert counts.matmul_params(other) == counts.matmul_params(own)
    assert counts.model_flops_per_token(other, 2048) == \
        counts.model_flops_per_token(own, 2048)
    seed = 2_147_483_663
    shapes = control.shapes_of(cell)
    params = W.make(shapes, seed, own["initializer_range"])
    tokens = np.random.default_rng(seed).integers(
        0, own["vocab_size"], (2, 33), dtype=np.int32)
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    for path, leaf in zip(W.leaf_names(shapes),
                          jax.tree_util.tree_leaves(shapes)):
        assert families.load(other).row_axis(path, leaf.ndim) == \
            families.load(own).row_axis(path, leaf.ndim), path
    losses = [reference.local_step(params, x, y,
                                   config=json.dumps(c, sort_keys=True),
                                   precision="f32", lr=3e-3)[0]
              for c in (own, other)]
    assert float(losses[0]) == float(losses[1])
    assert np.isfinite(float(losses[0]))


@pytest.mark.parametrize("entry", [
    families.load, C.arch_config, counts.matmul_params,
    lambda c: counts.model_flops_per_token(c, 2048)])
def test_an_unknown_family_names_the_missing_file(entry):
    config = dict(C.load_cell(CELLS[0]).config, family="no_such_family")
    with pytest.raises(ModuleNotFoundError,
                       match="chipbench/families/no_such_family.py"):
        entry(config)


@pytest.mark.parametrize("name", CELLS)
def test_every_configuration_names_a_family_with_the_contract(name):
    fam = families.load(C.load_cell(name).config)
    for attr in ("arch_kwargs", "matmul_params", "model_flops_per_token",
                 "row_axis", "loss_fn", "TINY"):
        assert hasattr(fam, attr), attr
    assert "arch_type" in fam.arch_kwargs(C.load_cell(name).config)


@pytest.mark.parametrize("name", CELLS)
def test_row_axes_are_the_programs_model_axes(name):
    """The reference selects per row along the axis the program's
    sparse_gather does: the leaf's model-sharded axis."""
    from repro.launch import sharding_rules as rules
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import _model_axis_of
    cell = tiny_cell(name)
    shapes = control.shapes_of(cell)
    specs = rules.param_specs(C.arch_config(cell.config), shapes,
                              make_host_mesh(1))
    fam = families.load(cell.config)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    for path, leaf, spec in zip(W.leaf_names(shapes),
                                jax.tree_util.tree_leaves(shapes),
                                spec_leaves):
        ax = fam.row_axis(path, leaf.ndim)
        want = _model_axis_of(spec) if leaf.ndim else None
        assert (None if ax is None else ax % leaf.ndim) == want, path
