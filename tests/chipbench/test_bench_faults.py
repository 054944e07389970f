"""A whole run without the chip check, at a tiny width: sound, it comes out
correct under the cell's own limits; with the timed step broken
underneath, it does not."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as C
from chipbench import run as R
from tiny import tiny_cell

CELLS = [w["name"] for w in C.load_benchmark()["workloads"]]
SEED = 2_147_483_659


def unchanged(step):
    """The step returns the state it was given (its loss is right)."""
    def f(p, e, batch, recv):
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        _, _, loss = step(copy(p), copy(e), batch, recv)
        return p, e, loss
    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(p, e, batch, recv):
        return step(p, e, {k: v[: v.shape[0] // 2] for k, v in batch.items()},
                    recv)
    return f


def loss_altered(step):
    """The reported loss altered where it is produced."""
    def f(*args):
        p, e, loss = step(*args)
        return p, e, loss * 1.01
    return f


def weight_altered(step):
    """One leaf of the new weights altered where it is produced."""
    def f(*args):
        p, e, loss = step(*args)
        fn = p["final_norm"]
        return dict(p, final_norm=dict(fn, scale=fn["scale"] * 1.01)), e, loss
    return f


def _run(cell, fault=None):
    return R.run_cell(cell, C.load_benchmark(), SEED, 0.2, False,
                      step_wrapper=fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(tiny_cell(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged, half_batch, loss_altered,
                                   weight_altered])
def test_broken_step_is_not_correct(fault):
    res = _run(tiny_cell(CELLS[0]), fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [unchanged, half_batch, loss_altered,
                                   weight_altered])
@pytest.mark.parametrize("name", CELLS[1:])
def test_broken_step_is_not_correct_in_the_other_cells(name, fault):
    res = _run(tiny_cell(name), fault)
    assert not res["correct"], res["checks"]
