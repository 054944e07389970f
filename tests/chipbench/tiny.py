"""A cell's traffic and limits at a width a CPU test can hold: its model
family's ``TINY`` widths."""
import dataclasses

from chipbench import cell as C
from chipbench import families

#: a leaf of a tiny model holds few elements, so which of them cross a
#: threshold moves its norm more than at a cell's widths: the norm gaps are
#: held to 0.1 here, the loss gap to the cell's own limit
TINY_NORM_LIMIT = 0.1


def tiny_cell(name: str) -> C.Cell:
    c = C.load_cell(name)
    t = dict(c.traffic, seq_len=32,
             sequences_per_device=2 * c.traffic["local_steps"])
    limits = {k: (v if k == "loss_gap" else TINY_NORM_LIMIT)
              for k, v in c.limits.items()}
    return dataclasses.replace(
        c, config=dict(c.config, **families.load(c.config).TINY),
        traffic=t, limits=limits)
