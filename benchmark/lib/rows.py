"""Shared by the generators: how the seed orders the row groups."""

import numpy as np


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole ``--seed``, negative or past 64 bits."""
    return np.random.default_rng(seed % 2**64)


def row_groups_in_seed_order(table, rg_rows: int, seed: int) -> list:
    """The table cut into row groups of ``rg_rows``, in an order drawn from
    ``seed``.

    The contents come from the configuration's ``content_seed``; the run's
    seed only orders the row groups in the file.  Each row group keeps its
    rows, so it encodes to the same pages whatever the seed, and every
    seed gives the device the same shapes (the same compiled programs) and
    the same work in another order.  Reordering rows inside a row group
    changed the run structure of the RLE streams, and with it the shapes:
    565 of 1,098 programs compiled anew for a second seed (my chip run,
    PR 22)."""
    groups = [table.slice(s, rg_rows) for s in range(0, table.num_rows,
                                                     rg_rows)]
    return [groups[i] for i in rng_for(seed).permutation(len(groups))]
