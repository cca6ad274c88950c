"""A shard of one day of the Criteo 1TB Click Logs in the MLPerf DLRM
schema (label, I1-I13, C1-C26, int32), from a configuration file such as
``configs/criteo_1tb_day_shard.json``.  Deviations are the file's
``assumed``."""

import numpy as np
import pyarrow as pa

from lib.rows import row_groups_in_seed_order


def build(cfg: dict, seed: int) -> list:
    """The row groups of the file, in the seed's order."""
    n = cfg["rows"]
    rng = np.random.default_rng(cfg["content_seed"])
    nulls = cfg["null_share"]
    cols = {"label": pa.array(
        (rng.random(n) < cfg["label_positive_share"]).astype(np.int32))}

    def with_nulls(name, values):
        share = nulls.get(name, 0.0)
        mask = rng.random(n) < share if share else None
        return pa.array(values, mask=mask)

    index = cfg["dense_pareto_index"]
    for i in range(1, cfg["dense_features"] + 1):
        u = rng.random(n)
        counts = np.floor((1.0 - u) ** (-1.0 / index)) - 1
        counts = np.minimum(counts, cfg["dense_max"] - 1).astype(np.int32)
        cols[f"I{i}"] = with_nulls(f"I{i}", counts)
    for name, card in cfg["cardinalities"].items():
        cols[name] = with_nulls(name, rng.integers(0, card, n, np.int32))
    return row_groups_in_seed_order(pa.table(cols), cfg["row_group_rows"],
                                    seed)
