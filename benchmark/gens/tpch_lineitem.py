"""TPC-H ``lineitem`` (spec 3.0.1 section 1.4, value rules of 4.2.3) at any
scale factor, from a configuration file such as
``configs/tpch_lineitem_sf1.json``.  Deviations are the file's ``assumed``."""

import datetime

import numpy as np
import pyarrow as pa

from lib.rows import row_groups_in_seed_order

EPOCH = datetime.date(1970, 1, 1)


def _day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


START, END = _day("1992-01-01"), _day("1998-08-02")  # o_orderdate range
CURRENT = _day("1995-06-17")  # CURRENTDATE of 4.2.3
FLAGS = ["A", "N", "R"]
STATUS = ["F", "O"]
INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)


def _dict(idx, values):
    return pa.DictionaryArray.from_arrays(pa.array(idx.astype(np.int32)),
                                          pa.array(values))


def _text(rng, n, lo, hi):
    lens = rng.integers(lo, hi + 1, n)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    data = LETTERS[rng.integers(0, len(LETTERS), int(offsets[-1]))]
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(data.tobytes()))


def build(cfg: dict, seed: int) -> list:
    """The row groups of the file, in the seed's order."""
    n, sf = cfg["rows"], cfg["scale_factor"]
    rng = np.random.default_rng(cfg["content_seed"])
    # orders of 1-7 lines until n lines; keys sparse (8 used of every 32)
    counts = rng.integers(1, 8, n // 4 + n // 16 + 16)
    ends = np.cumsum(counts)
    k = int(np.searchsorted(ends, n)) + 1
    counts, ends = counts[:k].copy(), ends[:k]
    counts[-1] -= int(ends[-1]) - n
    order = np.repeat(np.arange(k), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    orderdate = np.repeat(rng.integers(START, END + 1, k), counts)

    partkey = rng.integers(1, 200_000 * sf + 1, n)
    quantity = rng.integers(1, 51, n)
    retail = (90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)) / 100
    ship = (orderdate + rng.integers(1, 122, n)).astype(np.int32)
    receipt = (ship + rng.integers(1, 31, n)).astype(np.int32)
    returned = rng.integers(0, 2, n) * 2  # A (0) or R (2), even odds
    table = pa.table({
        "l_orderkey": ((order // 8) * 32 + order % 8 + 1).astype(np.int64),
        "l_partkey": partkey.astype(np.int64),
        "l_suppkey": rng.integers(1, 10_000 * sf + 1, n).astype(np.int64),
        "l_linenumber": (np.arange(n) - first + 1).astype(np.int32),
        "l_quantity": quantity.astype(np.int64),
        "l_extendedprice": np.round(quantity * retail, 2),
        "l_discount": rng.integers(0, 11, n) / 100,
        "l_tax": rng.integers(0, 9, n) / 100,
        "l_returnflag": _dict(np.where(receipt <= CURRENT, returned, 1),
                              FLAGS),
        "l_linestatus": _dict((ship > CURRENT).astype(np.int32), STATUS),
        "l_shipdate": ship,
        "l_commitdate": (orderdate + rng.integers(30, 91, n)).astype(np.int32),
        "l_receiptdate": receipt,
        "l_shipinstruct": _dict(rng.integers(0, len(INSTRUCT), n), INSTRUCT),
        "l_shipmode": _dict(rng.integers(0, len(MODES), n), MODES),
        "l_comment": _text(rng, n, 10, 43),
    })
    return row_groups_in_seed_order(table, cfg["row_group_rows"], seed)
