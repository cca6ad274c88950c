"""Comparison against pyarrow on the same bytes, counted value by value.

``plain`` is copied from ``chip_smoke.py`` (PR 21); the count of values
that differ replaces its all-or-nothing ``same`` so that a run can print the
number beside its limit."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def plain(table: pa.Table) -> pa.Table:
    """One chunk per column, dictionaries decoded: the form both sides of a
    comparison are brought to."""
    cols = []
    for c in table.columns:
        c = c.combine_chunks()
        if pa.types.is_dictionary(c.type):
            c = c.dictionary_decode()
        if pa.types.is_large_string(c.type):
            c = c.cast(pa.string())
        cols.append(c)
    return pa.table(cols, names=table.column_names)


def column_mismatches(got: pa.Array, want: pa.Array) -> int:
    """Values of ``want`` that ``got`` does not hold at the same row: a
    differing value, a null on one side only, or a row one side lacks.
    Floats compare by their bits, so -0.0 and NaN payloads count too."""
    n = min(len(got), len(want))
    extra = abs(len(got) - len(want))
    got, want = got.slice(0, n), want.slice(0, n)
    if got.type != want.type:
        return n + extra
    if got.equals(want):
        return extra
    if pa.types.is_floating(want.type):
        width = {4: pa.uint32(), 8: pa.uint64()}[want.type.bit_width // 8]
        got, want = got.view(width), want.view(width)
    both_null = pc.and_(got.is_null(), want.is_null())
    same = pc.or_(pc.fill_null(pc.equal(got, want), False), both_null)
    return int(n - pc.sum(same.cast(pa.int64())).as_py()) + extra


def mismatches(got: pa.Table, want: pa.Table) -> int:
    """Values that differ over every column of ``want`` (a column ``got``
    lacks counts every row)."""
    got, want = plain(got), plain(want)
    total = 0
    for name in want.column_names:
        if name not in got.column_names:
            total += want.num_rows
            continue
        total += column_mismatches(got.column(name).combine_chunks(),
                                   want.column(name).combine_chunks())
    return total


def lower_precision(table: pa.Table, how: str) -> pa.Table:
    """The control: the reference with one guarantee broken.  ``float32``
    rounds every float64 column through float32; ``nulls_to_zero`` reads
    every null as 0, as a loader that skips validity would."""
    cols = []
    for c in plain(table).columns:
        c = c.combine_chunks()
        if how == "float32" and pa.types.is_float64(c.type):
            c = pa.array(np.asarray(c.to_numpy(zero_copy_only=False),
                                    np.float32).astype(np.float64),
                         mask=c.is_null().to_numpy(zero_copy_only=False))
        elif how == "nulls_to_zero" and c.null_count:
            c = pc.fill_null(c, pa.scalar(0, c.type))
        cols.append(c)
    return pa.table(cols, names=table.column_names)
