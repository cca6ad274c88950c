"""Walking what a read returned: its device arrays, where they live."""


def _leaves(x):
    import jax

    if isinstance(x, jax.Array):
        yield x
    elif isinstance(x, (tuple, list)):
        for y in x:
            yield from _leaves(y)
    elif isinstance(x, dict):
        for y in x.values():
            yield from _leaves(y)


def column_arrays(col):
    """Every device array a decoded ``Column`` holds."""
    for field in ("values", "offsets", "validity", "dictionary",
                  "dict_indices"):
        yield from _leaves(getattr(col, field, None))


def table_arrays(table):
    """Every device array of a ``Table`` from ``ParquetFile.read``."""
    for parts in table._parts.values():
        for col in parts:
            yield from column_arrays(col)


def block(arrays) -> None:
    """Wait for every array."""
    import jax

    jax.block_until_ready(list(arrays))


def host_columns(table) -> list:
    """Columns with a part whose data array is not a device array (a
    value stream, or the index stream of a dictionary column)."""
    import jax

    out = []
    for path, parts in table._parts.items():
        for col in parts:
            data = col.values if col.values is not None else col.dict_indices
            if not isinstance(data, jax.Array):
                out.append(path)
                break
    return out
