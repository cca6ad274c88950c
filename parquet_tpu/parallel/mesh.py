"""Multi-chip sharded reads over a jax.sharding.Mesh.

Reference parity: the reference's only parallelism is caller-driven goroutine
fan-out over row groups / column chunks (SURVEY.md §2.5).  The TPU-native
equivalent: a ``Mesh`` over chips, row groups round-robined across the
``data`` axis, per-chip staging + decode, and the decoded chunks exposed as
global sharded ``jax.Array``s (``make_array_from_single_device_arrays``), so
downstream pjit computations consume them without resharding.  Collectives
ride ICI only if a consumer asks for replication — decode itself is
embarrassingly parallel, exactly like the reference's design.

Also home of ``decode_step_sharded``: a ``shard_map``-based batched decode
step over a mesh (the "training step" analog, exercised by the driver's
``dryrun_multichip``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..format.enums import Encoding
from ..io.column import Column
from ..io.reader import ParquetFile
from ..obs.ledger import ledger_account
from ..obs.metrics import counter as _ocounter
from ..obs.scope import account as _oaccount
from ..ops import device as dev
from ..utils import pool as _pool
from ..utils.debug import counters
from ..utils.env import env_str

# resolved once at import (hot-path rule: no registry get-or-create per
# file); the ledger account is owned HERE (analysis/lint.py PT003)
_M_FILES_SHARDED = _ocounter("device.files_sharded")
_M_STAGE_OVERLAPPED = _ocounter("device.stage_overlapped")
_ACC_STAGING = ledger_account("device.staging")


def default_mesh(n: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


def dataset_process_shard(dataset, process_index: Optional[int] = None,
                          process_count: Optional[int] = None):
    """This host's file shard of a multi-host dataset: files are
    round-robined across JAX processes (``Dataset.shard(i, n)``), so every
    process of a multi-controller mesh reads a disjoint, deterministic
    subset and the union covers the corpus exactly once.  Defaults come
    from the runtime (``jax.process_index()`` / ``jax.process_count()``);
    pass both explicitly to shard by something other than processes (e.g.
    one shard per chip for a caller-driven device fan-out)."""
    i = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if process_count is None else process_count
    return dataset.shard(i, n)


@dataclass(frozen=True)
class ShardedTable:
    """Row-sharded decode result over a mesh.

    ``arrays[path]`` is a global jax.Array sharded on rows (leading axis)
    over the mesh's first axis; every shard is padded to ``shard_rows`` so
    the global array exists, and ``row_counts[i]`` gives shard i's REAL row
    count (``row_mask()`` materializes the padding mask with the same
    sharding). ``validity[path]`` (present only for columns with nulls) is a
    row-aligned bool array sharded identically; padded and null slots hold
    zero fill in ``arrays[path]``. 64-bit columns use the (n, 2) uint32 pair
    representation (``ops.device.pairs_to_host``).

    Dictionary-encoded BYTE_ARRAY columns shard their int32 INDEX stream in
    ``arrays[path]``; the row-group dictionaries are UNIFIED (deduplicated
    across groups — equal ids mean equal strings, so filters, group-bys and
    joins on the index stream are exact on device) into one host
    ``dictionaries[path] = (uint8 values, int64 offsets)`` shared by every
    shard — ``lookup_strings(path, ids)`` materializes entries.

    PLAIN (non-dictionary) BYTE_ARRAY columns shard as the arrow ragged
    pair in ``ragged[path]`` (see field comment); a column whose chunks mix
    dictionary and plain encodings (pyarrow's mid-file dictionary-overflow
    fallback) densifies the dictionary chunks so the whole column ships
    ragged.
    """

    arrays: Dict[str, jax.Array]
    validity: Dict[str, jax.Array]
    row_counts: tuple
    mesh: Mesh
    dictionaries: Dict[str, tuple] = field(default_factory=dict)
    # PLAIN (non-dictionary) BYTE_ARRAY columns: ragged[path] =
    # (bytes_global, offsets_global) — per-shard value bytes padded to the
    # byte-widest shard, and per-shard slot-aligned int64 offsets (null
    # slots zero-length) padded to shard_rows+1 entries, both sharded on
    # the mesh's first axis like arrays[path]
    ragged: Dict[str, tuple] = field(default_factory=dict)
    # schema leaves by path: to_arrow recombines 64-bit pairs and restores
    # logical types (dates, timestamps, decimals, FLBA) through these
    leaves: Dict[str, object] = field(default_factory=dict)

    def lookup_strings(self, path: str, ids) -> list:
        """Materialize dictionary entries for index values of ``path``."""
        dvals, doffs = self.dictionaries[path]
        return [bytes(dvals[doffs[i]:doffs[i + 1]]) for i in np.asarray(ids)]

    def to_arrow(self):
        """Gather every shard back to host as one pyarrow.Table (padding
        rows dropped, 64-bit pairs recombined, dictionary-index columns as
        DictionaryArray over the unified dictionary).  Conversion routes
        through the leaf-aware ``_leaf_to_arrow`` so logical types (dates,
        timestamps, decimals, FLBA, binary-vs-string) survive exactly as
        in ``ParquetFile.read().to_arrow()``."""
        import pyarrow as pa

        from ..io.column import _leaf_to_arrow

        mask = np.asarray(self.row_mask())
        cols, names = [], []
        for path, arr in self.arrays.items():
            leaf = self.leaves.get(path)
            host = np.asarray(arr)
            valid = (np.asarray(self.validity[path])[mask]
                     if path in self.validity else None)
            if path in self.dictionaries:
                dvals, doffs = self.dictionaries[path]
                entries = _leaf_to_arrow(leaf, np.asarray(dvals),
                                         np.asarray(doffs, np.int64), None)
                ids = host[mask].astype(np.int32)
                ia = (pa.array(ids, mask=~valid) if valid is not None
                      else pa.array(ids))
                a = pa.DictionaryArray.from_arrays(ia, entries)
            else:
                if host.ndim == 2 and host.dtype == np.uint32 \
                        and host.shape[-1] == 2:
                    host = dev.pairs_to_host(
                        host, np.dtype(leaf.np_dtype()) if leaf is not None
                        else np.int64)
                rowvals = host[mask]
                if leaf is None:  # externally built table: generic numpy
                    a = (pa.array(rowvals, mask=~valid)
                         if valid is not None else pa.array(rowvals))
                elif valid is not None:
                    # _leaf_to_arrow takes DENSE values + slot validity
                    a = _leaf_to_arrow(leaf, rowvals[valid], None, valid)
                else:
                    a = _leaf_to_arrow(leaf, rowvals, None, None)
            cols.append(a)
            names.append(path)
        R = self.shard_rows
        nd = len(self.row_counts)

        def _offs32(o):
            if len(o) and int(o[-1]) > np.iinfo(np.int32).max:
                raise NotImplementedError(
                    "ragged shard holds more than 2 GiB of value bytes; "
                    "int32 arrow offsets cannot address it — use smaller "
                    "row groups or more shards")
            return o.astype(np.int32)

        for path, (b_g, o_g) in self.ragged.items():
            leaf = self.leaves.get(path)
            bh = np.asarray(b_g)
            oh = np.asarray(o_g)
            mb = len(bh) // nd if nd else 0
            valid_all = (np.asarray(self.validity[path])
                         if path in self.validity else None)
            chunks = []
            for d in range(nd):
                rc = self.row_counts[d]
                o = oh[d * (R + 1): d * (R + 1) + rc + 1].astype(np.int64)
                seg = bh[d * mb: d * mb + (int(o[-1]) if rc else 0)]
                if valid_all is not None:
                    v = np.asarray(valid_all[d * R: d * R + rc], bool)
                    # null slots are zero-length, so the dense offsets are
                    # the slot offsets with null entries dropped
                    dense_offs = np.concatenate([o[:-1][v], o[-1:]])
                    chunks.append(_leaf_to_arrow(leaf, seg,
                                                 _offs32(dense_offs), v))
                else:
                    chunks.append(_leaf_to_arrow(leaf, seg, _offs32(o),
                                                 None))
            cols.append(pa.chunked_array(chunks))
            names.append(path)
        # file schema order (self.leaves is insertion-ordered by schema)
        if self.leaves:
            want = [p for p in self.leaves if p in names]
            want += [p for p in names if p not in self.leaves]
            lookup = dict(zip(names, cols))
            names, cols = want, [lookup[p] for p in want]
        return pa.table(dict(zip(names, cols)))

    @property
    def shard_rows(self) -> int:
        return max(self.row_counts) if self.row_counts else 0

    @property
    def num_rows(self) -> int:
        return int(sum(self.row_counts))

    def row_mask(self) -> jax.Array:
        """Global bool array marking real (non-padding) rows."""
        host = np.concatenate(
            [np.arange(self.shard_rows) < c for c in self.row_counts]) \
            if self.row_counts else np.zeros(0, bool)
        sharding = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return jax.device_put(host, sharding)


#: prep verdict of :func:`read_table_sharded` for a fully PLAIN string
#: chunk: it ships as the host-assembled ragged pair by design, so it is
#: counted as ``chunks_host_ragged``, not as a fallback
_RAGGED = object()


def _decode_prepped(reader, prep_out):
    """Device-decode a prepared chunk, or fall back to host decode when the
    prescan/decode hit an unsupported shape (mixed page encodings, missing
    dictionary page, ...) — parity with decode_chunk_device(fallback=True).
    Returns (Column, null count)."""
    from ..format.enums import Type
    from ..io.reader import decode_chunk_host
    from .device_reader import _Unsupported, decode_staged

    if prep_out is _RAGGED:
        counters.inc("chunks_host_ragged")
    elif prep_out is not None:
        plan, staged = prep_out
        try:
            col = decode_staged(reader.leaf, Type(reader.meta.type), plan,
                                staged)
            counters.inc("chunks_device_decoded")
            return col, plan.total_slots - plan.total_values
        except _Unsupported:
            counters.inc("chunks_host_fallback")
    else:
        counters.inc("chunks_host_fallback")
    col = decode_chunk_host(reader)
    n_nulls = 0
    if col.validity is not None:
        v = np.asarray(col.validity)
        n_nulls = int(len(v) - v.sum())
    return col, n_nulls


def _unify_dictionaries(dv_parts: List[np.ndarray],
                        do_parts: List[np.ndarray]):
    """Deduplicate per-row-group dictionaries into one unified dictionary.

    Returns ``(values, offsets, remap)`` where ``remap[concat_id] ->
    unified id`` over the concatenation of the input dictionaries in order.
    Unified ids are first-occurrence ordered, so equal ids ⇔ equal strings
    across every row group — the property device-side filters/joins on the
    sharded index stream rely on."""
    from .. import native as _native
    from ..io.column import concat_byte_arrays
    from ..ops import ref

    cat_vals, cat_offs = concat_byte_arrays(dv_parts, do_parts)
    n = len(cat_offs) - 1
    res = _native.dict_build_ba(cat_vals, cat_offs, n + 1,
                                sample_bail=False)
    if res is None or isinstance(res, str):
        # shim unavailable: python dedup, same semantics
        seen: Dict[bytes, int] = {}
        remap = np.empty(n, np.int64)
        keep = []
        for i in range(n):
            key = bytes(cat_vals[cat_offs[i]:cat_offs[i + 1]])
            uid = seen.setdefault(key, len(seen))
            if uid == len(keep):
                keep.append(i)
            remap[i] = uid
        first_rows = np.array(keep, np.int64)
    else:
        remap, first_rows = res
        remap = np.asarray(remap, np.int64)
    uvals, uoffs = ref.gather_dictionary((cat_vals, cat_offs),
                                         np.asarray(first_rows, np.int64))
    return uvals, np.asarray(uoffs, np.int64), remap


def _slot_ragged(vals: np.ndarray, offs: np.ndarray, validity,
                 n_nulls: int):
    """Dense (values, offsets) → slot-aligned offsets where null slots are
    zero-length entries (the arrow convention the sharded ragged form
    uses); values are untouched."""
    if validity is None or not n_nulls:
        return vals, offs
    valid = np.asarray(validity, bool)
    lens = np.zeros(len(valid), np.int64)
    lens[valid] = offs[1:] - offs[:-1]
    so = np.zeros(len(valid) + 1, np.int64)
    np.cumsum(lens, out=so[1:])
    return vals, so


def read_table_sharded(source, mesh: Optional[Mesh] = None,
                       columns: Optional[Sequence[str]] = None,
                       axis: str = "data",
                       num_threads: Optional[int] = None) -> ShardedTable:
    """Read fixed-width columns of a file as a :class:`ShardedTable`.

    Row groups are assigned round-robin to the mesh's devices. The host
    phase (pread + decompress + prescan + H2D put targeted at each chunk's
    device) fans out across a thread pool so all devices stage concurrently
    (SURVEY.md §2.5 data-parallel row); decode dispatches are async, so
    device work overlaps too. Columns must be flat: fixed-width values
    shard directly (BOOLEAN/INT32/INT64/FLOAT/DOUBLE/FLBA — 64-bit as
    (n, 2) uint32 pairs), and dictionary-encoded BYTE_ARRAY columns shard
    their int32 index stream with the per-row-group dictionaries UNIFIED
    (first-occurrence dedup — id equality is string equality on every
    shard) into ``ShardedTable.dictionaries[path]``.
    PLAIN-encoded (non-dictionary) string columns shard as the ragged
    (bytes, slot-offsets) pair in ``ShardedTable.ragged``; nested columns
    raise ValueError (read them with ``ParquetFile.read(device=True)``,
    which keeps ragged forms).
    """
    from concurrent.futures import ThreadPoolExecutor

    from ..format.enums import Type
    from .device_reader import _Unsupported, prepare_chunk

    mesh = mesh or default_mesh(axis=axis)
    devs = list(mesh.devices.reshape(-1))
    pf = source if isinstance(source, ParquetFile) else ParquetFile(source)
    leaves = (pf.schema.leaves if columns is None
              else [pf.schema.leaf(c) for c in columns])
    n_rg = len(pf.metadata.row_groups or [])
    for leaf in leaves:
        if leaf.max_repetition_level > 0:
            raise ValueError(
                f"read_table_sharded: column {leaf.dotted_path!r} is "
                "nested; use ParquetFile.read(device=True)")
    if n_rg == 0:
        return ShardedTable(arrays={}, validity={},
                            row_counts=(0,) * len(devs), mesh=mesh,
                            dictionaries={})
    tasks = [(leaf, rg) for leaf in leaves for rg in range(n_rg)]

    def prep(task):
        leaf, rg = task
        reader = pf.row_group(rg).column(leaf.column_index)
        if leaf.physical_type == Type.BYTE_ARRAY:
            encs = reader.meta.encodings or []
            if not any(int(e) in (int(Encoding.PLAIN_DICTIONARY),
                                  int(Encoding.RLE_DICTIONARY))
                       for e in encs):
                # fully PLAIN chunk: it ships as the host-assembled ragged
                # pair anyway — device-staging it first would be a wasted
                # H2D+D2H round trip
                return _RAGGED, reader
        try:
            return prepare_chunk(reader, device=devs[rg % len(devs)]), reader
        except _Unsupported:
            return None, reader  # host fallback at decode time

    workers = num_threads or min(len(devs) * 2, 16)
    with ThreadPoolExecutor(max_workers=max(workers, 1)) as pool:
        prepped = list(pool.map(prep, tasks))

    arrays: Dict[str, jax.Array] = {}
    validities: Dict[str, jax.Array] = {}
    dictionaries: Dict[str, tuple] = {}
    ragged: Dict[str, tuple] = {}
    rg_rows = [pf.row_group(i).num_rows for i in range(n_rg)]
    shard_counts = [sum(rg_rows[rg] for rg in range(n_rg)
                        if rg % len(devs) == d) for d in range(len(devs))]
    maxlen = max(shard_counts) if shard_counts else 0
    for leaf in leaves:
        is_ba = leaf.physical_type == Type.BYTE_ARRAY
        per_dev_vals: Dict[int, List[jax.Array]] = {}
        per_dev_valid: Dict[int, List[jax.Array]] = {}
        has_nulls = False
        ba_parts = []  # (rg, device, indices, validity, n_nulls) per row group
        ragged_parts = []  # (rg, device, bytes, slot_offsets, validity, n_nulls)
        dict_vals_parts: List[np.ndarray] = []
        dict_offs_parts: List[np.ndarray] = []
        for (prep_out, reader), (l2, rg) in zip(prepped, tasks):
            if l2 is not leaf:
                continue
            d = rg % len(devs)
            with jax.default_device(devs[d]):
                col, n_nulls = _decode_prepped(reader, prep_out)
                if is_ba:
                    if not col.is_dictionary_encoded():
                        # PLAIN chunk: ship the arrow ragged pair; slot
                        # alignment (nulls zero-length) happens on host at
                        # staging scale
                        ragged_parts.append(
                            (rg, d) + _slot_ragged(
                                np.asarray(col.values),
                                np.asarray(col.offsets, np.int64),
                                col.validity, n_nulls)
                            + (col.validity, n_nulls))
                        continue
                    dvals, doffs = col._host_dictionary()
                    dict_vals_parts.append(np.asarray(dvals))
                    dict_offs_parts.append(np.asarray(doffs, np.int64))
                    # index placement deferred until the dictionaries are
                    # unified below (ids must mean the same string on
                    # every shard for device-side filters/joins)
                    ba_parts.append((rg, d, col.dict_indices, col.validity,
                                     n_nulls))
                    continue
                vals = col.values
                if col.is_dictionary_encoded():
                    vals = dev.dict_gather(col.dictionary,
                                           col.dict_indices)
                if not isinstance(vals, jax.Array):
                    vals = jnp.asarray(vals)
                valid = col.validity
                if valid is not None and n_nulls:
                    if not isinstance(valid, jax.Array):
                        valid = jnp.asarray(valid)
                    vals = dev.scatter_valid(vals, valid)  # row-align
                    has_nulls = True
                elif valid is not None:
                    valid = None  # nullable schema, no actual nulls
            per_dev_vals.setdefault(d, []).append(vals)
            per_dev_valid.setdefault(d, []).append(valid)
        if is_ba and ragged_parts:
            if ba_parts:
                # mixed dictionary/plain chunks (pyarrow's mid-file
                # dictionary-overflow fallback): densify the dictionary
                # chunks host-side so the whole column ships ragged
                from ..ops import ref as _ref

                for (rg, d, idx, valid, n_nulls), dvals, doffs in zip(
                        ba_parts, dict_vals_parts, dict_offs_parts):
                    g = _ref.gather_dictionary(
                        (np.asarray(dvals), np.asarray(doffs, np.int64)),
                        np.asarray(idx, np.int64))
                    ragged_parts.append(
                        (rg, d) + _slot_ragged(np.asarray(g[0]),
                                               np.asarray(g[1], np.int64),
                                               valid, n_nulls)
                        + (valid, n_nulls))
                ba_parts = []
            per_dev_r: Dict[int, List[tuple]] = {}
            col_has_nulls = any(nn and v is not None
                                for *_, v, nn in ragged_parts)
            for rg, d, vb, so, valid, nn in sorted(ragged_parts,
                                                   key=lambda p: p[0]):
                per_dev_r.setdefault(d, []).append((vb, so, valid, nn))
            shard_bytes, shard_offs, shard_valids = [], [], []
            for d in range(len(devs)):
                parts = per_dev_r.get(d, [])
                b = (np.concatenate([p[0] for p in parts]) if parts
                     else np.zeros(0, np.uint8))
                off_parts = [np.zeros(1, np.int64)]
                base = 0
                for vb, so, _, _ in parts:
                    off_parts.append(so[1:] + base)
                    base += int(so[-1])
                o = np.concatenate(off_parts)
                if len(o) < maxlen + 1:  # padding rows are zero-length
                    o = np.concatenate(
                        [o, np.full(maxlen + 1 - len(o), o[-1], np.int64)])
                shard_bytes.append(b)
                shard_offs.append(o)
                if col_has_nulls:
                    vps = [np.asarray(v, bool) if v is not None and nn
                           else np.ones(len(so) - 1, bool)
                           for vb, so, v, nn in parts]
                    va = (np.concatenate(vps) if vps
                          else np.zeros(0, bool))
                    shard_valids.append(np.pad(va, (0, maxlen - len(va))))
            max_bytes = max((len(b) for b in shard_bytes), default=0) or 1
            gb, go, gv = [], [], []
            for d in range(len(devs)):
                with jax.default_device(devs[d]):
                    b = shard_bytes[d]
                    if len(b) < max_bytes:
                        b = np.pad(b, (0, max_bytes - len(b)))
                    gb.append(jax.device_put(jnp.asarray(b), devs[d]))
                    go.append(jax.device_put(jnp.asarray(shard_offs[d]),
                                             devs[d]))
                    if col_has_nulls:
                        gv.append(jax.device_put(
                            jnp.asarray(shard_valids[d]), devs[d]))
            sh1 = NamedSharding(mesh, P(mesh.axis_names[0]))
            ragged[leaf.dotted_path] = (
                jax.make_array_from_single_device_arrays(
                    (max_bytes * len(devs),), sh1, gb),
                jax.make_array_from_single_device_arrays(
                    ((maxlen + 1) * len(devs),), sh1, go))
            if col_has_nulls:
                validities[leaf.dotted_path] = \
                    jax.make_array_from_single_device_arrays(
                        (maxlen * len(devs),), sh1, gv)
            continue
        if is_ba and dict_vals_parts:
            uvals, uoffs, remap = _unify_dictionaries(dict_vals_parts,
                                                      dict_offs_parts)
            dictionaries[leaf.dotted_path] = (uvals, uoffs)
            base = 0
            for (rg, d, idx, valid, n_nulls), doffs in zip(ba_parts,
                                                           dict_offs_parts):
                n_i = len(doffs) - 1
                sub = remap[base:base + n_i].astype(np.int32)
                base += n_i
                with jax.default_device(devs[d]):
                    if isinstance(idx, jax.Array):  # device route: gather
                        vals = jnp.asarray(sub)[idx]
                    else:
                        vals = jnp.asarray(sub[np.asarray(idx, np.int64)])
                    if valid is not None and n_nulls:
                        if not isinstance(valid, jax.Array):
                            valid = jnp.asarray(valid)
                        vals = dev.scatter_valid(vals, valid)
                        has_nulls = True
                    else:
                        valid = None
                per_dev_vals.setdefault(d, []).append(vals)
                per_dev_valid.setdefault(d, []).append(valid)
        template = next(p[0] for p in per_dev_vals.values() if p)
        shard_arrays, shard_valid = [], []
        for d in range(len(devs)):
            parts = per_dev_vals.get(d, [])
            with jax.default_device(devs[d]):
                if parts:
                    arr = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                else:  # more devices than row groups: typed empty shard
                    arr = jnp.zeros((0,) + tuple(template.shape[1:]),
                                    template.dtype)
                if arr.shape[0] < maxlen:
                    padw = [(0, maxlen - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
                    arr = jnp.pad(arr, padw)
                shard_arrays.append(jax.device_put(arr, devs[d]))
                if has_nulls:
                    vparts = [v if v is not None else jnp.ones(p.shape[0], bool)
                              for v, p in zip(per_dev_valid.get(d, []), parts)]
                    va = (jnp.concatenate(vparts) if len(vparts) > 1
                          else vparts[0] if vparts else jnp.zeros(0, bool))
                    if va.shape[0] < maxlen:
                        va = jnp.pad(va, (0, maxlen - va.shape[0]))
                    shard_valid.append(jax.device_put(va, devs[d]))
        nd = shard_arrays[0].ndim
        sharding = NamedSharding(mesh, P(mesh.axis_names[0],
                                         *(None,) * (nd - 1)))
        global_shape = (maxlen * len(shard_arrays),) + tuple(shard_arrays[0].shape[1:])
        arrays[leaf.dotted_path] = jax.make_array_from_single_device_arrays(
            global_shape, sharding, shard_arrays)
        if has_nulls:
            vsharding = NamedSharding(mesh, P(mesh.axis_names[0]))
            validities[leaf.dotted_path] = \
                jax.make_array_from_single_device_arrays(
                    (maxlen * len(shard_valid),), vsharding, shard_valid)
    return ShardedTable(arrays=arrays, validity=validities,
                        row_counts=tuple(shard_counts), mesh=mesh,
                        dictionaries=dictionaries, ragged=ragged,
                        leaves={leaf.dotted_path: leaf for leaf in leaves})


# ---------------------------------------------------------------------------
# shard_map decode step — the pjit'd "training step" analog
# ---------------------------------------------------------------------------


def decode_step_sharded(mesh: Mesh, n_per_shard: int, axis: str = "data"):
    """Build a jitted, mesh-sharded batched decode step; each call also
    counts its kernels' logical bytes (``kernel_bytes.*`` counters) and
    the runs its ``rle_expand`` scatters (``kernel_runs.rle_expand``).

    Input: per-device value words ``words_in [n_dev, W]`` (uint32, each
    device's batch of PLAIN INT64 values as little-endian words, ``W >=
    2 * n_per_shard``), uint8 level buffers and run tables likewise stacked
    on the leading mesh axis.  Each device decodes its shard
    (bitcast + RLE def-level expand + validity + null scatter); a psum'd
    row-count rides the ICI as the collective (the "global row count" a
    distributed scan wants).  This is the full per-step compute of the decode
    "model" under real dp sharding.
    """
    spec = P(axis)
    rep = P()

    def step(vwords, lbuf, run_ends, run_kinds, run_payloads, run_offs,
             run_widths):
        # one device's shard: drop the leading axis of size 1
        vw = vwords.reshape(vwords.shape[-1])
        lb = lbuf.reshape(lbuf.shape[-1])
        pairs = dev.fixed64_pairs(vw, n_per_shard)
        defs = dev.rle_expand(lb, n_per_shard, run_ends.reshape(-1),
                              run_kinds.reshape(-1), run_payloads.reshape(-1),
                              run_offs.reshape(-1), run_widths.reshape(-1))
        validity = defs == 1
        lo = jnp.where(validity, pairs[:, 0], 0)
        hi = jnp.where(validity, pairs[:, 1], 0)
        nrows = jax.lax.psum(jnp.sum(validity.astype(jnp.int32)), axis)
        return lo[None], hi[None], validity[None], nrows

    sharded = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(spec,) * 7,
        out_specs=(spec, spec, spec, rep),
        check_vma=False))
    n_dev = mesh.shape[axis]

    def call(vwords, lbuf, *runs):
        # the kernels' logical bytes, counted per call here: inside the
        # traced step they would count once per trace.  The step is handed
        # no unpadded level-stream length, so its encoded level bytes are
        # the staged buffers'
        counters.inc("kernel_bytes.fixed64_pairs", 16 * n_per_shard * n_dev)
        counters.inc("kernel_bytes.rle_expand",
                     int(np.prod(lbuf.shape)) + 4 * n_per_shard * n_dev)
        counters.inc("kernel_runs.rle_expand", int(np.prod(runs[0].shape)))
        return sharded(vwords, lbuf, *runs)

    return call


# ---------------------------------------------------------------------------
# Device-scale dataset reads — files round-robined over the mesh
# ---------------------------------------------------------------------------


def _overlap_enabled(n_files: int) -> bool:
    """PARQUET_TPU_DEVICE_OVERLAP: 0/off = stage then decode sequentially,
    auto = overlap when the shard has more than one file (a single file has
    no next stage to hide), force = always submit stage N+1 before decode
    N (chaos/identity tests pin both paths)."""
    mode = (env_str("PARQUET_TPU_DEVICE_OVERLAP") or "auto").strip().lower()
    if mode in ("0", "off", "false", "no"):
        return False
    if mode == "force":
        return True
    return n_files > 1


class _HostRoute(Exception):
    """Stage-phase verdict: this file must take the host path.  Carries the
    refusal reason/detail for ``device.route_refusals`` accounting."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(detail or reason)
        self.reason = reason
        self.detail = detail


@dataclass
class _FileStage:
    """One file's staged device state: every (leaf, row-group) chunk
    prepared (prescan + H2D put targeted at ``device``), admission grant
    and ``device.staging`` ledger residency held until :meth:`release`."""

    index: int
    pf: ParquetFile
    leaves: list
    rg_sel: list
    device: object
    est_bytes: int
    grant: int
    preps: list = field(default_factory=list)
    _released: bool = False

    def release(self) -> None:
        from ..utils.pool import read_admission

        if self._released:
            return
        self._released = True
        _ACC_STAGING.sub(self.est_bytes)
        read_admission().release(self.grant, tier="scan")


def _stage_dataset_file(dataset, i: int, columns, device) -> _FileStage:
    """Host phase of one dataset file's device read, run on a shared-pool
    worker: admission under the unified read budget, chunk-range prefetch
    (advise-backed readahead under the prescan + H2D), and a batched
    ``prepare_chunks_batched`` over every (leaf, row-group) targeted at
    ``device`` — one H2D dispatch per file.  Raises
    ``_HostRoute`` when the static encoding scan refuses the file; a chunk
    the stage plan refuses individually records its error and decodes on
    host at decode time (parity with ``decode_chunks_pipelined``)."""
    import contextlib

    from ..io.planner import device_encoding_supported
    from ..io.prefetch import make_chunk_prefetcher
    from ..io.reader import _select_leaves
    from ..utils.pool import read_admission
    from .device_reader import prepare_chunks_batched

    pf = dataset.file(i)
    dataset._check_schema(pf, dataset.paths[i])
    ok, why = device_encoding_supported(pf, columns)
    if not ok:
        raise _HostRoute("unsupported", why)
    leaves = _select_leaves(pf.schema, columns)
    rg_sel = list(range(len(pf.metadata.row_groups or [])))
    chunks = [pf.row_group(g).column(leaf.column_index)
              for leaf in leaves for g in rg_sel]
    est = sum(int(r.byte_range[1]) for r in chunks)
    # raw page payloads queue under the unified read budget and sit in the
    # device.staging account until the decode phase consumed them
    grant = read_admission().acquire(est, tier="scan")
    _ACC_STAGING.add(est)
    st = _FileStage(index=i, pf=pf, leaves=leaves, rg_sel=rg_sel,
                    device=device, est_bytes=est, grant=grant)
    try:
        with contextlib.ExitStack() as stack:
            pre = make_chunk_prefetcher(pf.source,
                                        n_streams=min(len(chunks), 4) or 1)
            if pre is not None:
                stack.enter_context(pf._source_override(pre))
                stack.callback(pre.close)
                pre.plan_many(r.byte_range for r in chunks)
            # every chunk's streams ride ONE batched device_put at the
            # file's chip — per-chunk H2D dispatch overhead scales with
            # row-group count, and the mesh route amortizes it per file
            st.preps.extend(prepare_chunks_batched(chunks, device=device))
    except BaseException:
        st.release()
        raise
    return st


def _decode_dataset_file(st: _FileStage):
    """Device phase: decode every staged chunk of one file (host fallback
    per refused chunk) and assemble the same per-file Table
    ``ParquetFile.read(device=True)`` returns."""
    from ..io.column import empty_column
    from ..io.faults import read_context
    from ..io.planner import count_device_refusal
    from ..io.reader import Table, decode_chunk_host

    pf = st.pf
    if not st.rg_sel:
        return Table(pf.schema, {leaf.dotted_path: empty_column(leaf)
                                 for leaf in st.leaves}, 0)
    n_rg = len(st.rg_sel)
    it = iter(st.preps)
    parts: Dict[str, list] = {}
    with jax.default_device(st.device):
        for leaf in st.leaves:
            cols = []
            for _ in range(n_rg):
                reader, prep, err = next(it)
                with read_context(path=pf._path, row_group=reader.rg_index,
                                  column=reader.leaf.dotted_path):
                    if err is not None:
                        count_device_refusal("unsupported", str(err))
                        counters.inc("chunks_host_fallback")
                        col = decode_chunk_host(reader)
                    else:
                        col, _nn = _decode_prepped(reader, prep)
                cols.append(col)
            parts[leaf.dotted_path] = cols
    return Table(pf.schema, None, pf.num_rows, parts=parts)


def read_dataset_device(dataset, columns=None, with_reports: bool = False,
                        host_read=None, mesh: Optional[Mesh] = None,
                        axis: str = "data"):
    """Per-file results for ``Dataset.read(device=True)``, yielded in file
    order as the same ``(table, sub_report, rows, error)`` tuples the host
    fan-out produces — ``Dataset._read_all`` merges both identically, so
    byte identity with the host path is structural, per-file host fallback
    included.

    Files round-robin over the mesh devices: file i's chunks stage H2D at
    ``devices[i % n]`` — the ``Dataset.shard(i, n)`` split a multi-host
    fleet applies per process (:func:`dataset_process_shard`) applied once
    more, per chip, inside the process.  Each file's stage→decode chain
    runs as one shared-pool task pinned to its chip and, when
    :func:`_overlap_enabled` allows, up to a window of later files run
    ahead of the consume frontier — file i+1 stages (and its chip decodes)
    while file i's decode completes, the write path's encode/emit
    double-buffering applied at the device boundary.  A file the static
    encoding scan refuses, or whose
    stage/decode dies on corrupt data, reroutes to ``host_read`` (the
    caller's plain per-file host read — fault policy, retries, and
    row-group skip semantics all apply there), with the refusal counted in
    ``device.route_refusals``.  Measured mesh throughput feeds
    ``RouteHistory`` under the ``"device_mesh"`` route, bucketed by mesh
    size."""
    from ..errors import CorruptedError, DeadlineError
    from ..io.faults import NON_DATA_ERRORS, ReadReport
    from ..io.planner import count_device_refusal, route_history
    from ..obs.metrics import pool_wait_seconds

    from concurrent.futures import Future

    mesh = mesh or default_mesh(axis=axis)
    devs = list(mesh.devices.reshape(-1))
    n = len(dataset.paths)
    overlap = _overlap_enabled(n)
    # nested inside a shared-pool worker: stage inline — blocking on
    # fut.result() from one of the pool's own workers while the pool is
    # saturated is the deadlock map_in_order's nested-submit guard exists
    # for (overlap degrades to sequential; correctness is unchanged)
    inline = _pool.in_shared_pool()

    def _stage_decode(i, device):
        # one file's full device chain on a pool worker: stage (prefetch +
        # prescan + H2D put) then decode on the file's chip.  Running the
        # decode here too is what lets files on DIFFERENT chips decode
        # concurrently instead of serializing on the consumer thread.
        st = _stage_dataset_file(dataset, i, columns, device)
        try:
            return st, _decode_dataset_file(st)
        except BaseException:
            st.release()
            raise

    def _submit(i):
        if inline:
            f = Future()
            try:
                f.set_result(_stage_decode(i, devs[i % len(devs)]))
            # ptlint: disable=PT005 -- capture-and-forward: the error
            # resurfaces at the driver's futs.pop(i).result() call below
            except BaseException as e:
                f.set_exception(e)
            return f
        return _pool.submit(_stage_decode, i, devs[i % len(devs)])

    def _host_one(i, reason, detail):
        count_device_refusal(reason, detail)
        return host_read(i)

    device_bytes = 0
    t_start = time.perf_counter()
    w0 = pool_wait_seconds()
    # overlap keeps up to min(mesh, 4) files in flight ahead of the
    # consume frontier — one per chip up to a memory-bounding cap; results
    # are still consumed strictly in file order, and the admission gate
    # (not the window) is what bounds resident staged bytes under a budget
    window = min(len(devs), 4) if overlap else 1
    futs: Dict[int, object] = {}
    try:
        for i in range(n):
            for j in range(i, min(i + window, n)):
                if j not in futs:
                    futs[j] = _submit(j)
                    if j > i:
                        # file j runs ahead while file i is still in
                        # flight / being consumed: the overlap the knob
                        # turns off
                        _oaccount(_M_STAGE_OVERLAPPED)
            res = None
            refusal = None
            try:
                res = futs.pop(i).result()
            except _HostRoute as e:
                refusal = (e.reason, e.detail)
            except DeadlineError:
                raise
            except NON_DATA_ERRORS:
                raise
            except (CorruptedError, OSError) as e:
                refusal = ("error", str(e))
            if res is None:
                yield _host_one(i, *refusal)
            else:
                st, tbl = res
                st.release()
                _oaccount(_M_FILES_SHARDED)
                device_bytes += st.est_bytes
                sub = ReadReport() if with_reports else None
                yield tbl, sub, st.pf.num_rows, None
    finally:
        for f in futs.values():
            # abandoned in-flight files (consumer stopped early, or an
            # exception above): wait them out and hand back their grants
            try:
                f.result()[0].release()
            except Exception:
                pass
        elapsed = time.perf_counter() - t_start
        if device_bytes:
            route_history().observe("device_mesh", device_bytes, elapsed,
                                    pool_wait_s=pool_wait_seconds() - w0,
                                    mesh_size=len(devs))
