"""Durable write sinks: the write-side analog of ``source.py``.

The read stack survives flaky storage (io/faults.py); this module makes the
*write* stack survive crashes.  Parquet's footer-last layout means a torn
write is detectable, but detection is not durability: a crashed writer that
opened the destination path directly leaves a half-written file AT the
destination, and a ``close()`` that never fsyncs leaves a "finished" file
that the page cache can still lose.  The jax_graft north star (SURVEY.md §5
checkpoint/resume) needs the standard stronger contract:

- **Atomic commit** (:class:`AtomicFileSink`): bytes go to
  ``<dest>.<rand>.tmp`` in the same directory; ``close()`` fsyncs the file,
  renames it over the destination, and fsyncs the directory so the rename
  itself is durable.  The destination path therefore either does not exist
  or holds a complete, footer-terminated file — never a torn one.
- **Abort** (:meth:`Sink.abort`): discard the write and remove the temp (or
  partial) file.  ``ParquetWriter.__exit__`` aborts when an exception is in
  flight instead of serializing a valid-looking footer over half-written
  row groups.

``ParquetWriter`` builds an :class:`AtomicFileSink` for every path sink by
default (``WriterOptions(atomic_commit=False)`` opts into the old direct
write via :class:`FileSink`, which still fsyncs and supports abort).
"""

from __future__ import annotations

import os
import secrets
from dataclasses import dataclass
from typing import List, Optional

from ..errors import WriteError
from ..utils.env import env_bool, env_opt_bytes
from ..utils.locks import make_lock
from ..obs import trace as _trace
from ..obs.ledger import ledger_account, maybe_check_pressure
from ..obs.metrics import counter as _counter
from ..obs.scope import account as _account

# resource-ledger account (obs/ledger.py): bytes currently coalescing in
# BufferedSinks process-wide — added as pages buffer, released as flushes
# hand them to the OS (or abort drops them), capacity = the live
# writeback knob
_ACC_WBUF = ledger_account("write.buffer", capacity=lambda:
                           write_buffer_bytes())

__all__ = ["Sink", "FileSink", "AtomicFileSink", "MmapFileSink",
           "BufferedSink", "WriteStats", "atomic_path_sink",
           "fsync_dir", "write_buffer_bytes", "write_autotune",
           "write_autotune_enabled"]

# default writeback buffer: large enough that page-sized writes coalesce into
# a handful of flushes per row group, small enough to stay cache-resident
DEFAULT_WRITE_BUFFER = 4 << 20

_HAS_WRITEV = hasattr(os, "writev")
try:
    _IOV_MAX = os.sysconf("SC_IOV_MAX")
    if _IOV_MAX <= 0:
        _IOV_MAX = 1024
except (AttributeError, OSError, ValueError):
    _IOV_MAX = 1024


@dataclass
class WriteStats:
    """What the pipelined write actually did (observability; surfaced as
    ``ParquetWriter.write_stats`` — the write-side mirror of
    :class:`~parquet_tpu.io.prefetch.ReadStats`).

    ``encode_s`` sums per-chunk encode wall time (wherever it ran),
    ``emit_s`` the serial offset-assign + sink-write phase, and
    ``pool_wait_s`` the time emit blocked on a background encode that had
    not finished — the write pipeline's bubble meter: ~0 means encode fully
    hid behind the previous group's emit.  ``bytes_buffered`` counts bytes
    coalesced through a :class:`BufferedSink`, ``bytes_flushed`` bytes that
    actually left toward the OS (equal to the file size for path sinks),
    ``sink_flushes`` how many coalesced flushes carried them, and
    ``writev_flushes`` how many of those went through the true vectored
    ``os.writev`` path (raw-fd sinks) instead of ``writelines``."""

    row_groups: int = 0
    overlapped_groups: int = 0
    encode_s: float = 0.0
    emit_s: float = 0.0
    pool_wait_s: float = 0.0
    bytes_buffered: int = 0
    bytes_flushed: int = 0
    sink_flushes: int = 0
    writev_flushes: int = 0

    def overlap_ratio(self) -> float:
        """Fraction of background encode time that emit did NOT wait for —
        1.0 means the pipeline fully hid encode behind emit, 0.0 means the
        write was effectively serial."""
        if not self.overlapped_groups or self.encode_s <= 0:
            return 0.0
        return max(0.0, min(1.0, 1.0 - self.pool_wait_s / self.encode_s))

    def as_dict(self) -> dict:
        return {"row_groups": self.row_groups,
                "overlapped_groups": self.overlapped_groups,
                "encode_s": round(self.encode_s, 4),
                "emit_s": round(self.emit_s, 4),
                "pool_wait_s": round(self.pool_wait_s, 4),
                "overlap_ratio": round(self.overlap_ratio(), 4),
                "bytes_buffered": self.bytes_buffered,
                "bytes_flushed": self.bytes_flushed,
                "sink_flushes": self.sink_flushes,
                "writev_flushes": self.writev_flushes}

    def publish(self) -> None:
        """Fold this writer's totals into the process-wide metrics
        registry (parquet_tpu/obs) and the current op scope — called at
        successful close.  Idempotent: a double-close (or a direct second
        call) publishes exactly once, so registry totals can never
        double."""
        if getattr(self, "_published", False):
            return
        self._published = True
        _account(_counter("write.row_groups"), self.row_groups)
        _account(_counter("write.overlapped_groups"), self.overlapped_groups)
        _account(_counter("write.encode_s"), self.encode_s)
        _account(_counter("write.emit_s"), self.emit_s)
        _account(_counter("write.pool_wait_s"), self.pool_wait_s)
        _account(_counter("write.bytes_buffered"), self.bytes_buffered)
        _account(_counter("write.bytes_flushed"), self.bytes_flushed)
        _account(_counter("write.sink_flushes"), self.sink_flushes)
        _account(_counter("write.writev_flushes"), self.writev_flushes)


# write-side auto-tuner (the mirror of io/prefetch.py's depth/window tuner):
# a writer that still needed many coalesced flushes PER ROW GROUP had a
# buffer too small for its page sizes — grow it for the next writer; one
# whose groups fit in a flush or two steps back toward the default
_WRITE_TUNE_RAISE_FLUSHES_PER_RG = 8.0
_WRITE_TUNE_DECAY_FLUSHES_PER_RG = 1.5
_WRITE_TUNE_MAX_BUFFER = 64 << 20


def write_autotune_enabled() -> bool:
    """``PARQUET_TPU_WRITE_AUTOTUNE`` opt-out (default on)."""
    return env_bool("PARQUET_TPU_WRITE_AUTOTUNE")


class _WriteAutoTuneState:
    """Process-wide feedback from observed :class:`WriteStats` to the next
    writer's writeback buffer size (ROADMAP follow-on: grow
    ``PARQUET_TPU_WRITE_BUFFER`` when ``sink_flushes`` per row group stays
    high).  An explicit env pin or ``PARQUET_TPU_WRITE_AUTOTUNE=0``
    bypasses the state entirely."""

    def __init__(self):
        self._lock = make_lock("sink.write_autotune")
        self.buffer = None  # None = default

    def suggest(self):
        with self._lock:
            return self.buffer

    def observe(self, stats: WriteStats) -> None:
        if stats.row_groups <= 0 or stats.bytes_buffered <= 0:
            return  # nothing buffered: pass-through writer, no signal
        per_rg = stats.sink_flushes / stats.row_groups
        with self._lock:
            b = self.buffer or DEFAULT_WRITE_BUFFER
            if per_rg > _WRITE_TUNE_RAISE_FLUSHES_PER_RG \
                    and b < _WRITE_TUNE_MAX_BUFFER:
                self.buffer = b * 2
            elif per_rg < _WRITE_TUNE_DECAY_FLUSHES_PER_RG \
                    and b > DEFAULT_WRITE_BUFFER:
                b //= 2
                self.buffer = None if b <= DEFAULT_WRITE_BUFFER else b

    def reset(self) -> None:
        with self._lock:
            self.buffer = None


_WRITE_AUTOTUNE = _WriteAutoTuneState()


def write_autotune() -> _WriteAutoTuneState:
    """The process-wide write auto-tune state (tests reset it)."""
    return _WRITE_AUTOTUNE


def _env_write_buffer() -> Optional[int]:
    """``PARQUET_TPU_WRITE_BUFFER`` as a pin, or None when unset OR
    unparseable — the single classifier both the size resolution and the
    autotune-eligibility gate consult, so a garbage value cannot count as
    "pinned" in one place while being ignored in the other."""
    return env_opt_bytes("PARQUET_TPU_WRITE_BUFFER")


def write_buffer_bytes() -> int:
    """Writeback buffer size: ``PARQUET_TPU_WRITE_BUFFER`` (bytes; ``0``
    disables coalescing) wins outright; otherwise the auto-tuned size from
    observed flush rates, falling back to the 4 MiB default."""
    pinned = _env_write_buffer()
    if pinned is not None:
        return pinned
    if write_autotune_enabled():
        tuned = _WRITE_AUTOTUNE.suggest()
        if tuned:
            return tuned
    return DEFAULT_WRITE_BUFFER


class Sink:
    """Minimal write-side protocol the writer relies on.  Any binary
    file-like object (``write``/``writelines``/``flush``/``close``) also
    works; ``abort`` is what distinguishes a crash-safe sink."""

    def write(self, data) -> int:
        raise NotImplementedError

    def writelines(self, parts) -> None:
        for p in parts:
            self.write(p)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        """Commit: make every written byte durable at the destination."""
        raise NotImplementedError

    def abort(self) -> None:
        """Discard: release resources and leave no (partial) destination."""
        raise NotImplementedError


def fsync_dir(path: str) -> None:
    """fsync the directory holding ``path`` so a just-created or
    just-renamed entry survives power loss.  Best-effort on filesystems or
    platforms where directories cannot be opened/fsynced (the rename itself
    already happened; only its durability ordering is at stake)."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    try:
        fd = os.open(d, getattr(os, "O_DIRECTORY", os.O_RDONLY))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _invalidate_dest(path) -> None:
    """Drop any cached footers/chunks of a just-committed destination.
    The caches' fstat identity handles rename-replaces and mtime-moving
    rewrites on its own; this closes the residual in-place same-size
    same-clock-tick window for writes made through this library."""
    from .cache import invalidate_path

    invalidate_path(path)


def _flushed_fileno(f):
    """Flush a file object's python-level buffer and return its fd (None
    when closed) — the one raw_fd contract both path sinks share."""
    if f is None:
        return None
    f.flush()
    return f.fileno()


class FileSink(Sink):
    """Direct-to-destination path sink: no atomicity, but fsync-on-close and
    abort-unlinks-the-partial-file.  The non-atomic mode of the writer
    (``atomic_commit=False``) — appropriate when the destination directory
    is not writable for siblings, or an external coordinator owns commit."""

    def __init__(self, path, fsync: bool = True):
        self.path = os.fspath(path)
        self.fsync = fsync
        self._f = open(self.path, "wb")

    def write(self, data) -> int:
        return self._f.write(data)

    def writelines(self, parts) -> None:
        self._f.writelines(parts)

    def flush(self) -> None:
        self._f.flush()

    def raw_fd(self):
        """OS-level fd for true vectored writes (the BufferedSink writev
        path).  The python-level buffer is flushed first so byte order is
        preserved across mixed fd/file-object writes; None when closed."""
        return _flushed_fileno(self._f)

    def close(self) -> None:
        if self._f is None:
            return
        f, self._f = self._f, None
        try:
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
        except BaseException:
            try:  # a failed flush/fsync must not leak the fd
                f.close()
            except OSError:
                pass
            raise
        f.close()
        _invalidate_dest(self.path)

    def abort(self) -> None:
        f, self._f = self._f, None
        if f is not None:
            try:
                f.close()
            except OSError:
                pass
        try:
            os.unlink(self.path)
        except OSError:
            # best-effort: abort usually runs inside an exception handler,
            # and an unlink failure must not mask the original error
            pass


class AtomicFileSink(Sink):
    """All-or-nothing path sink: write to ``<dest>.<rand>.tmp`` in the same
    directory, then ``close()`` = flush → fsync(file) → rename over ``dest``
    → fsync(dir).  Until close completes, the destination is untouched; a
    crash at ANY byte offset leaves at most a stray ``*.tmp`` (cheap to
    sweep — it can never be mistaken for data).  ``abort()`` unlinks the
    temp file and is idempotent; close-after-abort raises (there is nothing
    left to commit).

    The temp file lives in the destination's directory, not ``$TMPDIR``,
    because ``rename(2)`` is only atomic within one filesystem."""

    def __init__(self, dest, fsync: bool = True):
        self.dest = os.fspath(dest)
        self.fsync = fsync
        self.committed = False
        self.temp_path: Optional[str] = \
            f"{self.dest}.{secrets.token_hex(6)}.tmp"
        self._f = open(self.temp_path, "wb")

    def write(self, data) -> int:
        if self._f is None:
            raise ValueError(f"write on closed sink for {self.dest!r}")
        return self._f.write(data)

    def writelines(self, parts) -> None:
        if self._f is None:
            raise ValueError(f"write on closed sink for {self.dest!r}")
        self._f.writelines(parts)

    def flush(self) -> None:
        if self._f is not None:
            self._f.flush()

    def raw_fd(self):
        """OS-level fd of the TEMP file for true vectored writes (see
        :meth:`FileSink.raw_fd`); None when closed or committed."""
        return _flushed_fileno(self._f)

    def close(self) -> None:
        """Commit.  Any failure along the way aborts (the temp file is
        removed) and re-raises — a half-committed state is never retained,
        and the destination is never touched by a failed commit."""
        if self.committed:
            return
        if self._f is None:
            raise ValueError(
                f"commit after abort for {self.dest!r} (nothing to commit)")
        tp = self.temp_path
        f, self._f = self._f, None
        try:
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
            f.close()
            os.replace(tp, self.dest)
        except BaseException as e:
            # release the fd, sweep the temp file, and surface the commit
            # failure with both locations attached
            try:
                f.close()  # double-close of a file object is a no-op
            except OSError:
                pass
            try:
                os.unlink(tp)
            except OSError:
                pass
            self.temp_path = None
            if isinstance(e, OSError):
                raise WriteError(f"atomic commit failed: {e}",
                                 path=self.dest, temp_path=tp) from e
            raise
        self.temp_path = None
        self.committed = True
        if self.fsync:
            # the rename is on disk only once the directory entry is:
            # without this, a crash can resurrect the OLD destination
            fsync_dir(self.dest)
        _invalidate_dest(self.dest)

    def abort(self) -> None:
        f, self._f = self._f, None
        if f is not None:
            try:
                f.close()
            except OSError:
                pass
        tp, self.temp_path = self.temp_path, None
        if tp is not None and not self.committed:
            try:
                os.unlink(tp)
            except OSError:
                # best-effort: abort usually runs inside an exception
                # handler, and an unlink failure must not mask the original
                pass


class MmapFileSink(Sink):
    """mmap-backed atomic path sink (the ``PARQUET_TPU_MMAP_SINK``
    experiment): bytes copy into a memory-mapped temp file grown in
    8 MiB steps instead of going through buffered ``write()`` calls;
    ``close()`` = flush(map) → truncate-to-length → fsync → rename over
    the destination → fsync(dir) — the exact commit contract of
    :class:`AtomicFileSink`, so the crash matrix covers it unchanged.

    Measured verdict (bench cfg6 ``mmap_sink`` A/B): ~0.75x of the
    writev path — the map's fault+copy cost loses to vectored writes on
    page-cache-backed filesystems.  KEPT strictly as an opt-in because
    it removes syscall pressure under heavy seccomp/audit regimes; not
    the default."""

    _GROW = 8 << 20

    def __init__(self, dest, fsync: bool = True):
        import mmap

        self.dest = os.fspath(dest)
        self.fsync = fsync
        self.committed = False
        self.temp_path: Optional[str] = \
            f"{self.dest}.{secrets.token_hex(6)}.tmp"
        self._f = open(self.temp_path, "w+b")
        self._f.truncate(self._GROW)
        self._mm = mmap.mmap(self._f.fileno(), self._GROW)
        self._len = 0

    def _ensure(self, need: int) -> None:
        if need <= len(self._mm):
            return
        size = len(self._mm)
        while size < need:
            size += self._GROW
        self._f.truncate(size)
        self._mm.resize(size)

    def write(self, data) -> int:
        if self._f is None:
            raise ValueError(f"write on closed sink for {self.dest!r}")
        # normalize to a byte view without copying (bytes(data) would
        # memcpy every payload once more before the map copy)
        mv = data if isinstance(data, (bytes, bytearray)) \
            else memoryview(data).cast("B")
        n = len(mv)
        self._ensure(self._len + n)
        self._mm[self._len : self._len + n] = mv
        self._len += n
        return n

    def writelines(self, parts) -> None:
        for p in parts:
            self.write(p)

    def flush(self) -> None:
        if self._mm is not None:
            self._mm.flush()

    def close(self) -> None:
        """Commit: flush the map, trim to the written length, fsync,
        rename, fsync(dir) — failures abort (temp removed) and re-raise,
        exactly like :class:`AtomicFileSink.close`."""
        if self.committed:
            return
        if self._f is None:
            raise ValueError(
                f"commit after abort for {self.dest!r} (nothing to commit)")
        tp = self.temp_path
        f, self._f = self._f, None
        mm, self._mm = self._mm, None
        try:
            mm.flush()
            mm.close()
            f.truncate(self._len)
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
            f.close()
            os.replace(tp, self.dest)
        except BaseException as e:
            try:
                f.close()
            except OSError:
                pass
            try:
                os.unlink(tp)
            except OSError:
                pass
            self.temp_path = None
            if isinstance(e, OSError):
                raise WriteError(f"mmap sink commit failed: {e}",
                                 path=self.dest, temp_path=tp) from e
            raise
        self.temp_path = None
        self.committed = True
        if self.fsync:
            fsync_dir(self.dest)
        _account(_counter("write.mmap_commits"))
        _invalidate_dest(self.dest)

    def abort(self) -> None:
        f, self._f = self._f, None
        mm, self._mm = self._mm, None
        if mm is not None:
            try:
                mm.close()
            except (OSError, ValueError):
                pass
        if f is not None:
            try:
                f.close()
            except OSError:
                pass
        tp, self.temp_path = self.temp_path, None
        if tp is not None and not self.committed:
            try:
                os.unlink(tp)
            except OSError:
                pass


def atomic_path_sink(dest, fsync: bool = True) -> Sink:
    """The atomic path sink the writer (and the crash harness) commit
    through: :class:`MmapFileSink` when ``PARQUET_TPU_MMAP_SINK`` opts
    in, else :class:`AtomicFileSink` — one selector so the crash matrix
    always covers whichever variant production writes use."""
    if env_bool("PARQUET_TPU_MMAP_SINK"):
        return MmapFileSink(dest, fsync=fsync)
    return AtomicFileSink(dest, fsync=fsync)


def _writev_all(fd, parts) -> None:
    """Write every part to ``fd`` with ``os.writev`` — one syscall per
    ``IOV_MAX`` group instead of one per part — resuming short (partial)
    writes mid-part until every byte is down."""
    queue = [memoryview(p) for p in parts if len(p)]
    i = 0
    while i < len(queue):
        batch = queue[i:i + _IOV_MAX]
        written = os.writev(fd, batch)
        if written <= 0:
            raise OSError(f"writev wrote {written} of "
                          f"{sum(len(m) for m in batch)} bytes")
        for mv in batch:
            n = len(mv)
            if written >= n:
                written -= n
                i += 1
            else:
                queue[i] = mv[written:]
                break


class BufferedSink(Sink):
    """Coalescing writeback layer over any sink: page-sized writes
    accumulate by reference (no join copy) and flush to the inner sink as
    one vectored write once ``buffer_bytes`` is pending — a true
    ``os.writev`` when the inner sink exposes a raw fd (``raw_fd()``;
    FileSink/AtomicFileSink do), a ``writelines`` fallback otherwise — the
    write-side analog of the prefetcher's coalesced window reads.  The
    per-page ``write()`` syscall overhead this removes is the emit phase's
    residual cost once encode is pipelined (io/writer.py).

    ``buffer_bytes=0`` is a counting pass-through (every write goes straight
    to the inner sink); the default comes from ``PARQUET_TPU_WRITE_BUFFER``.
    ``flush()``/``close()`` drain the buffer first, so the inner sink's
    commit (fsync + atomic rename for :class:`AtomicFileSink`) always covers
    every accepted byte; ``abort()`` drops the buffer and aborts the inner
    sink.  Buffered parts are kept by reference — callers must not mutate a
    buffer after writing it (the parquet writer only writes immutable
    ``bytes``).  A ``stats`` :class:`WriteStats` accounts buffered vs
    flushed bytes and flush counts."""

    def __init__(self, inner: Sink, buffer_bytes: Optional[int] = None,
                 stats: Optional[WriteStats] = None):
        self.inner = inner
        self.buffer_bytes = (write_buffer_bytes() if buffer_bytes is None
                             else max(0, int(buffer_bytes)))
        self.stats = stats
        # auto-tune eligibility: the writer observes this sink's WriteStats
        # into the process tuner only when the size came from the tuner's
        # own resolution path (no explicit arg, no env pin) — mirrors the
        # prefetcher's _tunable gate
        self._tunable = (buffer_bytes is None and write_autotune_enabled()
                         and _env_write_buffer() is None)
        self._parts: List[bytes] = []
        self._buffered = 0

    def write(self, data) -> int:
        n = len(data)
        if self.buffer_bytes <= 0:
            self.inner.write(data)
            if self.stats is not None:
                self.stats.bytes_flushed += n
            return n
        self._parts.append(data)
        self._buffered += n
        _ACC_WBUF.add(n)
        if self.stats is not None:
            self.stats.bytes_buffered += n
        if self._buffered >= self.buffer_bytes:
            self._flush_buffer()
        else:
            # growth site: the write buffer can push the process over a
            # watermark between flushes (two env reads when none is set)
            maybe_check_pressure()
        return n

    def writelines(self, parts) -> None:
        if self.buffer_bytes <= 0:
            n = 0
            parts = list(parts)
            for p in parts:
                n += len(p)
            self.inner.writelines(parts)
            if self.stats is not None:
                self.stats.bytes_flushed += n
            return
        for p in parts:
            self._parts.append(p)
            self._buffered += len(p)
            _ACC_WBUF.add(len(p))
            if self.stats is not None:
                self.stats.bytes_buffered += len(p)
        if self._buffered >= self.buffer_bytes:
            self._flush_buffer()
        else:
            maybe_check_pressure()

    def _flush_buffer(self) -> None:
        if not self._parts:
            return
        if _trace.on():
            with _trace.span("sink.flush", bytes=self._buffered,
                             parts=len(self._parts)):
                self._flush_buffer_impl()
            return
        self._flush_buffer_impl()

    def _flush_buffer_impl(self) -> None:
        # hand the parts over before writing: a failed flush must not be
        # replayed (bytes may be partially down — the writer aborts on any
        # write error, and a retry would double-write the prefix)
        parts, self._parts = self._parts, []
        n, self._buffered = self._buffered, 0
        _ACC_WBUF.sub(n)  # released at hand-over: a failed flush's bytes
        # are dropped, not re-buffered, so the ledger must not hold them
        fd = None
        if _HAS_WRITEV:
            raw = getattr(self.inner, "raw_fd", None)
            if raw is not None:
                fd = raw()
        if fd is not None:
            _writev_all(fd, parts)
            if self.stats is not None:
                self.stats.writev_flushes += 1
        else:
            self.inner.writelines(parts)
        if self.stats is not None:
            self.stats.bytes_flushed += n
            self.stats.sink_flushes += 1

    def flush(self) -> None:
        self._flush_buffer()
        self.inner.flush()

    def close(self) -> None:
        self._flush_buffer()
        self.inner.close()

    def abort(self) -> None:
        self._parts = []
        _ACC_WBUF.sub(self._buffered)
        self._buffered = 0
        ab = getattr(self.inner, "abort", None)
        if ab is not None:
            ab()
        else:
            try:
                self.inner.close()
            except OSError:
                pass
