"""Out-of-core feature-chunked storage for the ``(m, n)`` design matrix.

The paper's headline regime — high-dimensional text-like data with
``m >> n`` and mostly-zero ``X`` — is exactly where the design matrix stops
fitting on one device while every *working set* (a feature chunk, the
screened active set, every ``(n,)``/``(m,)`` vector) still does. This module
provides the storage container the rest of the pipeline streams over:

* :class:`FeatureChunked` holds ``X`` as a sequence of fixed-size
  **feature-block chunks** (row blocks in the paper's features-major
  layout). Each chunk lives on the *host*, either dense (``np.ndarray``) or
  CSR (:class:`CsrChunk` — indptr/indices/data over the chunk's rows), and
  is shipped to the device only while it is being swept.
* :meth:`FeatureChunked.stream` is the single device-transfer point:
  it double-buffers ``jax.device_put`` (chunk ``i+1`` is dispatched while
  chunk ``i`` computes — transfers are async, so host→device copy overlaps
  device compute), and converts low-density CSR chunks to
  ``jax.experimental.sparse.BCOO`` so the hot sweeps cost FLOPs
  proportional to ``nnz`` rather than ``chunk_m * n``.
* :meth:`matvec` / :meth:`rmatvec` are the chunk-accumulated GEMV pair the
  streamed FISTA solver is built on (``grad = X r`` concatenates per-chunk
  rows; ``u = X^T w`` accumulates per-chunk partials), and
  :meth:`gather_rows` is the host-side gather the chunked
  :class:`~repro.core.path.PathDriver` uses to materialize only the rows
  that *survive screening* — peak device memory is ``O(chunk + kept)``,
  never ``O(m * n)``.

Chunk skipping (the chunk-level screening data plane): every streaming
entry point takes ``live_chunks=`` — a boolean mask (or index list) over
chunks — and chunks marked dead are never ``device_put`` at all.
:meth:`matvec` fills their output rows with zeros (their weights are
certified zero) and :meth:`rmatvec` simply omits their partials, so solver
sweeps cost transfers proportional to the *live* data. The safe-bound
machinery that certifies chunks dead lives in ``screen_stream.py``
(:class:`~repro.sparse.screen_stream.ChunkScreenCache`).

Disk residency: :meth:`save_store` / :meth:`from_store` round-trip the
container through an ``np.memmap``-backed directory (one flat binary per
array; chunks are memmap *views*, so host RSS stays O(touched pages), and
the OS page cache is the disk→host stage of the double buffer), and
:meth:`from_libsvm_cached` builds that store once from libsvm text in two
streaming passes — the full ``(m, n)`` matrix is never host-RAM-resident.

Device-memory contract: no method of this class ever places more than one
chunk (plus ``O(m + n)`` vectors) on the device at a time; the property test
in ``tests/test_sparse_stream.py`` walks the jaxprs of every per-chunk
kernel and asserts no ``(m, n)``-sized intermediate exists. ``as_dense()``
is the explicit escape hatch for in-core use and small tests.

``stats`` counts transfers (``puts`` — and ``chunks_streamed`` /
``chunks_skipped`` / ``bytes_put`` for the skip plane) and the largest row
block ever put on device (``max_put_rows``) so benchmarks and tests can
observe the contract instead of trusting it.

Store integrity: ``save_store`` (and the ``from_libsvm_cached`` build)
records a crc32 per store-grid chunk in ``meta.json``; ``from_store``
validates file presence/sizes up front (typed :class:`StoreMissingError` /
:class:`StoreCorruptError`) and verifies each grid chunk's checksum lazily,
the first time any of its rows is about to reach the device — so a corrupt
chunk is detected *before* its bytes can participate in a sweep or a
screening bound. Transient read faults retry with backoff
(:func:`_read_with_retry`; ``_read_fault_hook`` is the fault-injection
seam), and ``from_libsvm_cached`` rebuilds the store from the libsvm text
when opening it fails with a :class:`StoreError`.
"""

from __future__ import annotations

import json
import os
import time
import zlib
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.linalg import dot
from repro.obs import metrics as obs_metrics

__all__ = ["CsrChunk", "FeatureChunked", "BCOO_DENSITY_THRESHOLD",
           "StoreError", "StoreMissingError", "StoreCorruptError"]


class StoreError(RuntimeError):
    """Base error for on-disk store problems (missing, corrupt, unreadable)."""


class StoreMissingError(StoreError):
    """The store directory or one of its files does not exist."""


class StoreCorruptError(StoreError):
    """The store exists but fails validation (truncated file, bad meta,
    checksum mismatch)."""


#: Testing seam: when set, called as ``hook(tag, attempt)`` before every
#: guarded store read; raising ``OSError`` simulates a transient I/O fault
#: (``testing/faults.py`` installs finite- and infinite-fault versions).
_read_fault_hook = None
_READ_RETRIES = 3
_READ_BACKOFF_S = 0.02


def _read_with_retry(fn, tag: str):
    """Run a store read, retrying transient ``OSError`` with backoff.

    mmap-backed reads surface disk faults as ``OSError``/``BusError`` at
    page-touch time; NFS and flaky disks produce transient ones. Bounded
    retries with exponential backoff absorb those; persistent failure
    surfaces as a typed :class:`StoreError` naming the read.
    """
    last = None
    for attempt in range(_READ_RETRIES):
        try:
            if _read_fault_hook is not None:
                _read_fault_hook(tag, attempt)
            return fn()
        except OSError as e:
            last = e
            if attempt + 1 < _READ_RETRIES:
                time.sleep(_READ_BACKOFF_S * (2 ** attempt))
    raise StoreError(
        f"store read failed after {_READ_RETRIES} attempts: {tag}") from last


def _grid_chunk_crc(fmt: str, arrays, s: int, e: int) -> int:
    """crc32 of store-grid rows ``[s, e)`` — the payload bytes a sweep of
    those rows would consume (CSR: data + indices + the indptr slice)."""
    if fmt == "csr":
        data, indices, indptr = arrays
        lo, hi = int(indptr[s]), int(indptr[e])
        c = zlib.crc32(np.ascontiguousarray(data[lo:hi]).tobytes())
        c = zlib.crc32(np.ascontiguousarray(indices[lo:hi]).tobytes(), c)
        return zlib.crc32(np.ascontiguousarray(indptr[s:e + 1]).tobytes(), c)
    (X,) = arrays
    return zlib.crc32(np.ascontiguousarray(X[s:e]).tobytes())


def _store_grid_checksums(store_dir, meta: dict) -> dict:
    """Compute the ``meta["checksums"]`` block by re-reading the written
    binaries on the store's uniform chunk grid (verification's frame of
    reference, independent of any runtime re-chunking)."""
    m = int(meta["m"])
    cm = int(meta["chunk_m"])
    dt = np.dtype(meta["dtype"])
    if meta["format"] == "csr":
        indptr = np.memmap(os.path.join(store_dir, "indptr.bin"),
                           dtype=np.int64, mode="r", shape=(m + 1,))
        nnz = max(int(indptr[-1]), 1)
        arrays = (
            np.memmap(os.path.join(store_dir, "data.bin"), dtype=dt,
                      mode="r", shape=(nnz,)),
            np.memmap(os.path.join(store_dir, "indices.bin"),
                      dtype=np.int32, mode="r", shape=(nnz,)),
            indptr,
        )
    else:
        arrays = (np.memmap(os.path.join(store_dir, "X.bin"), dtype=dt,
                            mode="r", shape=(m, int(meta["n"]))),)
    crcs = [_grid_chunk_crc(meta["format"], arrays, s, min(s + cm, m))
            for s in range(0, m, cm)]
    out = {"algo": "crc32", "chunks": crcs}
    if meta.get("has_y"):
        y_path = os.path.join(store_dir, "y.bin")
        with open(y_path, "rb") as fy:
            out["y"] = zlib.crc32(fy.read())
    return out


def _require_store_file(store_dir, name: str,
                        nbytes: Optional[int] = None) -> str:
    p = os.path.join(store_dir, name)
    if not os.path.exists(p):
        raise StoreMissingError(f"store {store_dir} is missing {name}")
    if nbytes is not None and os.path.getsize(p) < nbytes:
        raise StoreCorruptError(
            f"{p} is truncated: {os.path.getsize(p)} bytes, "
            f"expected at least {nbytes}")
    return p

#: CSR chunks at or below this density are swept as BCOO on device (FLOPs
#: scale with nnz); denser CSR chunks are densified per transfer (the dense
#: sweep's bandwidth wins once a third of the entries are nonzero anyway).
BCOO_DENSITY_THRESHOLD = 0.05


class CsrChunk(NamedTuple):
    """Host CSR block over a contiguous range of feature rows."""

    data: np.ndarray     # (nnz,)
    indices: np.ndarray  # (nnz,) int32 column (sample) indices
    indptr: np.ndarray   # (rows + 1,) int64
    n_cols: int

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def density(self) -> float:
        denom = max(self.rows * self.n_cols, 1)
        return self.nnz / denom

    def to_dense(self, dtype=None) -> np.ndarray:
        out = np.zeros((self.rows, self.n_cols),
                       dtype=dtype or self.data.dtype)
        rows = np.repeat(np.arange(self.rows), np.diff(self.indptr))
        out[rows, self.indices] = self.data
        return out

    def row_sq(self) -> np.ndarray:
        """``||f_j||^2`` per chunk row, from the CSR data (no densify)."""
        sq = self.data.astype(self.data.dtype) ** 2
        out = np.zeros((self.rows,), dtype=self.data.dtype)
        if len(sq):
            rows = np.repeat(np.arange(self.rows), np.diff(self.indptr))
            np.add.at(out, rows, sq)
        return out


def _as_csr_parts(csr) -> tuple[np.ndarray, np.ndarray, np.ndarray, tuple]:
    """Duck-typed CSR unpack: scipy.sparse.csr_matrix, data/svm.CsrData, or
    a plain ``(data, indices, indptr, shape)`` tuple."""
    if hasattr(csr, "indptr") and hasattr(csr, "shape"):
        return (np.asarray(csr.data), np.asarray(csr.indices),
                np.asarray(csr.indptr), tuple(csr.shape))
    data, indices, indptr, shape = csr
    return np.asarray(data), np.asarray(indices), np.asarray(indptr), tuple(shape)


class FeatureChunked:
    """``X`` as host-resident feature-row chunks, streamed to device on use.

    Build with :meth:`from_dense` or :meth:`from_csr`; the constructor takes
    an explicit chunk list (each ``np.ndarray`` of shape ``(rows_i, n)`` or
    :class:`CsrChunk`) for callers assembling chunks from external storage.
    """

    def __init__(self, chunks: Sequence[Union[np.ndarray, CsrChunk]], n: int,
                 dtype=np.float32,
                 bcoo_threshold: float = BCOO_DENSITY_THRESHOLD):
        if not chunks:
            raise ValueError("FeatureChunked needs at least one chunk")
        self.chunks = list(chunks)
        self.n = int(n)
        self.dtype = np.dtype(dtype)
        self.bcoo_threshold = float(bcoo_threshold)
        rows = []
        for c in self.chunks:
            if isinstance(c, CsrChunk):
                if c.n_cols != self.n:
                    raise ValueError(f"chunk n_cols {c.n_cols} != {self.n}")
                rows.append(c.rows)
            else:
                if c.ndim != 2 or c.shape[1] != self.n:
                    raise ValueError(f"bad chunk shape {c.shape}")
                rows.append(c.shape[0])
        self.offsets = np.concatenate([[0], np.cumsum(rows)]).astype(np.int64)
        self.m = int(self.offsets[-1])
        self.stats = {"puts": 0, "max_put_rows": 0, "bcoo_puts": 0,
                      "chunks_streamed": 0, "chunks_skipped": 0,
                      "bytes_put": 0}
        # set by from_store: lazy checksum-verification state over the
        # store's uniform chunk grid (None = not store-backed / no sums)
        self._store = None

    def _bump(self, key: str, n: int = 1):
        """Increment a legacy ``stats`` counter and mirror it into the
        process-wide metrics registry under ``stream.<key>``."""
        self.stats[key] += n
        obs_metrics.counter("stream." + key).inc(n)

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_dense(cls, X, chunk_m: int = 512, **kw) -> "FeatureChunked":
        """Split a dense ``(m, n)`` host matrix into row chunks (no copy of
        the chunk data beyond numpy views)."""
        X = np.asarray(X)
        m, n = X.shape
        chunk_m = max(int(chunk_m), 1)
        chunks = [X[s: s + chunk_m] for s in range(0, m, chunk_m)]
        return cls(chunks, n, dtype=X.dtype, **kw)

    @classmethod
    def from_csr(cls, csr, chunk_m: int = 512, **kw) -> "FeatureChunked":
        """Split a CSR matrix over feature rows into :class:`CsrChunk`s.

        ``csr`` is anything with ``data``/``indices``/``indptr``/``shape``
        (scipy ``csr_matrix``, :class:`repro.data.svm.CsrData`) or a plain
        ``(data, indices, indptr, shape)`` tuple. Slicing CSR row blocks is
        an ``indptr`` slice — no per-element work.
        """
        data, indices, indptr, shape = _as_csr_parts(csr)
        m, n = shape
        chunk_m = max(int(chunk_m), 1)
        chunks = []
        for s in range(0, m, chunk_m):
            e = min(s + chunk_m, m)
            lo, hi = indptr[s], indptr[e]
            chunks.append(CsrChunk(
                data=data[lo:hi],
                indices=np.asarray(indices[lo:hi], np.int32),
                indptr=np.asarray(indptr[s: e + 1] - lo, np.int64),
                n_cols=int(n),
            ))
        return cls(chunks, int(n), dtype=data.dtype, **kw)

    # -- shape / metadata --------------------------------------------------

    @property
    def shape(self) -> tuple[int, int]:
        return (self.m, self.n)

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    def chunk_bounds(self, i: int) -> tuple[int, int]:
        return int(self.offsets[i]), int(self.offsets[i + 1])

    def chunk_density(self, i: int) -> float:
        c = self.chunks[i]
        if isinstance(c, CsrChunk):
            return c.density
        denom = max(c.size, 1)
        return float(np.count_nonzero(c)) / denom

    def density(self) -> float:
        nnz = sum(c.nnz if isinstance(c, CsrChunk) else np.count_nonzero(c)
                  for c in self.chunks)
        return nnz / max(self.m * self.n, 1)

    # -- escape hatch ------------------------------------------------------

    def as_dense(self) -> np.ndarray:
        """Materialize the full host matrix (in-core escape hatch)."""
        return np.concatenate([
            c.to_dense(self.dtype) if isinstance(c, CsrChunk)
            else np.asarray(c, self.dtype)
            for c in self.chunks
        ], axis=0)

    # -- device streaming --------------------------------------------------

    def _verify_rows(self, s: int, e: int) -> None:
        """Checksum-verify the store-grid chunks overlapping rows ``[s, e)``
        before those bytes reach any consumer; each grid chunk is verified
        once per container (the memmap views are read-only thereafter, and
        re-verifying every stream would double the path's disk traffic)."""
        st = self._store
        if st is None:
            return
        cm = st["chunk_m"]
        for j in range(s // cm, -(-e // cm)):
            if st["verified"][j]:
                continue
            gs, ge = j * cm, min((j + 1) * cm, self.m)
            got = _read_with_retry(
                lambda: _grid_chunk_crc(st["format"], st["arrays"], gs, ge),
                f"{st['dir']} rows [{gs}, {ge})")
            if got != st["crcs"][j]:
                raise StoreCorruptError(
                    f"checksum mismatch in store chunk {j} of {st['dir']} "
                    f"(rows [{gs}, {ge})): expected "
                    f"{st['crcs'][j]:#010x}, got {got:#010x}")
            st["verified"][j] = True

    def verify(self) -> None:
        """Eagerly checksum-verify the whole store (no-op when the container
        is not store-backed or the store predates checksums)."""
        self._verify_rows(0, self.m)

    def _device_form(self, i: int):
        """One chunk's device representation: dense ``jax.Array`` or BCOO."""
        from jax.experimental import sparse as jsparse

        self._verify_rows(*self.chunk_bounds(i))
        c = self.chunks[i]
        rows = c.rows if isinstance(c, CsrChunk) else c.shape[0]
        self._bump("puts")
        self._bump("chunks_streamed")
        self.stats["max_put_rows"] = max(self.stats["max_put_rows"], rows)
        obs_metrics.gauge("stream.max_put_rows").set_max(rows)
        if isinstance(c, CsrChunk) and c.density <= self.bcoo_threshold:
            self._bump("bcoo_puts")
            row_idx = np.repeat(np.arange(c.rows, dtype=np.int32),
                                np.diff(c.indptr))
            idx = np.stack([row_idx, c.indices.astype(np.int32)], axis=1)
            data = c.data.astype(self.dtype)
            self._bump("bytes_put", data.nbytes + idx.nbytes)
            return jsparse.BCOO(
                (jax.device_put(data), jax.device_put(idx)),
                shape=(c.rows, self.n),
            )
        dense = np.asarray(c.to_dense(self.dtype) if isinstance(c, CsrChunk)
                           else c, self.dtype)
        self._bump("bytes_put", dense.nbytes)
        return jax.device_put(dense)

    def live_order(self, live_chunks) -> list:
        """Normalize a ``live_chunks`` spec (bool mask over chunks, or index
        list) into an ascending chunk-index list; ``None`` means all live."""
        if live_chunks is None:
            return list(range(self.n_chunks))
        lv = np.asarray(live_chunks)
        if lv.dtype == bool:
            if lv.shape != (self.n_chunks,):
                raise ValueError(
                    f"live_chunks mask shape {lv.shape} != ({self.n_chunks},)")
            return [int(i) for i in np.nonzero(lv)[0]]
        return sorted(int(i) for i in lv)

    def stream(self, live_chunks=None):
        """Yield ``((start, stop), device_chunk)`` with one-chunk prefetch.

        ``jax.device_put`` is asynchronous: dispatching chunk ``i+1``'s
        transfer before yielding chunk ``i`` overlaps the next copy with the
        caller's compute on the current chunk (classic double buffering);
        at most two chunks are in flight on the device at any moment. For
        memmap-backed chunks the host-side read of chunk ``i+1`` (OS page-in
        inside ``_device_form``) also happens before the caller computes on
        chunk ``i``, so disk→host overlaps device compute the same way.

        ``live_chunks`` restricts the stream to the live subset: dead
        chunks are *never* transferred (their ``device_put`` is skipped
        entirely and counted in ``stats["chunks_skipped"]``). The prefetch
        runs over the live subsequence, so skipping keeps the double buffer.
        """
        order = self.live_order(live_chunks)
        self._bump("chunks_skipped", self.n_chunks - len(order))
        if not order:
            return
        nxt = self._device_form(order[0])
        for j, i in enumerate(order):
            cur = nxt
            if j + 1 < len(order):
                nxt = self._device_form(order[j + 1])
            yield self.chunk_bounds(i), cur

    # -- chunk-accumulated GEMV pair (the solver's two sweeps) -------------

    def matvec(self, v, live_chunks=None) -> jax.Array:
        """``X @ v`` — per-chunk rows, concatenated (the gradient sweep).

        Dead chunks contribute exact zero rows without being transferred:
        the screened solver only ever multiplies into weights certified zero
        there, and the zero-fill keeps the output shape ``(m,)``.
        """
        v = jnp.asarray(v, self.dtype)
        if live_chunks is None:
            return jnp.concatenate(
                [_chunk_mv(dev, v) for _, dev in self.stream()])
        live = set(self.live_order(live_chunks))
        it = self.stream(live_chunks=live_chunks)
        parts = []
        for i in range(self.n_chunks):
            s, e = self.chunk_bounds(i)
            if i in live:
                parts.append(_chunk_mv(next(it)[1], v))
            else:
                parts.append(jnp.zeros((e - s,), self.dtype))
        return jnp.concatenate(parts)

    def rmatvec(self, w, live_chunks=None) -> jax.Array:
        """``X^T w`` — per-chunk partials, accumulated (the margin sweep).

        Dead chunks are skipped outright: their ``w`` slice is zero, so
        their partial is an exact zero addend.
        """
        w = jnp.asarray(w, self.dtype)
        acc = jnp.zeros((self.n,), self.dtype)
        for (s, e), dev in self.stream(live_chunks=live_chunks):
            acc = acc + _chunk_rmv(dev, w[s:e])
        return acc

    def col_sq(self) -> jax.Array:
        """``||x_i||^2`` per *sample* (column) — the transposed reduction.

        Chunk-accumulated sum over feature rows of ``X**2``; CSR chunks
        scatter their squared data by column index on the host (no densify,
        no transfer). Theta-independent, so the result is memoized on the
        container — sample rules read it every path step for free. This is
        what lets ``sifs``/``sample_vi`` run out-of-core instead of forcing
        ``as_dense()``.
        """
        cached = getattr(self, "_col_sq_cache", None)
        if cached is not None:
            return cached
        acc = jnp.zeros((self.n,), self.dtype)
        for i, c in enumerate(self.chunks):
            if isinstance(c, CsrChunk):
                part = np.zeros((self.n,), dtype=self.dtype)
                if c.nnz:
                    np.add.at(part, c.indices,
                              (c.data.astype(self.dtype)) ** 2)
                acc = acc + jnp.asarray(part)
            else:
                acc = acc + _chunk_csq(self._device_form(i))
        self._col_sq_cache = acc
        return acc

    def row_sq(self) -> jax.Array:
        """``||f_j||^2`` for every feature row (one stream; CSR chunks from
        their data, no densify)."""
        outs = []
        for i, c in enumerate(self.chunks):
            if isinstance(c, CsrChunk):
                outs.append(jnp.asarray(c.row_sq().astype(self.dtype)))
            else:
                outs.append(_chunk_sq(self._device_form(i)))
        return jnp.concatenate(outs)

    # -- host-side gather (the screened-path reduction) --------------------

    def gather_rows(self, idx: np.ndarray) -> np.ndarray:
        """Dense host gather of the given global feature rows.

        The chunked path driver calls this with the rows that *survived*
        screening (bucket-padded): only chunks containing surviving rows are
        touched, and only those rows are densified — the device then holds a
        ``(kept_padded, n)`` block, never the full matrix.
        """
        idx = np.asarray(idx, np.int64)
        out = np.zeros((len(idx), self.n), dtype=self.dtype)
        which = np.searchsorted(self.offsets[1:], idx, side="right")
        for ci in np.unique(which):
            self._verify_rows(*self.chunk_bounds(int(ci)))
        for ci in np.unique(which):
            sel = np.nonzero(which == ci)[0]
            local = idx[sel] - self.offsets[ci]
            c = self.chunks[ci]
            if isinstance(c, CsrChunk):
                for dst, r in zip(sel, local):
                    lo, hi = c.indptr[r], c.indptr[r + 1]
                    out[dst, c.indices[lo:hi]] = c.data[lo:hi]
            else:
                out[sel] = c[local]
        return out

    # -- disk-resident store (np.memmap-backed chunks) ---------------------

    def save_store(self, store_dir, y=None) -> str:
        """Write this container to an mmap-able on-disk store.

        Layout: ``meta.json`` plus one flat binary per array — ``X.bin``
        (dense, row-major ``(m, n)``) or ``data.bin``/``indices.bin``/
        ``indptr.bin`` (CSR over feature rows). Arrays are written chunk by
        chunk, so saving never needs the full matrix in RAM either.
        ``meta.json`` is written last and doubles as the build-complete
        marker. Pass ``y`` to store labels alongside (``y.bin``).
        """
        os.makedirs(store_dir, exist_ok=True)
        all_csr = all(isinstance(c, CsrChunk) for c in self.chunks)
        if all_csr:
            running = 0
            indptr_parts = [np.zeros((1,), np.int64)]
            with open(os.path.join(store_dir, "data.bin"), "wb") as fd, \
                    open(os.path.join(store_dir, "indices.bin"), "wb") as fi:
                for c in self.chunks:
                    np.asarray(c.data, self.dtype).tofile(fd)
                    np.asarray(c.indices, np.int32).tofile(fi)
                    indptr_parts.append(
                        np.asarray(c.indptr[1:], np.int64) + running)
                    running += c.nnz
            np.concatenate(indptr_parts).tofile(
                os.path.join(store_dir, "indptr.bin"))
            fmt = "csr"
        else:
            with open(os.path.join(store_dir, "X.bin"), "wb") as fx:
                for c in self.chunks:
                    dense = (c.to_dense(self.dtype) if isinstance(c, CsrChunk)
                             else np.asarray(c, self.dtype))
                    dense.tofile(fx)
            fmt = "dense"
        if y is not None:
            np.asarray(y, self.dtype).tofile(os.path.join(store_dir, "y.bin"))
        chunk_m = int(max(self.offsets[i + 1] - self.offsets[i]
                          for i in range(self.n_chunks)))
        meta = {"format": fmt, "m": self.m, "n": self.n,
                "dtype": self.dtype.name, "chunk_m": chunk_m,
                "has_y": y is not None}
        # integrity manifest computed from the bytes that actually landed on
        # disk; meta.json (written last) is still the build-complete marker
        meta["checksums"] = _store_grid_checksums(store_dir, meta)
        with open(os.path.join(store_dir, "meta.json"), "w") as fm:
            json.dump(meta, fm)
        return str(store_dir)

    @classmethod
    def from_store(cls, store_dir, chunk_m: Optional[int] = None,
                   **kw) -> "FeatureChunked":
        """Open an on-disk store with ``np.memmap``-backed chunks.

        Chunks are *views* into the memmaps, so nothing is read from disk
        until a chunk is actually streamed — host RSS tracks the touched
        pages (plus whatever the OS cares to cache), never the matrix.
        ``chunk_m`` overrides the stored chunking (views are free to
        re-slice). Labels saved alongside are exposed as ``.labels`` (or
        ``None``).

        Integrity: raises :class:`StoreMissingError` when the directory or
        a required file is absent, :class:`StoreCorruptError` when meta is
        unparseable or a file is shorter than meta implies. Stores carrying
        a checksum manifest additionally verify each store-grid chunk's
        crc32 lazily, on first touch (see :meth:`verify` to front-load it).
        """
        if not os.path.isdir(store_dir):
            raise StoreMissingError(f"no such store directory: {store_dir}")
        meta_path = _require_store_file(store_dir, "meta.json")
        try:
            with open(meta_path) as fm:
                meta = json.load(fm)
            m, n = int(meta["m"]), int(meta["n"])
            dtype = np.dtype(meta["dtype"])
            fmt = meta["format"]
        except (ValueError, KeyError, TypeError) as e:
            raise StoreCorruptError(
                f"unreadable store meta {meta_path}: {e}") from e
        chunk_m = int(chunk_m or meta["chunk_m"])
        if fmt == "csr":
            _require_store_file(store_dir, "indptr.bin", (m + 1) * 8)
            indptr = np.memmap(os.path.join(store_dir, "indptr.bin"),
                               dtype=np.int64, mode="r", shape=(m + 1,))
            nnz = int(_read_with_retry(lambda: indptr[-1],
                                       f"{store_dir}/indptr.bin"))
            _require_store_file(store_dir, "data.bin", nnz * dtype.itemsize)
            _require_store_file(store_dir, "indices.bin", nnz * 4)
            data = np.memmap(os.path.join(store_dir, "data.bin"),
                             dtype=dtype, mode="r")
            indices = np.memmap(os.path.join(store_dir, "indices.bin"),
                                dtype=np.int32, mode="r")
            fc = cls.from_csr((data, indices, indptr, (m, n)),
                              chunk_m=chunk_m, **kw)
            arrays = (data, indices, indptr)
        else:
            _require_store_file(store_dir, "X.bin", m * n * dtype.itemsize)
            X = np.memmap(os.path.join(store_dir, "X.bin"), dtype=dtype,
                          mode="r", shape=(m, n))
            fc = cls.from_dense(X, chunk_m=chunk_m, **kw)
            arrays = (X,)
        sums = meta.get("checksums")
        if sums and sums.get("algo") == "crc32":
            grid_cm = int(meta["chunk_m"])
            n_grid = -(-m // grid_cm)
            crcs = list(sums["chunks"])
            if len(crcs) != n_grid:
                raise StoreCorruptError(
                    f"store {store_dir}: manifest has {len(crcs)} chunk "
                    f"checksums, grid has {n_grid}")
            fc._store = {"dir": str(store_dir), "format": fmt,
                         "arrays": arrays, "chunk_m": grid_cm,
                         "crcs": crcs,
                         "verified": np.zeros((n_grid,), dtype=bool)}
        y_path = os.path.join(store_dir, "y.bin")
        if meta.get("has_y") and os.path.exists(y_path):
            raw = _read_with_retry(
                lambda: open(y_path, "rb").read(), y_path)
            if sums and "y" in sums and zlib.crc32(raw) != sums["y"]:
                raise StoreCorruptError(
                    f"checksum mismatch in {y_path}: labels are corrupt")
            fc.labels = np.frombuffer(raw, dtype=dtype).copy()
        else:
            fc.labels = None
        return fc

    @classmethod
    def from_libsvm_cached(cls, path, store_dir=None, chunk_m: int = 512,
                           dtype=np.float32, n_features: Optional[int] = None,
                           zero_based: bool = False, rebuild: bool = False,
                           **kw) -> tuple:
        """Libsvm text → on-disk CSR store (built once) → memmap container.

        Returns ``(FeatureChunked, y)``. The store is built in two streaming
        passes over the text (pass 1 counts nnz per feature row, pass 2
        scatters values into preallocated memmaps), transposing the
        sample-major text into the paper's feature-row layout with memory
        O(m + one line) — the dense ``(m, n)`` matrix never exists in host
        RAM. Re-opens the existing store on subsequent calls (it sits next
        to the text as ``<path>.store/`` unless ``store_dir`` is given);
        ``rebuild=True`` forces a rebuild. Gzip input works transparently.

        A store that fails to open (:class:`StoreError` — missing files,
        truncation, checksum mismatch) is rebuilt from the source text once;
        the error propagates only when the rebuild fails too.
        """
        from ..data.svm import iter_libsvm

        store_dir = str(store_dir or f"{path}.store")
        if rebuild or not os.path.exists(os.path.join(store_dir, "meta.json")):
            os.makedirs(store_dir, exist_ok=True)
            # pass 1: samples, labels, nnz per feature row
            counts = np.zeros((1024,), np.int64)
            labels = []
            for label, idx, _ in iter_libsvm(path, zero_based=zero_based):
                labels.append(label)
                if idx:
                    top = max(idx)
                    while top >= len(counts):
                        counts = np.concatenate([counts, np.zeros_like(counts)])
                    np.add.at(counts, idx, 1)
            n = len(labels)
            if n == 0:
                raise ValueError(f"no samples in {path}")
            seen_m = int(np.max(np.nonzero(counts)[0])) + 1 if counts.any() else 0
            m = int(n_features) if n_features else seen_m
            if seen_m > m:
                raise ValueError(
                    f"feature index {seen_m - 1} >= n_features={m}")
            counts = counts[:m]
            indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
            nnz = int(indptr[-1])
            dt = np.dtype(dtype)
            data = np.memmap(os.path.join(store_dir, "data.bin"), dtype=dt,
                             mode="w+", shape=(max(nnz, 1),))
            indices = np.memmap(os.path.join(store_dir, "indices.bin"),
                                dtype=np.int32, mode="w+",
                                shape=(max(nnz, 1),))
            # pass 2: scatter each sample's entries at the rows' fill fronts
            fill = indptr[:-1].copy()
            for col, (_, idx, vals) in enumerate(
                    iter_libsvm(path, zero_based=zero_based)):
                if not idx:
                    continue
                jj = np.asarray(idx, np.int64)
                pos = fill[jj]
                data[pos] = np.asarray(vals, dt)
                indices[pos] = col
                fill[jj] += 1
            data.flush()
            indices.flush()
            indptr.tofile(os.path.join(store_dir, "indptr.bin"))
            y = np.where(np.asarray(labels) > 0, 1.0, -1.0).astype(dt)
            y.tofile(os.path.join(store_dir, "y.bin"))
            meta = {"format": "csr", "m": m, "n": n, "dtype": dt.name,
                    "chunk_m": int(chunk_m), "has_y": True}
            meta["checksums"] = _store_grid_checksums(store_dir, meta)
            with open(os.path.join(store_dir, "meta.json"), "w") as fm:
                json.dump(meta, fm)
        try:
            fc = cls.from_store(store_dir, chunk_m=chunk_m, **kw)
            # eager verify: silent corruption must trigger the rebuild
            # fallback *here*, not a StoreCorruptError mid-path later
            fc.verify()
        except StoreError:
            if rebuild or not os.path.exists(path):
                raise  # fresh build already, or no source to rebuild from
            return cls.from_libsvm_cached(
                path, store_dir=store_dir, chunk_m=chunk_m, dtype=dtype,
                n_features=n_features, zero_based=zero_based, rebuild=True,
                **kw)
        return fc, fc.labels


# --------------------------------------------------------------------------
# per-chunk device kernels (jitted once per chunk shape / sparsity pattern)
# --------------------------------------------------------------------------
# These, plus the screen-sweep kernels in screen_stream.py, are the ONLY
# functions that ever see a chunk on device — the memory-shape property test
# walks exactly these jaxprs.

@jax.jit
def _chunk_mv(Xc, v):
    return dot(Xc, v)


@jax.jit
def _chunk_rmv(Xc, wc):
    # dense (rows, n).T @ (rows,) and BCOO both support this contraction;
    # for BCOO the vector-matrix form avoids materializing the transpose
    if isinstance(Xc, jnp.ndarray):
        return dot(Xc.T, wc)
    return dot(wc, Xc)


@jax.jit
def _chunk_sq(Xc):
    return jnp.sum(Xc * Xc, axis=1)


@jax.jit
def _chunk_csq(Xc):
    # transposed reduction: per-sample (column) partial of ||x_i||^2
    return jnp.sum(Xc * Xc, axis=0)
