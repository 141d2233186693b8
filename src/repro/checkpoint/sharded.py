"""Sharded (per-host) checkpoint extraction, writing and elastic restore.

Save path, two phases (so the trainer only blocks on the cheap one):

  1. ``extract_snapshot(state)`` — device→host copy of every *addressable*
     shard with ``replica_id == 0`` plus its global index. O(local bytes),
     synchronous, step-boundary cost. This is the transparent-checkpoint
     "freeze" moment, the analogue of CRIU's stop-and-copy. The copy itself
     is pipelined: ``copy_to_host_async`` is issued across *all* shards
     first, then a single gather pass materializes them — the device→host
     DMAs of different tensors overlap instead of serializing behind one
     blocking ``np.asarray`` per leaf. With ``on_device_quantize``, selected
     leaves (optimizer moments before an urgent save) are absmax-int8
     quantized *on device* first, so they cross the device→host link at 1/4
     width; the stored bytes are identical to a host-side quantize.
  2. ``write_snapshot(dir, snapshot)`` — encode + write shard container(s).
     Runs in the async writer thread (checkpoint/IO overlaps training).

Restore is pipelined too: tensors decode in parallel on the codec executor
(mmap reads, digest validation and decompression release the GIL) and each
tensor reassembles into a preallocated destination buffer — see
CheckpointReader. ``restore_to_template_streaming`` goes further for
device-destined restores: decode overlaps the host→device transfers, raw
single-chunk payloads stream from validated mmap views (page cache →
device, no intermediate host buffer), and int8-quantized payloads cross
the link at 1/4 width and widen on device — the restore mirror of the
on-device quantize below, and the heart of the fast-resume (MTTR) path.

Restore is **mesh-independent** ("elastic"): the manifest stores global shapes
and per-piece global indices, and ``restore_to_template`` re-slices saved
pieces into whatever sharding the *target* template carries. Saving on a
512-chip mesh and restoring on 256 chips (a lost pod) — or on one CPU device —
is the same code path. This generalizes the paper's "resume on a new instance"
to "resume on a different topology".

In a real multi-host deployment each process calls ``extract_snapshot`` /
``write_snapshot`` for its own shard file into the shared staging dir and
process 0 commits after a barrier (``jax.experimental.multihost_utils``); in
this single-process container process 0 owns every shard, same code path.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from concurrent.futures import Future
from concurrent.futures import wait as futures_wait
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import numpy as np

import jax

from . import chunkstore
from . import manifest as mf
from . import serialize as ser
from ..distributed import multihost
from ..distributed.sharding import addressable_shard_spans
from .device_delta import (DeltaBlocks, DeviceDeltaTracker, copy_to_host_async,
                           write_delta_blocks_piece)
from .ioutil import fsync_dir

Index = tuple[tuple[int, int], ...]

# leaves below this stored size batch into one executor task on restore —
# per-task overhead beats decode cost for scalar/counter leaves, and configs
# can carry hundreds of them
SMALL_LEAF_BYTES = 4096


@dataclass
class LeafPieces:
    """All locally-owned pieces of one logical tensor. A piece payload is a
    dense host ndarray, or a ``device_delta.DeltaBlocks`` when the
    fingerprint path pruned the device→host copy to the dirty blocks."""

    global_shape: tuple[int, ...]
    dtype: str                     # logical dtype (pre-quantization)
    pieces: list[tuple[Index, Any]]     # ndarray | DeltaBlocks
    is_scalar_py: bool = False     # python int/float leaf (restore casts back)
    py_type: str = ""
    prequant: str = ""             # "int8": pieces hold on-device-quantized data
    scale: float | None = None     # absmax scale when prequant


@dataclass
class Snapshot:
    """Host-side frozen training state, ready to be written."""

    step: int
    leaves: dict[str, LeafPieces]
    leaf_order: list[str]
    treedef_repr: str
    mesh: dict
    nbytes: int = 0
    # D2H accounting (the save-path win the ledger reports): bytes that
    # actually crossed the device→host link vs. bytes the fingerprint path
    # proved unchanged and never transferred. stall_s is the wall time the
    # trainer was blocked inside extract — the save's step-boundary cost.
    d2h_bytes: int = 0
    d2h_skipped: int = 0
    stall_s: float = 0.0
    # invoked by the store with the final manifest records once this
    # snapshot's checkpoint is durably committed (device-delta bookkeeping)
    on_committed: Callable[[list[dict]], None] | None = None


def _slices_to_index(slices, shape) -> Index:
    out = []
    for sl, dim in zip(slices, shape):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        out.append((int(start), int(stop)))
    return tuple(out)


def _stage_async(leaf) -> None:
    """Issue the device→host DMA for one array without blocking."""
    if leaf.is_fully_replicated:
        copy_to_host_async(leaf)
    else:
        for shard in leaf.addressable_shards:
            if shard.replica_id == 0:
                copy_to_host_async(shard.data)


def prestage(state, tracker: DeviceDeltaTracker | None = None):
    """Start device→host copies for every array leaf and return ``state``.

    The trainer hands this to the coordinator as the state supplier, so the
    moment a checkpoint decision is made the DMAs are already in flight —
    by the time ``extract_snapshot`` gathers, most bytes have landed.

    With a ``tracker`` (device-delta saves) the staging is double-buffered
    differently: fingerprint-eligible leaves dispatch their per-block digest
    + diff compute on device instead of a full-state DMA — only the dirty
    blocks will cross later, and the digest compute overlaps whatever the
    trainer does next (the gather of save N runs under step N+1's compute;
    every staged result is a fresh device buffer, so donation of the state
    into the next step can never alias it). Non-eligible leaves stage the
    ordinary way. Urgent saves never pass a tracker.
    """
    from ..core.ledger import span  # deferred: see chunkstore._retry
    with span("save.prestage"):
        if tracker is not None:
            for name, leaf in ser.flatten_state(state).items():
                if (not tracker.prestage_leaf(name, leaf)
                        and isinstance(leaf, jax.Array)):
                    _stage_async(leaf)
        else:
            for leaf in jax.tree_util.tree_leaves(state):
                if isinstance(leaf, jax.Array):
                    _stage_async(leaf)
    return state


def extract_snapshot(state, *, step: int, mesh_info: dict | None = None,
                     on_device_quantize: Callable[[str], bool] | None = None,
                     tracker: DeviceDeltaTracker | None = None,
                     ) -> Snapshot:
    """Freeze `state` to host memory; returns shard pieces per leaf.

    Three passes: (0) optionally absmax-int8-quantize selected leaves on
    device (``on_device_quantize(name)`` — urgent saves pass the optimizer-
    moment predicate, shrinking the device→host transfer 4x); (1) issue
    ``copy_to_host_async`` across every staged array so the DMAs overlap;
    (2) gather each shard into host memory — the only blocking pass.

    With a ``tracker`` (periodic delta saves), leaves whose previous-save
    fingerprints are device-resident take the dirty-block path instead:
    digests compare on device, only changed blocks are gathered to host
    (``DeltaBlocks`` pieces), and unchanged blocks never cross the link.
    ``on_device_quantize`` and ``tracker`` are mutually exclusive by
    construction — urgent saves bypass fingerprinting entirely.

    Spans: ``spoton.save.diff_wait`` around ``tracker.begin`` and each
    leaf's diff resolve, ``spoton.save.d2h`` around the gather pass.
    """
    from ..core.ledger import span  # deferred: see chunkstore._retry
    t_stall0 = time.perf_counter()
    named = ser.flatten_state(state)
    leaf_order = list(named)
    tracked: dict[str, Any] = {}
    commit_cb = None
    if tracker is not None and on_device_quantize is None:
        with span("save.diff_wait"):
            tracked, commit_cb = tracker.begin(named)
    prequant: dict[str, tuple[Any, Any]] = {}       # name -> (q_array, scale)
    if on_device_quantize is not None:
        from ..kernels.quantize import quantize_int8
        for name, leaf in named.items():
            if (isinstance(leaf, jax.Array) and leaf.ndim >= 1
                    and ser.is_float_dtype(leaf.dtype)
                    and on_device_quantize(name)):
                prequant[name] = quantize_int8(leaf)
    for name, leaf in named.items():                # phase 1: async staging
        if name in tracked:
            with span("save.diff_wait"):
                tracked[name].resolve()     # diff sync + dirty-block gather
            continue
        staged = prequant[name][0] if name in prequant else leaf
        if isinstance(staged, jax.Array):
            _stage_async(staged)
    leaves: dict[str, LeafPieces] = {}
    nbytes = 0
    d2h_bytes = 0
    d2h_skipped = 0
    with span("save.d2h"):
        for name, leaf in named.items():            # phase 2: gather
            if name in tracked:
                res = tracked[name].finish()
                if res is not None:
                    db, leaf_d2h, leaf_skip = res
                    leaves[name] = LeafPieces(
                        db.shape, db.dtype_name,
                        [(tuple((0, s) for s in db.shape), db)])
                    nbytes += db.nbytes
                    d2h_bytes += leaf_d2h
                    d2h_skipped += leaf_skip
                    continue
                # high-churn dense fallback: gathered below like any
                # other leaf
            is_scalar_py = (isinstance(leaf, (int, float, bool))
                            and not isinstance(leaf, np.generic))
            pq, scale = None, None
            if name in prequant:
                src, dev_scale = prequant[name]
                pq, scale = "int8", float(np.asarray(dev_scale))
            else:
                src = leaf
            if isinstance(src, jax.Array) and not src.is_fully_replicated:
                pieces = []
                for shard in src.addressable_shards:
                    if shard.replica_id != 0:
                        continue
                    arr = np.asarray(shard.data)
                    pieces.append(
                        (_slices_to_index(shard.index, src.shape), arr))
                    nbytes += arr.nbytes
                    d2h_bytes += arr.nbytes
                lp = LeafPieces(tuple(src.shape),
                                ser.dtype_to_name(leaf.dtype), pieces,
                                prequant=pq or "", scale=scale)
            else:
                arr = ser.to_host(src)
                nbytes += arr.nbytes
                d2h_bytes += arr.nbytes
                lp = LeafPieces(
                    tuple(arr.shape), ser.dtype_to_name(leaf.dtype if pq
                                                        else arr.dtype),
                    [(tuple((0, s) for s in arr.shape), arr)],
                    is_scalar_py=is_scalar_py, py_type=type(leaf).__name__,
                    prequant=pq or "", scale=scale,
                )
            leaves[name] = lp
    treedef = jax.tree_util.tree_structure(state)
    return Snapshot(step=step, leaves=leaves, leaf_order=leaf_order,
                    treedef_repr=str(treedef), mesh=mesh_info or {},
                    nbytes=nbytes, d2h_bytes=d2h_bytes,
                    d2h_skipped=d2h_skipped,
                    stall_s=time.perf_counter() - t_stall0,
                    on_committed=commit_cb)


def _piece_codec(name: str, lp: LeafPieces, arr: np.ndarray, *,
                 compress: bool, quantize_moments: bool) -> str:
    """Codec for one piece; a pre-quantized piece keeps its int8 half and
    only the compression half is policy-chosen (over the int8 payload)."""
    if lp.prequant:
        comp = ser.default_codec_for(name, arr, compress=compress,
                                     quantize_moments=False)
        return lp.prequant if comp == "raw" else f"{lp.prequant}+{comp}"
    return ser.default_codec_for(name, arr, compress=compress,
                                 quantize_moments=quantize_moments)


def write_snapshot(
    dirpath: str,
    snapshot: Snapshot,
    *,
    process_index: int = 0,
    compress: bool = True,
    quantize_moments: bool = False,
) -> list[dict]:
    """Write this process's shard container. Returns tensor records (+file).
    Each piece's encode runs under a ``spoton.save.encode`` span."""
    from ..core.ledger import span  # deferred: see chunkstore._retry
    pending = []
    for name, lp in snapshot.leaves.items():
        for pi, (index, arr) in enumerate(lp.pieces):
            codec = _piece_codec(name, lp, arr, compress=compress,
                                 quantize_moments=quantize_moments)
            with span("save.encode", step=snapshot.step):
                pending.append(ser.encode_tensor(
                    f"{name}#{pi}", arr, global_shape=lp.global_shape,
                    index=index, codec=codec,
                    prequant_scale=lp.scale if lp.prequant else None,
                    logical_dtype=lp.dtype if lp.prequant else None))
    fname = f"shard_p{process_index:03d}.spot"
    records = ser.write_shard_file(os.path.join(dirpath, fname), pending)
    out = []
    for rec in records:
        d = rec.to_json()
        d["file"] = fname
        out.append(d)
    return out


def _encode_job(step: int, fn, *args):
    """One encode job of a delta save, under a ``spoton.save.encode`` span
    on the codec worker that runs it."""
    from ..core.ledger import span  # deferred: see chunkstore._retry
    with span("save.encode", step=step):
        return fn(*args)


def _delta_encode_piece(pool, key, arr, codec, chunk_size, index, pin,
                        prequant_scale=None, dirty_dirs=None):
    """Worker-pool task: quantize one piece, chunk it into the pool.

    Hashing and compression consume memoryview windows over the staged (or
    quantized) array buffer — the piece is never re-materialized as bytes.
    """
    codec = ser.resolve_codec(codec)
    quant, comp = ser.split_codec(codec)
    if prequant_scale is not None:
        raw, scale = np.ascontiguousarray(arr), prequant_scale
    else:
        raw, scale = ser.quantize(arr, quant)
    nbytes = raw.nbytes
    refs, written = chunkstore.store_payload_chunks(
        pool, key, ser.array_bytes_view(raw), codec=codec, comp=comp,
        chunk_size=chunk_size, index=index, pin=pin, dirty_dirs=dirty_dirs)
    return codec, scale, refs, written, nbytes


def write_snapshot_delta(
    snapshot: Snapshot,
    pool: chunkstore.ChunkPool,
    *,
    compress: bool = True,
    quantize_moments: bool = False,
    chunk_size: int = chunkstore.DEFAULT_CHUNK_SIZE,
    index: chunkstore.DeltaIndex | None = None,
    pin=lambda h: None,
    executor=None,
) -> tuple[list[dict], int]:
    """Incremental write: every piece chunked into the shared pool.

    Encode/compress runs on the shared codec executor so serialization
    overlaps across tensors. Returns (manifest tensor records, bytes
    physically written) — unchanged chunks cost a hash + an mtime touch, so
    the second number is the actual churn, not the state size.

    Durability bar: every chunk a manifest references must be durable before
    the manifest commits. For a POSIX pool (``pool.durable_dirs``) that
    means the per-save dir-fsync barrier below; for a cache-tier pool
    (``backend.BackendChunkPool``, ``durable_dirs=False``) ``store_chunk``
    collects no dirty dirs — the pool pipelines backend uploads instead and
    the store's pre-commit ``flush_uploads`` barrier replaces the fsyncs.
    """
    ex = executor if executor is not None else chunkstore.codec_executor()
    jobs = []
    dirty_dirs: set[str] = set()    # fan-out dirs with new chunks this save
    for name, lp in snapshot.leaves.items():
        for pi, (idx, arr) in enumerate(lp.pieces):
            if isinstance(arr, DeltaBlocks):
                # fingerprint-pruned piece: only its dirty blocks reached
                # the host; clean blocks reuse the previous save's refs
                fut = ex.submit(_encode_job, snapshot.step,
                                write_delta_blocks_piece, pool, (name, pi),
                                arr, index, pin, dirty_dirs)
                jobs.append((name, pi, idx, lp, arr, fut))
                continue
            # snapshot pieces were frozen by to_host at extract time; this
            # normalizes scalars/0-d values, it does not alias live state
            arr = np.asarray(arr)  # spotlint: ignore[SPOT021]
            codec = _piece_codec(name, lp, arr, compress=compress,
                                 quantize_moments=quantize_moments)
            fut = ex.submit(_encode_job, snapshot.step, _delta_encode_piece,
                            pool, (name, pi), arr, codec, chunk_size, index,
                            pin, lp.scale if lp.prequant else None,
                            dirty_dirs)
            jobs.append((name, pi, idx, lp, arr, fut))
    try:
        results = [fut.result() for *_rest, fut in jobs]
    except BaseException:
        # quiesce before propagating: a straggler task must not call pin()
        # after the caller has already unpinned this save's chunks
        for *_rest, fut in jobs:
            fut.cancel()
        futures_wait([fut for *_rest, fut in jobs])
        raise
    if dirty_dirs:
        # one fsync per distinct dirty fan-out dir, overlapped on the
        # executor — every new chunk's rename is durable before the caller
        # commits a manifest that references it. The results must be
        # collected: an fsync that failed with a real IO error means a
        # referenced chunk's rename may not survive a crash, and committing
        # a manifest over it would claim durability the disk refused.
        sync_futs = [ex.submit(fsync_dir, d) for d in dirty_dirs]
        futures_wait(sync_futs)
        for sf in sync_futs:
            sf.result()
    records = []
    new_bytes = 0
    for (name, pi, idx, lp, arr, fut), res in zip(jobs, results):
        codec, scale, refs, written, raw_len = res
        new_bytes += written
        if isinstance(arr, DeltaBlocks):
            shape, dtype_name = arr.shape, arr.dtype_name
        else:
            shape = tuple(arr.shape)
            dtype_name = lp.dtype if lp.prequant else ser.dtype_to_name(arr.dtype)
        rec = ser.TensorRecord(
            name=f"{name}#{pi}", dtype=dtype_name,
            shape=shape, global_shape=lp.global_shape,
            index=idx, nbytes=sum(r.nbytes for r in refs), crc32=0,
            codec=codec, scale=scale)
        d = rec.to_json()
        d["chunks"] = [r.to_json() for r in refs]
        d["raw_nbytes"] = raw_len
        # optional shard->chunk-span map: the axis-0 row band each chunk
        # covers, so a restoring process can select exactly the chunks its
        # shards address (manifest.record_shard_spans documents the format)
        quant, _ = ser.split_codec(codec)
        if shape:
            row_bytes = (int(np.prod(shape[1:], dtype=np.int64))
                         * ser.stored_dtype(dtype_name, quant).itemsize)
            spans = mf.shard_span_map(shape, row_bytes,
                                      (r.raw_len for r in refs))
            if spans is not None:
                d["shard_spans"] = spans
        records.append(d)
    return records, new_bytes


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------

def _submit_leaf_jobs(
    ex: Any,
    names: Sequence[str],
    size_of: Callable[[str], int],
    run_one: Callable[[str], Any],
) -> tuple[dict[str, Callable[[], Any]], list[Future]]:
    """One decode job per leaf, coalescing sub-4KiB leaves into one task
    (per-task executor overhead beats decode cost for scalar/counter
    leaves, and configs can carry hundreds). Returns ({name: resolver},
    submitted futures) — resolvers block on and return that leaf's result;
    the futures list is for cancel/quiesce on failure."""
    small = [n for n in names if size_of(n) < SMALL_LEAF_BYTES]
    resolve: dict[str, Callable[[], Any]] = {}
    futs: list[Future] = []
    if len(small) >= 2:
        small_fut = ex.submit(
            lambda ns=tuple(small): {n: run_one(n) for n in ns})
        futs.append(small_fut)
        for n in small:
            resolve[n] = (lambda n=n: small_fut.result()[n])
    for n in names:
        if n not in resolve:
            fut = ex.submit(run_one, n)
            futs.append(fut)
            resolve[n] = fut.result
    return resolve, futs


class CheckpointReader:
    """Random access over a committed checkpoint's tensors.

    Reads both manifest formats: v1 records point into per-process shard
    container files inside the step dir; v2 (delta) records carry chunk
    references into the store's shared content-addressed pool.

    The data path is zero-copy where the formats allow: shard containers and
    pool chunks are mmap'd (one mapping per file, reused across tensors),
    crc validation runs on the mapped views, and each tensor decodes into a
    preallocated destination buffer instead of per-chunk
    ``frombuffer(...).copy()`` concatenation. ``read_many`` decodes whole
    tensors in parallel on the codec executor; ``read_slice`` parallelizes
    across one tensor's chunks."""

    def __init__(self, ckpt_dir: str, tensor_records: list[dict],
                 chunk_pool: chunkstore.ChunkPool | None = None):
        self.ckpt_dir = ckpt_dir
        self.chunk_pool = chunk_pool or chunkstore.ChunkPool(
            os.path.join(os.path.dirname(os.path.abspath(ckpt_dir)),
                         chunkstore.CHUNKS_DIRNAME))
        self._readers: dict[str, ser.ShardFileReader] = {}
        self._readers_lock = threading.Lock()
        # shard-aware restore accounting: chunks decoded vs proven skippable
        # by range-addressed reads, plus regions that had to fall back to the
        # piece-assembly path (read_slice) — the bench and tests read these
        self.region_stats = {"region_reads": 0, "chunks_decoded": 0,
                             "chunks_skipped": 0, "fallback_reads": 0}
        self._stats_lock = threading.Lock()
        # name -> list of (record, file)
        self.by_name: dict[str, list[dict]] = {}
        for rec in tensor_records:
            base = rec["name"].rsplit("#", 1)[0]
            self.by_name.setdefault(base, []).append(rec)

    def close(self) -> None:
        with self._readers_lock:
            readers, self._readers = list(self._readers.values()), {}
        for r in readers:
            r.close()

    def _reader(self, fname: str) -> ser.ShardFileReader:
        with self._readers_lock:
            if fname not in self._readers:
                self._readers[fname] = ser.ShardFileReader(
                    os.path.join(self.ckpt_dir, fname))
            return self._readers[fname]

    def _read_piece_into(self, rec: dict, out: np.ndarray | None,
                         *, parallel: bool = True) -> np.ndarray:
        """Decode one piece; fills ``out`` in place when it matches the
        stored payload (raw codec, same dtype/shape, contiguous) and returns
        it, else returns a freshly decoded array in the logical dtype."""
        quant, _comp = ser.split_codec(rec.get("codec", "raw"))
        if "chunks" in rec:
            pdtype = ser.stored_dtype(rec["dtype"], quant)
            if (out is not None and not quant and out.dtype == pdtype
                    and tuple(out.shape) == tuple(rec["shape"])
                    and out.flags.c_contiguous):
                dst = out
            else:
                dst = ser.alloc_payload(rec["dtype"], rec["shape"], quant)
            chunkstore.read_payload_into(
                self.chunk_pool, rec["chunks"], dst,
                executor=chunkstore.restore_executor() if parallel else None)
            return ser.finish_payload(dst, dtype_name=rec["dtype"],
                                      quant=quant, scale=rec.get("scale"))
        reader = self._reader(rec["file"])
        if out is not None and reader.read_into(rec["name"], out):
            return out
        return reader.read(rec["name"])

    def _read_piece(self, rec: dict) -> np.ndarray:
        return self._read_piece_into(rec, None)

    def global_shape(self, name: str) -> tuple[int, ...]:
        return tuple(self.by_name[name][0]["global_shape"])

    def dtype(self, name: str) -> np.dtype:
        return ser.name_to_dtype(self.by_name[name][0]["dtype"])

    def names(self) -> list[str]:
        return list(self.by_name)

    def read_slice(self, name: str, index: Index | None = None,
                   *, parallel: bool = True) -> np.ndarray:
        """Assemble an arbitrary global slice of `name` from saved pieces.

        ``parallel`` spreads chunk decode over the codec executor; callers
        already running *on* that executor (``read_many`` jobs) pass False —
        a job must never block on sub-jobs queued behind it.
        """
        gshape = self.global_shape(name)
        full = tuple((0, int(s)) for s in gshape)
        if index is None:
            index = full
        # single-piece fast path: the decoded piece IS the result — no
        # destination buffer, no assembly copy. Quantized pieces in
        # particular would otherwise materialize at logical width twice
        # (dequantized piece, then a copy into ``out``).
        if tuple(tuple(int(x) for x in p) for p in index) == full:
            rec = self.single_piece_record(name)
            if rec is not None:
                return self._read_piece_into(rec, None, parallel=parallel)
        out_shape = tuple(stop - start for start, stop in index)
        out = np.empty(out_shape, dtype=self.dtype(name))
        filled = 0
        for rec in self.by_name[name]:
            pidx = tuple(tuple(p) for p in rec["index"])
            # intersection of requested region and piece region
            inter = tuple((max(a0, b0), min(a1, b1)) for (a0, a1), (b0, b1) in zip(index, pidx))
            if any(lo >= hi for lo, hi in inter):
                continue
            n_inter = int(np.prod([hi - lo for lo, hi in inter]))
            if inter == pidx == tuple(index):
                # piece exactly covers the request: decode straight into out
                piece = self._read_piece_into(rec, out, parallel=parallel)
                if piece is not out:
                    out[...] = piece
                filled += n_inter
                continue
            piece = self._read_piece_into(rec, None, parallel=parallel)
            src = tuple(slice(lo - b0, hi - b0) for (lo, hi), (b0, _) in zip(inter, pidx))
            dst = tuple(slice(lo - a0, hi - a0) for (lo, hi), (a0, _) in zip(inter, index))
            out[dst] = piece[src]
            filled += n_inter
        if filled != int(np.prod(out_shape)):
            raise IOError(
                f"{name}: requested region not fully covered by saved pieces "
                f"({filled} of {int(np.prod(out_shape))} elements)")
        return out

    def stored_nbytes(self, name: str) -> int:
        """Stored (encoded) bytes across all of ``name``'s pieces."""
        return sum(int(r.get("nbytes", 0)) for r in self.by_name[name])

    def single_piece_record(self, name: str) -> dict | None:
        """The one record covering the whole tensor, or None when the tensor
        was saved as multiple shard pieces (streaming whole-tensor reads and
        device-side dequant need a single payload with a single scale)."""
        recs = self.by_name[name]
        if len(recs) != 1:
            return None
        rec = recs[0]
        full = tuple((0, int(s)) for s in rec["global_shape"])
        if tuple(tuple(int(x) for x in p) for p in rec["index"]) != full:
            return None
        return rec

    def read_region_streaming(self, name: str, region: Index,
                              *, parallel: bool = True) -> np.ndarray | None:
        """Range-addressed decode of one contiguous global region of ``name``
        — only the chunks whose bytes the region touches are opened, so a
        sharded restore reads O(shard), not O(tensor).

        The stored layout must allow it: a v2 single-full-piece record whose
        flat C-order payload makes the region one contiguous byte range
        (i.e. only axis 0 sub-sliced; trailing axes full). Chunk selection
        goes through the manifest's shard-span map when the record carries
        one (``manifest.record_shard_spans``), else through ``raw_len``
        prefix sums — both pick the same chunks. Returns the region in the
        logical dtype, or None when the layout cannot be range-addressed
        (v1 container records, multi-piece saves, trailing-axis slices);
        callers fall back to ``read_slice``, which is always correct.
        Bit-identical to slicing the full-leaf read: raw chunks decode into
        the exact destination window, and int8 dequantization multiplies
        elementwise with the tensor-global scale, so restoring a region
        equals restoring the tensor and slicing it."""
        rec = self.single_piece_record(name)
        if rec is None or "chunks" not in rec:
            return None
        shape = tuple(int(s) for s in rec["shape"])
        region = tuple((int(a), int(b)) for a, b in region)
        if len(region) != len(shape):
            return None
        if any(not 0 <= a <= b <= s for (a, b), s in zip(region, shape)):
            return None
        full = tuple((0, s) for s in shape)
        if region == full:
            return self._read_piece_into(rec, None, parallel=parallel)
        if any((a, b) != (0, s) for (a, b), s in zip(region[1:], shape[1:])):
            return None          # trailing-axis sub-slice: not flat-contiguous
        quant, _comp = ser.split_codec(rec.get("codec", "raw"))
        pdtype = ser.stored_dtype(rec["dtype"], quant)
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * pdtype.itemsize
        a, b = region[0]
        byte_lo, byte_hi = a * row_bytes, b * row_bytes
        refs = rec["chunks"]
        offs = mf.chunk_byte_offsets(rec)
        spans = mf.record_shard_spans(rec)
        if spans is not None:
            # chunks whose row band intersects [a, b)
            c0 = bisect.bisect_right([hi for _, hi in spans], a)
            c1 = bisect.bisect_left([lo for lo, _ in spans], b)
        else:
            c0 = bisect.bisect_right(offs, byte_lo) - 1
            c1 = bisect.bisect_left(offs, byte_hi)
        c0, c1 = max(c0, 0), min(c1, len(refs))
        if c1 <= c0:
            return None          # degenerate map/region: let read_slice decide
        out = np.empty(tuple(hi - lo for lo, hi in region), dtype=pdtype)
        decoded, skipped = chunkstore.read_payload_range_into(
            self.chunk_pool, refs[c0:c1], out,
            byte_lo=byte_lo, base_off=offs[c0],
            executor=chunkstore.restore_executor() if parallel else None)
        with self._stats_lock:
            st = self.region_stats
            st["region_reads"] += 1
            st["chunks_decoded"] += decoded
            st["chunks_skipped"] += skipped + (len(refs) - (c1 - c0))
        return ser.finish_payload(out, dtype_name=rec["dtype"], quant=quant,
                                  scale=rec.get("scale"))

    def read_region_for_restore(self, name: str, region: Index) -> np.ndarray:
        """One shard-region decode job on the RESTORE lane: range-addressed
        when the stored layout allows, ``read_slice`` fallback otherwise.
        Runs *on* the restore executor, so chunk work inside stays serial —
        a lane job must never block on sub-jobs queued behind it."""
        arr = self.read_region_streaming(name, region, parallel=False)
        if arr is not None:
            return arr
        with self._stats_lock:
            self.region_stats["fallback_reads"] += 1
        return self.read_slice(name, region, parallel=False)

    def read_payload(self, name: str, *, parallel: bool = True
                     ) -> tuple[np.ndarray, str, str, float | None]:
        """Stored (post-decompress, pre-dequantize) payload of a
        single-full-piece tensor: (payload, logical dtype name, quant,
        scale). An int8-coded record's payload comes back as int8 — the
        streaming restore ships it across the host→device link at 1/4 the
        logical width and widens it on device."""
        rec = self.single_piece_record(name)
        if rec is None:
            raise ValueError(f"{name}: not a single full-coverage piece")
        quant, _comp = ser.split_codec(rec.get("codec", "raw"))
        pdtype = ser.stored_dtype(rec["dtype"], quant)
        shape = tuple(rec["shape"])
        if "chunks" in rec:
            crefs = rec["chunks"]
            if len(crefs) == 1:
                ref = chunkstore.ChunkRef.from_json(crefs[0])
                if ref.comp in ("", "raw"):
                    # zero-copy: validated mmap view of the pool chunk —
                    # the device transfer copies straight from the page
                    # cache, no intermediate host buffer at all
                    # intentional escape: the view's lifetime is the
                    # returned array's (np.frombuffer holds the only
                    # reference); the pool chunk is immutable and
                    # committed, and device_put copies out of it
                    # before the restore returns
                    view = self.chunk_pool.read_view(ref)  # spotlint: ignore[SPOT020]
                    arr = np.frombuffer(view, dtype=pdtype).reshape(shape)
                    return arr, rec["dtype"], quant, rec.get("scale")
            dst = ser.alloc_payload(rec["dtype"], shape, quant)
            chunkstore.read_payload_into(
                self.chunk_pool, crefs, dst,
                executor=chunkstore.restore_executor() if parallel else None)
            return dst, rec["dtype"], quant, rec.get("scale")
        # intentional escape: lifetime transfers to the np.frombuffer
        # array; the backing reader mmap stays open until this
        # CheckpointReader is closed, after device transfer
        view = self._reader(rec["file"]).read_payload_view(rec["name"])  # spotlint: ignore[SPOT020]
        if view is not None:
            arr = np.frombuffer(view, dtype=pdtype).reshape(shape)
            return arr, rec["dtype"], quant, rec.get("scale")
        dst = ser.alloc_payload(rec["dtype"], shape, quant)
        if not self._reader(rec["file"]).read_payload_into(rec["name"], dst):
            raise IOError(f"{name}: container payload does not match its record")
        return dst, rec["dtype"], quant, rec.get("scale")

    def read_many(self, names: list[str]) -> dict[str, np.ndarray]:
        """Read whole tensors in parallel (one restore-lane job per leaf,
        sub-4KiB leaves coalesced — see ``_submit_leaf_jobs``; inside each
        job chunk decode is serial — no nested submission)."""
        resolve, futs = _submit_leaf_jobs(
            chunkstore.restore_executor(), names, self.stored_nbytes,
            lambda n: self.read_slice(n, None, parallel=False))
        try:
            return {n: resolve[n]() for n in names}
        except BaseException:
            for f in futs:
                f.cancel()
            futures_wait(futs)
            raise

    def validate(self) -> None:
        """Full-content crc validation of every piece (per-chunk for v2)."""
        for name, recs in self.by_name.items():
            for rec in recs:
                self._read_piece(rec)


def _idx_of_slices(slices, shape) -> Index:
    return _slices_to_index(slices, shape)


def _leaf_sharding(leaf):
    """The template leaf's device sharding, or None for a host leaf."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not hasattr(sharding, "device_set"):
        return None
    return sharding


def _check_template(reader: CheckpointReader, named: dict) -> None:
    for name, leaf in named.items():
        if name not in reader.by_name:
            raise KeyError(f"checkpoint missing leaf {name!r}; has {sorted(reader.by_name)[:8]}...")
        if hasattr(leaf, "shape") and reader.global_shape(name) != tuple(leaf.shape):
            raise ValueError(
                f"{name}: shape mismatch ckpt={reader.global_shape(name)} "
                f"vs template={tuple(leaf.shape)}")


def _host_leaf_value(name: str, leaf, host: dict):
    """Finalize one host-destined leaf from its decoded array (scalar cast
    back to its python type; arrays cast to the template dtype)."""
    if isinstance(leaf, (int, float, bool)) and not isinstance(leaf, np.generic):
        return type(leaf)(host[name].reshape(())[()])
    return host[name].astype(leaf.dtype, copy=False)


def restore_to_template(reader: CheckpointReader, template) -> Any:
    """Restore a pytree matching `template`'s structure, shapes and shardings.

    Template leaves may be jax.Arrays (their sharding is reproduced —
    elastic restore reads only the slices each device needs),
    jax.ShapeDtypeStruct with `.sharding`, numpy arrays, or python scalars.

    Host-destined leaves decode in parallel (``read_many``); device-sharded
    leaves decode per-device-slice with chunk-level parallelism inside each
    callback. Both paths are bit-identical to a serial restore — only the
    schedule differs. For restores that should land on device, see
    ``restore_to_template_streaming``, which additionally overlaps decode
    with the host→device transfers.
    """
    named = ser.flatten_state(template)
    treedef = jax.tree_util.tree_structure(template)
    _check_template(reader, named)
    host_names = [n for n, leaf in named.items() if _leaf_sharding(leaf) is None]
    host = reader.read_many(host_names)
    out = {}
    for name, leaf in named.items():
        if name in host:
            out[name] = _host_leaf_value(name, leaf, host)
            continue
        shape = tuple(leaf.shape)
        dtype = leaf.dtype

        def cb(idx, _name=name, _shape=shape, _dtype=dtype):
            region = _idx_of_slices(idx, _shape)
            return reader.read_slice(_name, region).astype(_dtype, copy=False)
        out[name] = jax.make_array_from_callback(shape, leaf.sharding, cb)
    return jax.tree_util.tree_unflatten(treedef, [out[n] for n in named])


def _whole_tensor_sharding(sharding, shape: tuple[int, ...]) -> bool:
    """True when every addressable device wants the full tensor (single
    device or fully replicated) — the whole-payload streaming fast path."""
    try:
        imap = sharding.devices_indices_map(shape)
    except Exception:
        return False
    full = tuple((0, s) for s in shape)
    return all(_slices_to_index(idx, shape) == full for idx in imap.values())


def restore_to_template_streaming(reader: CheckpointReader, template) -> Any:
    """Streaming disk→device restore: ``restore_to_template`` semantics with
    the read→decode→``jax.device_put`` stages pipelined.

    Every leaf's read/decode job is submitted to the scheduler's RESTORE
    lane up front (tiny leaves batched into one task, int8-quantized leaves
    queued first) — restore work jumps every queued periodic-save encode,
    and yielding periodic workers help it run (restore QoS); the main
    thread consumes completions and immediately issues the
    asynchronous host→device transfer — so disk IO, decompression and H2D
    DMA of different tensors overlap instead of serializing. int8-quantized
    payloads cross the link at stored (1/4) width and widen on device in a
    single batched dispatch (``kernels.quantize.dequantize_int8_many``)
    whose execution overlaps the remaining full-width decodes; sharded
    template leaves decode per-device-slice from prefetched regions;
    host-destined leaves (no device sharding on the template leaf) come out
    exactly as the serial path produces them. Bit-identical to
    ``restore_to_template`` — only the schedule differs.
    """
    from ..kernels.quantize import dequantize_int8_many

    named = ser.flatten_state(template)
    treedef = jax.tree_util.tree_structure(template)
    _check_template(reader, named)
    ex = chunkstore.restore_executor()
    all_futs: list = []

    # --- planning pass ----------------------------------------------------
    plans: dict[str, str] = {}
    regions: dict[str, dict[Index, Any]] = {}
    for name, leaf in named.items():
        sharding = _leaf_sharding(leaf)
        if sharding is None:
            plans[name] = "host"
        elif (_whole_tensor_sharding(sharding, tuple(leaf.shape))
                and reader.single_piece_record(name) is not None):
            rec = reader.single_piece_record(name)
            quant, _ = ser.split_codec(rec.get("codec", "raw"))
            plans[name] = "quantized" if quant == "int8" else "payload"
        else:
            plans[name] = "sharded"

    # --- submission pass: every leaf's decode work enters the executor.
    # Quantized payloads go first: they are the smallest bytes-on-disk per
    # logical byte, so their decode+H2D finishes early and the batched
    # on-device widen runs *under* the remaining full-width decodes.
    def _run_one(name: str):
        if plans[name] == "host":
            return reader.read_slice(name, None, parallel=False)
        return reader.read_payload(name, parallel=False)

    order = sorted((n for n, p in plans.items() if p != "sharded"),
                   key=lambda n: plans[n] != "quantized")
    resolve, job_futs = _submit_leaf_jobs(ex, order, reader.stored_nbytes,
                                          _run_one)
    all_futs.extend(job_futs)
    for name, leaf in named.items():
        if plans[name] != "sharded":
            continue
        # per-shard enqueue: decode jobs only for the regions some
        # *addressable* device of this process materializes — in a
        # multihost pod each process touches O(its shards) chunks, and the
        # range-addressed read inside skips every chunk outside the region
        per_region: dict[Index, Any] = {}
        for key in addressable_shard_spans(leaf.sharding, tuple(leaf.shape)):
            per_region[key] = ex.submit(reader.read_region_for_restore,
                                        name, key)
        regions[name] = per_region
        all_futs.extend(per_region.values())

    # --- consumption: transfers issue as decodes land ---------------------
    out = {}
    try:
        # quantized leaves first: 1/4-width H2D per payload as it lands,
        # then ONE batched widen/multiply/cast dispatch for all of them —
        # bit-identical to serialize.finish_payload
        qnames = [n for n in order if plans[n] == "quantized"]
        if qnames:
            payloads, q_scales, q_dtypes = [], [], []
            for name in qnames:
                payload, dtype_name, _quant, scale = resolve[name]()
                payloads.append(payload)
                q_scales.append(scale)
                q_dtypes.append(dtype_name)
            # one batched H2D for all quantized payloads (python-side
            # device_put overhead is per *call*, not per array)
            q_devs = jax.device_put(
                payloads, [named[n].sharding for n in qnames])
            for name, arr in zip(qnames, dequantize_int8_many(
                    q_devs, q_scales, q_dtypes)):
                if arr.dtype != np.dtype(named[name].dtype):
                    arr = arr.astype(named[name].dtype)
                out[name] = arr
        # full-width payloads: resolve in decode order, then one batched H2D
        # — per-call device_put python overhead holds the GIL the decode
        # threads still need, so fewer/larger transfer calls win
        pnames = [n for n in order if plans[n] == "payload"]
        if pnames:
            staged = []
            for name in pnames:
                payload, _dtype_name, _quant, _scale = resolve[name]()
                staged.append(payload.astype(named[name].dtype, copy=False))
            for name, arr in zip(pnames, jax.device_put(
                    staged, [named[n].sharding for n in pnames])):
                out[name] = arr
        for name, leaf in named.items():
            plan = plans[name]
            if plan in ("quantized", "payload"):
                continue
            if plan == "host":
                out[name] = _host_leaf_value(name, leaf, {name: resolve[name]()})
            else:
                shape = tuple(leaf.shape)
                dtype = leaf.dtype

                def cb(idx, _shape=shape, _dtype=dtype, _futs=regions[name]):
                    key = _idx_of_slices(idx, _shape)
                    return _futs[key].result().astype(_dtype, copy=False)
                out[name] = jax.make_array_from_callback(shape, leaf.sharding, cb)
    except BaseException:
        for f in all_futs:
            f.cancel()
        futures_wait(all_futs)
        raise
    # pod rendezvous: no participant takes its first post-restore step until
    # every participant has materialized its shards — multihost semantics
    # (jax.experimental.multihost_utils API), simulated in-process for CPU
    # CI via distributed.multihost.use_simulated_barrier. A lone process
    # with no barrier installed passes straight through.
    multihost.sync_global_devices("spoton:restore_streaming")
    return jax.tree_util.tree_unflatten(treedef, [out[n] for n in named])
