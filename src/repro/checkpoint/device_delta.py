"""Device-resident delta detection — the save path's change detector.

Before this module, every save staged the *full* model+optimizer state
device→host and then discovered on the host (sha1 per chunk, ``DeltaIndex``
memo) that most chunks hadn't changed — full-state D2H bandwidth and host
hashing spent on bytes the save then threw away. The tracker moves the
decision onto the device:

* after each committed save it keeps, per tensor piece, the uint32 per-block
  fingerprint array (``kernels.fingerprint``) **device-resident**, plus the
  pool ``ChunkRef`` of every block from that save's manifest;
* the next save recomputes fingerprints on device, compares them against the
  previous save's with one elementwise ``!=`` (only the tiny bool vector
  crosses the link), gathers **only the dirty blocks** into one device array
  and copies that to host;
* clean blocks reuse the previous save's chunk refs — they skip the D2H
  copy, the host sha1 *and* the encode entirely. Transferred (dirty) blocks
  still get the pool's sha1 content address, so the pool, manifests, gc and
  restore are untouched and restores stay bit-identical.

Fingerprint vs content address: the device digest (32 bits/block) decides
what to *skip*; the host sha1 (160 bits) remains the *addressing* and
integrity scheme for every byte that lands in the pool. A fingerprint
collision (2^-32 per changed block) would reuse a stale block in one
checkpoint — the inherent risk of any digest-delta scheme, bounded by the
shape/dtype/codec/chunk-size identity checks below, which also make the
*systematic* aliasing cases (reshaped or recast leaf with identical bytes)
take the full path rather than trusting the digest.

Consistency contract: a block is skipped **only** against refs recorded from
this process's last *committed* save (the commit callback fires after the
COMMITTED marker lands), so every reused ref is reachable from a committed
manifest — the pool gc never sweeps those. Cross-writer sweeps on a shared
store are age-gated (hours) and held off by the throttled ``touch`` below
(seconds); the periodic re-verify additionally re-checks clean refs against
the pool *while the device data is still available*, so a missing chunk
simply turns its block dirty instead of dangling.

Urgent (termination) saves bypass the tracker: the eviction-notice window
cannot wait for a fingerprint round-trip at a step boundary, so they take
the full prestage path (and may on-device-quantize, which the tracker never
mixes with — quantized payloads have tensor-global scales, so one changed
element dirties every block anyway).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
import functools

from ..kernels.fingerprint import (fingerprint_blocks, fingerprint_diff,
                                   n_blocks_of, supported_dtype)
from . import chunkstore
from . import codec_sched
from . import serialize as ser
from .ioutil import array_bytes_view

# leaves below this size take the dense path: the fingerprint dispatch +
# bookkeeping costs more than just copying them
MIN_FINGERPRINT_BYTES = 1 << 16


@dataclass
class DeltaBlocks:
    """Sparse payload of one tensor piece: dirty blocks on host, clean
    blocks as pool refs from the last committed save. Stands in for the
    dense ndarray inside ``Snapshot.leaves[...].pieces`` — the write path
    encodes the dirty rows and reuses the clean refs verbatim."""

    shape: tuple[int, ...]
    dtype_name: str            # payload dtype (tracked pieces never quantize)
    nbytes: int                # full raw payload bytes
    chunk_size: int
    n_blocks: int
    codec: str                 # resolved, compression-only codec
    dirty_ids: tuple[int, ...]
    dirty_data: np.ndarray | None   # (k, elems_per_block), payload dtype
    clean_refs: dict[int, chunkstore.ChunkRef] = field(default_factory=dict)

    def dirty_bytes(self) -> int:
        return sum(min(self.chunk_size, self.nbytes - ci * self.chunk_size)
                   for ci in self.dirty_ids)

    def dirty_view(self, j: int, ci: int) -> memoryview:
        """Raw-byte window of the j-th dirty row (block ``ci``), trimmed to
        the block's valid length (the last block may be partial)."""
        valid = min(self.chunk_size, self.nbytes - ci * self.chunk_size)
        return array_bytes_view(self.dirty_data[j])[:valid]


def stable_piece_key(name: str, index, global_shape,
                     dtype_name: str) -> tuple[str, int]:
    """Rescale-stable tracker key for one tensor piece.

    ``(leaf name, global flat byte offset of the piece's first element)`` —
    derived from the *global logical coordinates*, not from any local block
    or device numbering, so the same stored bytes map to the same key on
    every topology. That is what lets an elastic rescale remap surviving
    fingerprints instead of invalidating the tracker: a piece a process
    still addresses after the mesh re-plan keeps its entry under the
    identical key. Fully-replicated and whole-tensor pieces sit at offset 0,
    which keeps the common single-piece lookups trivial.
    """
    itemsize = ser.name_to_dtype(dtype_name).itemsize
    off_elems = 0
    stride = 1
    for (lo, _hi), dim in zip(reversed(tuple(index or ())),
                              reversed(tuple(global_shape or ()))):
        off_elems += int(lo) * stride
        stride *= int(dim)
    return name, off_elems * itemsize


@dataclass
class _Entry:
    """Per-piece state from the last committed save."""

    fp: Any                    # device uint32[n_blocks]
    refs: list[chunkstore.ChunkRef]
    codec: str
    shape: tuple[int, ...]
    dtype_name: str
    chunk_size: int
    verified_at: float         # monotonic ts of last pool check/touch
    # global byte span [offset, offset+length) this piece covers, plus the
    # whole leaf's logical byte size — the inputs of the rescale
    # addressability decision (see DeviceDeltaTracker.rescale)
    span: tuple[int, int] = (0, 0)
    total_nbytes: int = 0


@dataclass
class _Pending:
    """Fingerprint work issued at prestage, consumed by extract."""

    leaf: Any                  # the array the digests were computed over
    fp: Any                    # device uint32[n_blocks]
    diff: Any | None           # device bool[n_blocks] (when an entry existed)
    # the exact entry the diff was computed against: an async commit may
    # replace the entry between prestage and extract, and a diff against
    # the old fingerprints must never be paired with the new refs (a block
    # that reverted to its older value would silently reuse a stale chunk)
    ent: "_Entry | None" = None


@functools.partial(jax.jit, static_argnames=("epb", "n_blocks"))
def _gather_blocks(x, ids, epb, n_blocks):
    """One device gather of the dirty blocks: (k, epb) in x's dtype. The
    result is a fresh buffer, so a donated/overwritten ``x`` on the next
    train step can never alias the bytes being written out."""
    flat = x.reshape(-1)
    pad = n_blocks * epb - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n_blocks, epb)[ids]


def copy_to_host_async(arr) -> None:
    """Start ``arr``'s device→host copy without blocking. A backend without
    async transfer reports UNIMPLEMENTED, and the later gather then simply
    blocks. Any other error (a deleted buffer, a lost device) propagates."""
    try:
        arr.copy_to_host_async()
    except jax.errors.JaxRuntimeError as e:
        if not str(e).startswith("UNIMPLEMENTED"):
            raise


class _Staged:
    """One leaf's in-flight delta extraction (diff dispatched on device)."""

    def __init__(self, tracker: "DeviceDeltaTracker", name: str, leaf,
                 ent: _Entry, fp_new, diff_dev, codec: str):
        self.tracker = tracker
        self.name = name
        self.leaf = leaf
        self.ent = ent
        self.fp_new = fp_new
        self.diff_dev = diff_dev
        self.codec = codec
        self.dense = False         # high churn: gather wouldn't pay
        self._gathered = None
        self._dirty: np.ndarray | None = None

    def resolve(self) -> None:
        """Sync the tiny diff vector, re-verify clean refs if due, and issue
        the device gather + async D2H for the dirty blocks. Called in the
        extract's staging pass so gathers of different leaves overlap.

        When most blocks are dirty the block gather cannot beat a plain
        full-leaf stage (it's the same bytes plus an index pass), so the
        leaf falls back to the dense path — the fingerprints are still
        committed, so the next low-churn save deltas normally."""
        diff = np.asarray(self.diff_dev)
        dirty = set(np.nonzero(diff)[0].tolist())
        ent = self.ent
        if len(dirty) > self.tracker.dense_fallback_frac * len(ent.refs):
            self.dense = True
            with self.tracker._lock:
                self.tracker.stats["dense_fallbacks"] += 1
            copy_to_host_async(self.leaf)
            return
        now = time.monotonic()
        if now - ent.verified_at > self.tracker.touch_interval_s:
            # periodic liveness pass over the clean refs — while the device
            # data is still here, so a swept chunk just turns dirty. touch
            # keeps reused chunks' mtimes ahead of cross-writer age gates;
            # throttling it is what removes the per-chunk stat+utime
            # syscalls from the steady-state save, and the pass itself runs
            # batched on the scheduler's RESTORE lane (stat/utime release
            # the GIL) so a large leaf — thousands of blocks — doesn't
            # serialize two syscalls per chunk on the thread the trainer is
            # stalled on; the restore lane because the trainer is stalled
            # on this pass right now — it must not queue behind background
            # periodic encodes
            pool = self.tracker.pool
            refs = ent.refs

            def _verify(ids):
                return [ci for ci in ids
                        if not (pool.check(refs[ci].hash, refs[ci].nbytes)
                                and pool.touch(refs[ci].hash))]

            clean = [ci for ci in range(len(refs)) if ci not in dirty]
            batch = 512
            if len(clean) <= batch:
                dirty.update(_verify(clean))
            else:
                ex = chunkstore.restore_executor()
                for fut in [ex.submit(_verify, clean[i:i + batch])
                            for i in range(0, len(clean), batch)]:
                    dirty.update(fut.result())
            ent.verified_at = now
        self._dirty = np.asarray(sorted(dirty), dtype=np.int64)
        if self._dirty.size:
            epb = ent.chunk_size // np.dtype(self.leaf.dtype).itemsize
            # pad the id vector to a power-of-two bucket: the ids' shape is
            # part of the jit cache key, and churn drifts save-to-save, so
            # unbucketed gathers would recompile on the trainer thread for
            # every new dirty count. Padding repeats the last id; finish()
            # slices the duplicate rows off after the host copy.
            k = self._dirty.size
            k_pad = min(1 << (k - 1).bit_length() if k > 1 else 1,
                        len(ent.refs))
            ids = np.pad(self._dirty, (0, k_pad - k), mode="edge")
            self._gathered = _gather_blocks(self.leaf, jnp.asarray(ids),
                                            epb, len(ent.refs))
            copy_to_host_async(self._gathered)

    def finish(self) -> tuple[DeltaBlocks, int, int] | None:
        """Materialize: returns (piece payload, d2h bytes, skipped bytes),
        or None when ``resolve`` chose the dense fallback (the caller
        gathers the whole leaf as usual)."""
        if self.dense:
            return None
        ent = self.ent
        data = (np.asarray(self._gathered)[:self._dirty.size]
                if self._gathered is not None else None)
        dirty_ids = tuple(int(i) for i in self._dirty)
        nbytes = int(np.prod(ent.shape)) * ser.name_to_dtype(ent.dtype_name).itemsize
        db = DeltaBlocks(
            shape=ent.shape, dtype_name=ent.dtype_name, nbytes=nbytes,
            chunk_size=ent.chunk_size, n_blocks=len(ent.refs),
            codec=self.codec, dirty_ids=dirty_ids, dirty_data=data,
            clean_refs={ci: ent.refs[ci] for ci in range(len(ent.refs))
                        if ci not in set(dirty_ids)})
        self.tracker.stats["blocks_transferred"] += len(dirty_ids)
        self.tracker.stats["blocks_skipped"] += len(ent.refs) - len(dirty_ids)
        # honest link accounting: the bucket-padded gather rows crossed too,
        # plus the diff bool vector
        moved = (self._gathered.size * np.dtype(self.leaf.dtype).itemsize
                 if self._gathered is not None else 0)
        d2h = moved + len(ent.refs)
        return db, d2h, nbytes - db.dirty_bytes()


class DeviceDeltaTracker:
    """Owns the device-resident fingerprints and clean-block refs across
    saves. One tracker per (store, training process); thread-safe — the
    async writer commits on its own thread while the trainer stages the
    next save."""

    def __init__(self, pool: chunkstore.ChunkPool, *, chunk_size: int,
                 compress: bool = True, quantize_moments: bool = False,
                 min_bytes: int = MIN_FINGERPRINT_BYTES,
                 touch_interval_s: float = 30.0,
                 dense_fallback_frac: float = 0.5):
        self.pool = pool
        self.chunk_size = int(chunk_size)
        self.compress = compress
        self.quantize_moments = quantize_moments
        self.min_bytes = min_bytes
        self.touch_interval_s = touch_interval_s
        self.dense_fallback_frac = dense_fallback_frac
        self._lock = threading.Lock()
        self._entries: dict[tuple[str, int], _Entry] = {}
        self._pending: dict[str, _Pending] = {}
        # observability: decisions this process made, read by tests/benches.
        # ``fallbacks`` counts leaves whose previous entry was unusable;
        # ``dense_fallbacks`` leaves that were diffed but so dirty that
        # ``_Staged.resolve`` sent them down the dense path
        self.stats = {"tracked_saves": 0, "blocks_skipped": 0,
                      "blocks_transferred": 0, "fallbacks": 0,
                      "dense_fallbacks": 0, "rescale_events": 0,
                      "fp_kept": 0, "fp_dropped": 0}

    # -- eligibility --------------------------------------------------------

    def _codec_for(self, name: str, leaf) -> str | None:
        """Resolved codec when ``leaf`` can take the fingerprint path, else
        None (dense). Tracked pieces must be single-device jax arrays with a
        bitcastable dtype and a quantization-free codec — the int8 absmax
        scale is tensor-global, so quantized payloads re-encode wholesale
        whenever anything changed and block deltas buy nothing."""
        if self.chunk_size % 4:
            return None
        if not isinstance(leaf, jax.Array) or leaf.ndim < 1:
            return None
        try:
            if not (leaf.is_fully_replicated
                    or len(leaf.sharding.device_set) == 1):
                return None
        except Exception:
            return None
        dt = np.dtype(leaf.dtype)
        if not supported_dtype(dt) or leaf.nbytes < self.min_bytes:
            return None
        codec = ser.resolve_codec(ser.codec_for_meta(
            name, dt, leaf.nbytes, ndim=leaf.ndim, compress=self.compress,
            quantize_moments=self.quantize_moments))
        quant, _comp = ser.split_codec(codec)
        return None if quant else codec

    # -- prestage (trainer supplier) ---------------------------------------

    def prestage_leaf(self, name: str, leaf) -> bool:
        """Kick the fingerprint + diff compute for one leaf at checkpoint-
        decision time, so the device work overlaps the gap until extract.
        Returns False when the leaf is not fingerprint-eligible (caller
        falls back to the plain D2H prestage)."""
        codec = self._codec_for(name, leaf)
        if codec is None:
            return False
        with self._lock:
            ent = self._entries.get((name, 0))
        # fingerprint dispatch runs OUTSIDE the tracker lock: the same lock
        # serializes the async writer's commit bookkeeping, and a commit
        # callback queued behind a device kernel dispatch would stall the
        # writer thread (and anything waiting on it) for no correctness
        # gain — _Entry values are never mutated in place, and begin()'s
        # `pend.ent is ent` guard already discards a diff whose entry was
        # swapped by a commit that landed in between
        if ent is not None and self._usable(ent, leaf, codec):
            fp, diff = fingerprint_diff(leaf, ent.fp,
                                        block_bytes=self.chunk_size)
            copy_to_host_async(diff)
        else:
            fp, diff, ent = fingerprint_blocks(
                leaf, block_bytes=self.chunk_size), None, None
        with self._lock:
            self._pending[name] = _Pending(leaf=leaf, fp=fp, diff=diff,
                                           ent=ent)
        return True

    def _usable(self, ent: _Entry, leaf, codec: str) -> bool:
        """The previous save's entry may only suppress transfers when every
        identity the digest does NOT cover matches — shape, dtype, chunk
        size, codec, block count. A fingerprint match across any of these
        (the forced-collision case) must take the full path."""
        return (ent.shape == tuple(leaf.shape)
                and ent.dtype_name == ser.dtype_to_name(leaf.dtype)
                and ent.chunk_size == self.chunk_size
                and ent.codec == codec
                and len(ent.refs) == n_blocks_of(leaf.nbytes, self.chunk_size))

    # -- extract ------------------------------------------------------------

    def begin(self, named: dict[str, Any]) -> tuple[
            dict[str, _Staged], Callable[[list[dict]], None]]:
        """Start one save's delta extraction over the flattened state.

        Returns (staged, on_committed): ``staged`` maps leaf name to its
        in-flight dirty-block extraction (only leaves with a usable previous
        entry — everything else takes the dense path, while its fingerprint
        is still computed here so the *next* save can delta against it);
        ``on_committed`` must be invoked with the final manifest records
        after the checkpoint commits, and installs the new fingerprints +
        refs as the comparison point for the next save.
        """
        staged: dict[str, _Staged] = {}
        new_fps: dict[str, tuple[Any, str]] = {}   # name -> (fp_dev, codec)
        # decision pass under the lock (snapshot the entry + consume the
        # pending prestage for each leaf), device dispatch outside it: the
        # lock also serializes the async writer's commit bookkeeping, and
        # holding it across fingerprint kernel dispatches would queue the
        # writer thread behind device work. Safe because _Entry values are
        # never mutated in place and the `pend.ent is ent` identity check
        # below rejects any diff whose entry a concurrent commit swapped.
        plan: list[tuple] = []
        with self._lock:
            for name, leaf in named.items():
                codec = self._codec_for(name, leaf)
                if codec is None:
                    continue
                pend = self._pending.pop(name, None)
                ent = self._entries.get((name, 0))
                plan.append((name, leaf, codec, ent, pend))
            self._pending.clear()                  # saves never interleave
        fallbacks = 0
        for name, leaf, codec, ent, pend in plan:
            usable = ent is not None and self._usable(ent, leaf, codec)
            if pend is not None and pend.leaf is leaf:
                fp = pend.fp
                # the prestaged diff is only valid against the entry it
                # was computed from; if an async commit swapped the
                # entry in between, recompute below against the new one
                diff = pend.diff if pend.ent is ent else None
            elif usable:
                fp, diff = fingerprint_diff(leaf, ent.fp,
                                            block_bytes=self.chunk_size)
                copy_to_host_async(diff)
            else:
                fp, diff = fingerprint_blocks(
                    leaf, block_bytes=self.chunk_size), None
            new_fps[name] = (fp, codec)
            if not usable:
                if ent is not None:
                    fallbacks += 1
                continue                           # dense path this save
            if diff is None:
                diff = fp != ent.fp
                copy_to_host_async(diff)
            staged[name] = _Staged(self, name, leaf, ent, fp, diff, codec)
        with self._lock:
            self.stats["fallbacks"] += fallbacks
            if staged:
                self.stats["tracked_saves"] += 1
        return staged, self._make_commit_cb(new_fps)

    # -- commit -------------------------------------------------------------

    def _make_commit_cb(self, new_fps: dict[str, tuple[Any, str]]):
        def on_committed(records: list[dict]) -> None:
            by_name = {rec["name"]: rec for rec in records}
            with self._lock:
                for name, (fp, codec) in new_fps.items():
                    rec = by_name.get(f"{name}#0")
                    if rec is None or "chunks" not in rec:
                        continue
                    if rec.get("codec", "raw") != codec:
                        continue                   # policy changed mid-save
                    refs = [chunkstore.ChunkRef.from_json(c)
                            for c in rec["chunks"]]
                    if len(refs) != int(np.prod(fp.shape)):
                        continue
                    itemsize = ser.name_to_dtype(rec["dtype"]).itemsize
                    nbytes = int(np.prod(rec["shape"])) * itemsize
                    key = stable_piece_key(name, rec["index"],
                                           rec["global_shape"], rec["dtype"])
                    self._entries[key] = _Entry(
                        fp=fp, refs=refs, codec=codec,
                        shape=tuple(rec["shape"]), dtype_name=rec["dtype"],
                        chunk_size=self.chunk_size,
                        verified_at=time.monotonic(),
                        span=(key[1], nbytes),
                        total_nbytes=(int(np.prod(rec["global_shape"]))
                                      * itemsize))
        return on_committed

    # -- elastic topology changes -------------------------------------------

    def rescale(self, addressable: Callable[[str, int, int, int], bool]
                | None = None) -> dict[str, int]:
        """Remap tracker state across an elastic topology change.

        ``addressable(name, byte_lo, byte_hi, total_nbytes)`` answers
        whether this process still owns the piece's global byte span under
        the new mesh; None means fully-replicated data parallelism (the
        fleet's model), where every span stays addressable. Entries are
        keyed by global logical offset (``stable_piece_key``), so a
        surviving span keeps its device fingerprints — the next delta save
        still skips every clean block instead of re-transferring the world,
        which is what carries the D2H win through a rescale. Entries whose
        span the process no longer owns are dropped (their chunks remain in
        the pool; a save from their new owner re-seeds them). In-flight
        prestage work is discarded either way: it was computed against the
        old mesh's arrays.

        Returns ``{"kept": k, "dropped": d}`` and accumulates the same into
        ``stats``. For a full reset (restore onto unknown state) use
        ``invalidate``.
        """
        with self._lock:
            self._pending.clear()
            snapshot = [(key, ent.span, ent.total_nbytes)
                        for key, ent in self._entries.items()]
        # the predicate is caller code — never run it under the tracker lock
        drop = [key for key, (lo, ln), total in snapshot
                if addressable is not None
                and not addressable(key[0], lo, lo + ln, total)]
        with self._lock:
            dropped = 0
            for key in drop:
                if self._entries.pop(key, None) is not None:
                    dropped += 1
            kept = len(self._entries)
            self.stats["rescale_events"] += 1
            self.stats["fp_kept"] += kept
            self.stats["fp_dropped"] += dropped
        return {"kept": kept, "dropped": dropped}

    def invalidate(self) -> None:
        """Drop all device state; the next save takes the full path (and
        re-seeds the tracker). The blunt instrument — restores onto
        arbitrary state need it; elastic topology changes should call
        ``rescale`` instead, which keeps every still-addressable span."""
        with self._lock:
            self._entries.clear()
            self._pending.clear()


def write_delta_blocks_piece(pool: chunkstore.ChunkPool, key: tuple,
                             db: DeltaBlocks,
                             index: chunkstore.DeltaIndex | None,
                             pin: Callable[[str], None],
                             dirty_dirs: set | None):
    """Write-path worker for a sparse piece: encode+store the dirty blocks,
    reuse the clean refs verbatim (pinned so gc keeps them until the
    manifest commits). Mirrors ``chunkstore.store_payload_chunks`` for the
    dirty subset; the DeltaIndex memo is kept warm so a later tracker-less
    save of the same state still gets its raw-digest skips."""
    _quant, comp = ser.split_codec(db.codec)
    dirty_pos = {ci: j for j, ci in enumerate(db.dirty_ids)}
    refs: list[chunkstore.ChunkRef] = []
    written = 0
    for ci in range(db.n_blocks):
        j = dirty_pos.get(ci)
        if j is None:
            ref = db.clean_refs[ci]
            pin(ref.hash)
            refs.append(ref)
            continue
        # periodic-save encode: hand the worker to queued restore/urgent
        # jobs between blocks (chunk-granular preemption)
        codec_sched.maybe_yield()
        ref, n, rd = chunkstore.store_chunk(
            pool, db.dirty_view(j, ci), comp=comp, pin=pin,
            dirty_dirs=dirty_dirs)
        if index is not None:
            index.put((key, ci), rd, db.codec, ref)
        written += n
        refs.append(ref)
    return db.codec, None, refs, written, db.nbytes
