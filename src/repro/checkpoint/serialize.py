"""Tensor (de)serialization for Spot-on checkpoints.

A checkpoint shard file is a self-describing container:

    MAGIC | u32 header_len | header JSON (utf-8) | payload

The header lists every tensor stored in the file with its name (pytree key
path), dtype, local shape, global shape, the global index (slice) this piece
covers, byte offset/length into the payload, a crc32 checksum, and optional
codec ("zstd"/"zlib" per-tensor compression, "int8" absmax quantization for
optimizer moments).  Per-tensor compression keeps partial reads cheap: an
elastic restore that needs one tensor's bytes never decompresses the whole
file.  ``zstandard`` is an optional dependency: when it is not installed,
requested zstd codecs degrade to the stdlib ``zlib`` codec at encode time
(recorded as such in the header, so files stay self-describing), the default
codec policy compresses only payloads where zlib pays (integer/bool data —
on float tensors zlib's ~20 MB/s for a ~7% ratio would dominate checkpoint
time, so they stay raw), and reading a zstd-coded file raises a clear error
instead of an ImportError at import.

The encode path holds a **one-copy invariant**: a tensor's payload is
materialized on the host at most once (the staged array itself for raw
codecs, the int8 array for quantized ones). ``quantize`` returns a contiguous
*array*, not bytes, and everything downstream — chunking, hashing, crc,
compression, file writes — operates on ``memoryview`` windows over that
buffer. Decode is symmetric: ``ShardFileReader`` maps its container once and
decodes tensors from mmap slices straight into caller-preallocated
destination buffers (``read_into``).

bfloat16 (and other ml_dtypes extended types) round-trip via dtype-name lookup
rather than numpy's descr machinery, which cannot serialize custom dtypes.
"""

from __future__ import annotations

import io
import json
import os
import struct
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from ..faults import inject as faults
from .ioutil import array_bytes_view, mmap_view, release_view

try:  # optional: zstd beats zlib on ratio+speed, but zlib always exists
    import zstandard
    HAVE_ZSTD = True
except ImportError:  # pragma: no cover - environment-dependent
    zstandard = None
    HAVE_ZSTD = False

import jax
import jax.numpy as jnp
import ml_dtypes  # ships with jax

MAGIC = b"SPOTON1\n"
_U32 = struct.Struct("<I")

# dtype registry covering numpy natives + ml_dtypes extensions used by JAX.
_EXTENDED_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
    "float8_e5m2": ml_dtypes.float8_e5m2,
}


def dtype_to_name(dtype) -> str:
    return np.dtype(dtype).name


def name_to_dtype(name: str) -> np.dtype:
    if name in _EXTENDED_DTYPES:
        return np.dtype(_EXTENDED_DTYPES[name])
    return np.dtype(name)


@dataclass
class TensorRecord:
    """Metadata for one stored tensor piece."""

    name: str
    dtype: str                    # logical dtype (pre-quantization)
    shape: tuple[int, ...]        # local (stored piece) shape
    global_shape: tuple[int, ...]
    index: tuple[tuple[int, int], ...]  # [start, stop) per dim, global coords
    offset: int = 0
    nbytes: int = 0
    crc32: int = 0
    codec: str = "raw"            # raw | zstd | int8 | int8+zstd
    scale: float | None = None    # absmax scale for int8 codec

    def to_json(self) -> dict:
        d = {
            "name": self.name, "dtype": self.dtype, "shape": list(self.shape),
            "global_shape": list(self.global_shape),
            "index": [list(p) for p in self.index],
            "offset": self.offset, "nbytes": self.nbytes, "crc32": self.crc32,
            "codec": self.codec,
        }
        if self.scale is not None:
            d["scale"] = self.scale
        return d

    @staticmethod
    def from_json(d: dict) -> "TensorRecord":
        return TensorRecord(
            name=d["name"], dtype=d["dtype"], shape=tuple(d["shape"]),
            global_shape=tuple(d["global_shape"]),
            index=tuple(tuple(p) for p in d["index"]),
            offset=d["offset"], nbytes=d["nbytes"], crc32=d["crc32"],
            codec=d.get("codec", "raw"), scale=d.get("scale"),
        )


# ---------------------------------------------------------------------------
# pytree <-> named leaves
# ---------------------------------------------------------------------------

def _key_str(path) -> str:
    """Stable, filesystem-free name for a pytree key path."""
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:  # pragma: no cover - future key kinds
            parts.append(str(k))
    return "/".join(parts)


def flatten_state(tree) -> dict[str, Any]:
    """Flatten a pytree into {keypath: leaf}. Leaves may be jax/np arrays or scalars."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in leaves:
        name = _key_str(path)
        if name in out:
            raise ValueError(f"duplicate leaf name {name!r}")
        out[name] = leaf
    return out


def tree_structure_of(tree):
    return jax.tree_util.tree_structure(tree)


def unflatten_state(treedef, named: dict[str, Any], order: Sequence[str]):
    return jax.tree_util.tree_unflatten(treedef, [named[n] for n in order])


def to_host(leaf) -> np.ndarray:
    """Device/py leaf -> numpy array: the snapshot *freeze*.

    jax.Array leaves stay zero-copy views (np.asarray of an immutable
    buffer — on CPU backends not even a transfer). Caller-owned numpy leaves
    are **copied**: the encode path hashes and writes from memoryview windows
    over this buffer, so if it aliased live state a concurrent in-place
    mutation between digest and write would commit a chunk whose bytes match
    neither its content address nor its crc — an unrestorable checkpoint
    that was reported committed. The copy is the freeze the snapshot
    contract promises, and it is the save path's one materialization.
    """
    if isinstance(leaf, jax.Array):
        return np.asarray(leaf)
    if isinstance(leaf, np.ndarray):
        return leaf.copy()
    return np.asarray(leaf)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

def resolve_codec(codec: str) -> str:
    """Degrade zstd-suffixed codecs to zlib when zstandard is unavailable."""
    if codec.endswith("zstd") and not HAVE_ZSTD:
        return codec[:-len("zstd")] + "zlib"
    return codec


def split_codec(codec: str) -> tuple[str, str]:
    """Codec string -> (quantization, compression) halves.

    The delta write path applies the two halves at different granularities:
    quantization per tensor (the absmax scale is tensor-global), compression
    per chunk (so unchanged chunks can skip the compressor entirely).
    """
    quant = "int8" if codec.startswith("int8") else ""
    if codec.endswith("zstd"):
        comp = "zstd"
    elif codec.endswith("zlib"):
        comp = "zlib"
    else:
        comp = ""
    return quant, comp


def quantize(arr: np.ndarray, quant: str) -> tuple[np.ndarray, float | None]:
    """Tensor -> contiguous payload *array* (+ absmax scale for int8).

    Returns an array, not bytes: for the raw codec this is the input itself
    when already contiguous (zero-copy), so downstream hashing/compression
    can run on memoryview windows without a ``.tobytes()`` materialization.
    """
    if quant == "int8":
        # one float32 temporary per tensor: encode workers quantize several
        # moment tensors at once, and each extra full-size temporary is
        # host memory the save holds on top of its snapshot
        x = arr.astype(np.float32, copy=False)
        absmax = (np.maximum(np.max(x), -np.min(x)) if x.size
                  else np.float32(0.0))
        scale, inv = int8_scale_inv(absmax)
        # multiply-only elementwise step, float32 scalar arithmetic: this is
        # what keeps a host quantize bit-identical to the on-device kernel
        # (kernels/quantize) even under XLA's fast-math, which rewrites
        # division into reciprocal-multiply — identical payload bytes are what
        # let urgent (device-quantized) and periodic (host-quantized) saves of
        # the same state dedup to the same pool chunks
        y = x * inv
        np.round(y, out=y)
        np.clip(y, -127, 127, out=y)
        return y.astype(np.int8), float(scale)
    return np.ascontiguousarray(arr), None


def int8_scale_inv(absmax) -> tuple[np.float32, np.float32]:
    """absmax -> (scale, 1/scale), both float32, computed with numpy scalar
    ops. Every quantize implementation (host, jnp oracle, Pallas kernel)
    funnels its reduce result through this one function so the scalar
    rounding sequence — and therefore the stored bytes — cannot diverge."""
    absmax = np.float32(absmax)
    scale = absmax / np.float32(127.0) if absmax > 0 else np.float32(1.0)
    return scale, np.float32(1.0) / scale


def compress_bytes(buf, comp: str) -> bytes:
    """Compress a bytes-like (bytes or memoryview window) payload."""
    if comp == "zstd":
        return zstandard.ZstdCompressor(level=3).compress(buf)
    if comp == "zlib":
        return zlib.compress(buf, 3)
    return buf


def decompress_bytes(buf, comp: str) -> bytes:
    if comp == "zstd":
        if not HAVE_ZSTD:
            raise IOError(
                "payload was written with the zstd codec but the 'zstandard' "
                "package is not installed (pip install zstandard)")
        return zstandard.ZstdDecompressor().decompress(buf)
    if comp == "zlib":
        return zlib.decompress(buf)
    return buf


def stored_dtype(dtype_name: str, quant: str) -> np.dtype:
    """Dtype of the raw (pre-compression) payload on disk."""
    return np.dtype(np.int8) if quant == "int8" else name_to_dtype(dtype_name)


def alloc_payload(dtype_name: str, shape, quant: str) -> np.ndarray:
    """Preallocated destination for a tensor's raw payload — decode fills
    this in place (one mmap-slice copy per chunk, no concatenation)."""
    return np.empty(tuple(shape), dtype=stored_dtype(dtype_name, quant))


def finish_payload(dst: np.ndarray, *, dtype_name: str, quant: str,
                   scale: float | None) -> np.ndarray:
    """Filled payload array -> logical tensor (dequantize if needed).

    The dequantize multiplies in float32 with a float32 scale — the exact
    arithmetic of the device dequant kernel (kernels/quantize), so host- and
    device-restored tensors are bit-identical. A float32 target multiplies
    straight into the output dtype (one allocation); other targets need the
    float32 intermediate before the final cast, but never a second astype
    when the cast is a no-op.
    """
    if quant == "int8":
        target = name_to_dtype(dtype_name)
        s = np.float32(scale)
        if target == np.float32:
            return np.multiply(dst, s, dtype=np.float32)
        return (dst.astype(np.float32) * s).astype(target)
    return dst


def payload_to_array(raw, *, dtype_name: str, shape, quant: str,
                     scale: float | None) -> np.ndarray:
    """Decoded (decompressed) raw payload bytes -> tensor (copies)."""
    shape = tuple(shape)
    dst = np.frombuffer(raw, dtype=stored_dtype(dtype_name, quant)).reshape(shape)
    if quant != "int8":
        dst = dst.copy()        # frombuffer views are read-only
    return finish_payload(dst, dtype_name=dtype_name, quant=quant, scale=scale)


def _encode(arr: np.ndarray, codec: str):
    quant, comp = split_codec(codec)
    raw, scale = quantize(arr, quant)
    view = array_bytes_view(raw)
    if comp:
        return compress_bytes(view, comp), scale
    return view, scale          # zero-copy: raw codec payload is the array


def _decode(buf, rec: TensorRecord) -> np.ndarray:
    quant, comp = split_codec(rec.codec)
    try:
        raw = decompress_bytes(buf, comp) if comp else buf
    except IOError as e:
        raise IOError(f"tensor {rec.name!r}: {e}") from None
    return payload_to_array(raw, dtype_name=rec.dtype, shape=rec.shape,
                            quant=quant, scale=rec.scale)


# ---------------------------------------------------------------------------
# shard file writer / reader
# ---------------------------------------------------------------------------

@dataclass
class PendingTensor:
    record: TensorRecord
    payload: Any               # bytes or memoryview over the staged array


def encode_tensor(
    name: str,
    arr: np.ndarray,
    *,
    global_shape: tuple[int, ...] | None = None,
    index: tuple[tuple[int, int], ...] | None = None,
    codec: str = "raw",
    prequant_scale: float | None = None,
    logical_dtype: str | None = None,
) -> PendingTensor:
    """Encode one tensor piece.

    ``prequant_scale`` marks ``arr`` as an already-quantized int8 payload
    (produced on-device before the host copy): the quantize half of ``codec``
    is skipped, ``logical_dtype`` records the original dtype, and the on-disk
    bytes are identical to a host-side quantize of the same values.
    """
    # `arr` is snapshot-owned: to_host froze (copied) it at the snapshot
    # boundary, so this asarray is a no-op normalization, not an alias of
    # live training state
    arr = np.asarray(arr)  # spotlint: ignore[SPOT021]
    codec = resolve_codec(codec)
    gshape = tuple(global_shape if global_shape is not None else arr.shape)
    idx = tuple(index if index is not None else tuple((0, s) for s in arr.shape))
    if prequant_scale is not None:
        _quant, comp = split_codec(codec)
        view = array_bytes_view(np.ascontiguousarray(arr))
        payload = compress_bytes(view, comp) if comp else view
        scale = prequant_scale
        dtype_name = logical_dtype or dtype_to_name(arr.dtype)
    else:
        payload, scale = _encode(arr, codec)
        dtype_name = dtype_to_name(arr.dtype)
    rec = TensorRecord(
        name=name, dtype=dtype_name, shape=tuple(arr.shape),
        global_shape=gshape, index=idx, nbytes=len(payload),
        crc32=zlib.crc32(payload), codec=codec, scale=scale,
    )
    return PendingTensor(rec, payload)


def write_shard_file(path, tensors: Iterable[PendingTensor]) -> list[TensorRecord]:
    """Write a shard container; returns finalized records (offsets filled)."""
    tensors = list(tensors)
    offset = 0
    records = []
    for t in tensors:
        t.record.offset = offset
        offset += t.record.nbytes
        records.append(t.record)
    header = json.dumps({"tensors": [r.to_json() for r in records]}).encode()
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(_U32.pack(len(header)))
        f.write(header)
        for t in tensors:
            faults.write_bytes(f, t.payload, op="shard.write", path=str(path))
        f.flush()
        os.fsync(f.fileno())
    return records


class ShardFileReader:
    """Random access into a shard container; validates crc per read.

    The container is mapped once (``mmap``) and every tensor read slices the
    mapping — no per-tensor ``open``/``read`` syscalls, and raw-codec tensors
    copy straight from the page cache into the destination buffer. Falls back
    to one buffered read of the whole file where mmap is unavailable.
    """

    def __init__(self, path: "str | os.PathLike[str]") -> None:
        self.path = path
        self._buf: memoryview | None = mmap_view(str(path))
        if bytes(self._buf[:len(MAGIC)]) != MAGIC:
            magic = bytes(self._buf[:len(MAGIC)])
            release_view(self._buf)
            raise ValueError(f"{path}: bad magic {magic!r}")
        (hlen,) = _U32.unpack(self._buf[len(MAGIC):len(MAGIC) + 4])
        self._payload_start = len(MAGIC) + 4 + hlen
        header = json.loads(bytes(self._buf[len(MAGIC) + 4:self._payload_start]))
        self.records = {r["name"]: TensorRecord.from_json(r) for r in header["tensors"]}

    def close(self) -> None:
        if self._buf is not None:
            release_view(self._buf)
            self._buf = None

    def names(self) -> list[str]:
        return list(self.records)

    def _payload_view(self, rec: TensorRecord) -> memoryview:
        if self._buf is None:
            raise ValueError(f"{self.path}: reader is closed")
        start = self._payload_start + rec.offset
        buf = self._buf[start:start + rec.nbytes]
        if zlib.crc32(buf) != rec.crc32:
            raise IOError(f"{self.path}:{rec.name}: crc mismatch (corrupt shard)")
        return buf

    def read(self, name: str) -> np.ndarray:
        return _decode(self._payload_view(self.records[name]),
                       self.records[name])

    def read_into(self, name: str, dst: np.ndarray) -> bool:
        """Decode ``name`` directly into preallocated ``dst`` when its dtype
        and shape match the stored payload; returns False (caller falls back
        to ``read``) otherwise. One copy: mmap slice -> dst."""
        rec = self.records[name]
        quant, _comp = split_codec(rec.codec)
        if quant:
            return False
        return self.read_payload_into(name, dst)

    def read_payload_view(self, name: str) -> memoryview | None:
        """crc-validated zero-copy view of an *uncompressed* tensor's stored
        payload (mmap slice — a device transfer can copy straight from the
        page cache). None for compressed records; the view's lifetime is
        tied to this reader's mapping."""
        rec = self.records[name]
        _quant, comp = split_codec(rec.codec)
        if comp:
            return None
        return self._payload_view(rec)

    def read_payload_into(self, name: str, dst: np.ndarray) -> bool:
        """Fill ``dst`` with the *stored* payload (post-decompress,
        pre-dequantize): for an int8-coded tensor ``dst`` must be int8 —
        this is what lets the streaming restore ship quantized payloads to
        the device at 1/4 width and widen them there."""
        rec = self.records[name]
        quant, comp = split_codec(rec.codec)
        if (tuple(dst.shape) != tuple(rec.shape)
                or dst.dtype != stored_dtype(rec.dtype, quant)
                or not dst.flags.c_contiguous):
            return False
        buf = self._payload_view(rec)
        out = array_bytes_view(dst)
        if comp:
            out[:] = decompress_bytes(buf, comp)
        else:
            out[:] = buf
        return True

    def validate(self) -> None:
        for name in self.records:
            self.read(name)


def is_float_dtype(dtype) -> bool:
    """True for float dtypes *including* ml_dtypes extended types, which
    numpy's issubdtype does not classify as inexact."""
    dt = np.dtype(dtype)
    return (np.issubdtype(dt, np.floating)
            or any(dt == np.dtype(t) for t in _EXTENDED_DTYPES.values()))


def is_moment_name(name: str) -> bool:
    """True for optimizer-moment leaves (``opt_state/.../mu|nu``)."""
    wrapped = f"/{name}/"
    return "/mu/" in wrapped or "/nu/" in wrapped


def default_codec_for(name: str, arr: np.ndarray, *, compress: bool,
                      quantize_moments: bool) -> str:
    """Checkpoint codec policy.

    Optimizer moments (``opt_state/.../mu|nu``) may be int8-quantized — a
    beyond-paper optimization that shrinks termination checkpoints so they fit
    inside the eviction-notice window. Params and scalars stay exact.
    """
    # metadata-only inspection (dtype/nbytes/ndim); the buffer is not
    # retained, so aliasing is harmless here
    arr = np.asarray(arr)  # spotlint: ignore[SPOT021]
    return codec_for_meta(name, arr.dtype, arr.nbytes, ndim=arr.ndim,
                          compress=compress, quantize_moments=quantize_moments)


def codec_for_meta(name: str, dtype, nbytes: int, *, ndim: int,
                   compress: bool, quantize_moments: bool) -> str:
    """``default_codec_for`` from metadata alone — the device-delta tracker
    must know a leaf's codec *before* any bytes reach the host (the codec
    decides whether the fingerprint path applies at all), so the policy is
    keyed on (name, dtype, nbytes, ndim), never on array content."""
    dtype = np.dtype(dtype)
    if (quantize_moments and is_moment_name(name) and is_float_dtype(dtype)
            and ndim >= 1):
        return resolve_codec("int8+zstd") if compress else "int8"
    if compress and nbytes >= 1024:
        if HAVE_ZSTD:
            return "zstd"
        # zlib runs ~20 MB/s on float payloads for a ~7% ratio — it would
        # dominate checkpoint time for no real size win, so large float
        # tensors stay raw; integer/bool payloads still compress well
        if dtype.kind in "iub":
            return "zlib"
    return "raw"
