"""BitX lossless delta compression (paper §4.3).

Encode: align the floats of a fine-tuned tensor with its base tensor in
serialization order, bitcast both to unsigned words, XOR, split the delta into
byte planes (MSB plane ≈ all zeros within a family, Fig. 5), entropy-code each
plane with zstd. Decode is the exact inverse; the pipeline verifies bit-exact
reconstruction.

Array math goes through an :class:`ArrayBackend` selected once per store
(``get_backend("numpy"|"jax"|"auto")``), two implementations tested
bit-identical:

* ``numpy`` — host path for mmap'd safetensors ingestion (the
  evaluation/throughput path, mirroring the paper's C++ engine);
* ``jax`` — the Pallas kernels (``repro.kernels``), the TPU deployment path:
  same-width tensors are concatenated per bucket and transformed in ONE
  fused kernel launch (interpret mode on the CPU backend, so tests validate
  the kernel bodies there). ``auto`` picks jax only when a TPU is attached.

The per-codec encode/decode lanes live in the :mod:`repro.core.codecs`
registry; :class:`BitXCodec` remains as a thin back-compat facade over it.

Container format (``.bitx``): a 16-byte magic+version, a JSON header
describing per-tensor records, then concatenated zstd frames. Per-tensor
records keep the base tensor's content hash so retrieval can fetch the base
from the CAS pool (§4.4.4).
"""

from __future__ import annotations

import functools
import io
import json
import mmap
import os
import struct
import threading
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Protocol, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.codecs import CodecRuntime, EncodeInput, get_codec, raw_or_stored

__all__ = [
    "ArrayBackend",
    "BitXCodec",
    "TensorRecord",
    "BitXWriter",
    "BitXReader",
    "ENTROPY_BACKEND",
    "JaxBackend",
    "NumpyBackend",
    "TMP_SUFFIX",
    "get_backend",
    "xor_delta_planes_np",
    "merge_planes_xor_np",
    "byte_planes_np",
]

MAGIC = b"BITX0001"
DEFAULT_ZSTD_LEVEL = 3
# Entropy coder stamped into every container header. Frames of another coder
# are not interchangeable, so the reader refuses a container stamped otherwise.
ENTROPY_BACKEND = "zstd"

# Containers are written to ``<path>.part`` and atomically renamed into
# place, so a crash mid-write can never leave a torn file at a path the
# index might reference. Leftover ``.part`` files are crash debris; the
# store's fsck orphan scan recognizes the suffix and deletes them under
# repair (they are never referenced by the version graph).
TMP_SUFFIX = ".part"

# Device-resident base bit views (``JaxBackend``): the share of the device's
# reported memory limit they may hold, which leaves the rest to the largest
# flush, and the budget where the device reports no limit (the CPU backend).
_RESIDENT_SHARE = 0.5
_RESIDENT_FALLBACK_BYTES = 1 << 30


@functools.lru_cache(maxsize=None)
def _resident_budget_bytes() -> int:
    """Bytes of base bit views ``JaxBackend`` keeps on the default device."""
    import jax
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return int(limit * _RESIDENT_SHARE) if limit else _RESIDENT_FALLBACK_BYTES


def _bit_view_np(arr: np.ndarray) -> np.ndarray:
    """View a numpy array as unsigned words of the same width (no copy)."""
    if arr.dtype.kind == "u":
        return arr
    if arr.dtype.kind in ("f", "i"):
        return arr.view(f"<u{arr.dtype.itemsize}")
    raise ValueError(f"unsupported dtype {arr.dtype}")


# ---------------------------------------------------------------------------
# Host (numpy) transform implementations — the reference semantics every
# ArrayBackend must match bit for bit.
# ---------------------------------------------------------------------------

def _xor_delta_planes_host(base: np.ndarray, ft: np.ndarray) -> List[np.ndarray]:
    """XOR bit views and split into byte planes (MSB first). The plane split
    is a strided view of the little-endian byte buffer, so the whole encode
    is two passes over memory (XOR, then per-plane copy)."""
    a = _bit_view_np(np.ascontiguousarray(base)).reshape(-1)
    b = _bit_view_np(np.ascontiguousarray(ft)).reshape(-1)
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape, a.dtype, b.dtype)
    delta = np.bitwise_xor(a, b)
    nb = delta.dtype.itemsize
    raw = delta.view(np.uint8).reshape(-1, nb)
    # little-endian: byte column nb-1 is the MSB
    return [np.ascontiguousarray(raw[:, nb - 1 - i]) for i in range(nb)]


def _byte_planes_host(x: np.ndarray) -> List[np.ndarray]:
    """MSB-first byte planes of ``x``'s bit view (the ZipNN split)."""
    v = _bit_view_np(np.ascontiguousarray(x)).reshape(-1)
    nb = v.dtype.itemsize
    raw = v.view(np.uint8).reshape(-1, nb)
    return [np.ascontiguousarray(raw[:, nb - 1 - i]) for i in range(nb)]


def _merge_planes_xor_host(planes: Sequence[np.ndarray], base: np.ndarray) -> np.ndarray:
    """Inverse of :func:`_xor_delta_planes_host`; returns the ft bit view
    shaped like ``base``."""
    a = _bit_view_np(np.ascontiguousarray(base))
    nb = a.dtype.itemsize
    assert len(planes) == nb
    n = a.size
    raw = np.empty((n, nb), np.uint8)
    for i, p in enumerate(planes):
        raw[:, nb - 1 - i] = p
    delta = raw.reshape(-1).view(a.dtype.str)
    return np.bitwise_xor(delta, a.reshape(-1)).reshape(a.shape)


def _merge_planes_host(planes: Sequence[np.ndarray], dtype_np, shape) -> np.ndarray:
    """Inverse of :func:`_byte_planes_host`; returns an array of ``dtype_np``
    (the ZipNN merge)."""
    nb = np.dtype(dtype_np).itemsize
    assert len(planes) == nb
    n = int(np.prod(shape)) if len(shape) else 1
    raw = np.empty((n, nb), np.uint8)
    for i, p in enumerate(planes):
        raw[:, nb - 1 - i] = p
    return raw.reshape(-1).view(np.dtype(dtype_np).str).reshape(shape)


# ---------------------------------------------------------------------------
# ArrayBackend: the one dispatch point for the pipeline's array math.
# ---------------------------------------------------------------------------

class ArrayBackend(Protocol):
    """Array-transform provider selected once at ``ZLLMStore`` construction.

    Single-tensor ops are the reference semantics; the ``*_batch`` variants
    take many tensors at once and MUST produce per-tensor results identical
    to mapping the single op — backends exploit that freedom to concatenate
    same-width tensors and run one fused kernel launch per bucket. The
    transforms are elementwise in the bit view, so batching can never change
    the emitted bytes.

    An item of ``xor_delta_planes_batch`` is ``(base, ft)`` or
    ``(base_loader, ft, base_hash, family)``: a keyed item names its base by
    content hash, and the base model it belongs to, and loads it only if
    the backend does not hold it already (``release_resident`` drops what
    it holds).
    """

    name: str
    supports_batching: bool

    def xor_delta_planes(self, base: np.ndarray, ft: np.ndarray) -> List[np.ndarray]: ...
    def byte_planes(self, x: np.ndarray) -> List[np.ndarray]: ...
    def merge_planes_xor(self, planes: Sequence[np.ndarray], base: np.ndarray) -> np.ndarray: ...
    def merge_planes(self, planes: Sequence[np.ndarray], dtype_np, shape) -> np.ndarray: ...
    def xor_delta_planes_batch(self, pairs: Sequence[Tuple]) -> List[List[np.ndarray]]: ...
    def byte_planes_batch(self, xs: Sequence[np.ndarray]) -> List[List[np.ndarray]]: ...
    def merge_planes_xor_batch(self, items: Sequence[Tuple[Sequence[np.ndarray], np.ndarray]]) -> List[np.ndarray]: ...
    def merge_planes_batch(self, items: Sequence[Tuple[Sequence[np.ndarray], np.dtype, Tuple[int, ...]]]) -> List[np.ndarray]: ...
    def path_counts(self) -> Dict[str, int]: ...
    def release_resident(self, hashes: Iterable[str]) -> None: ...


def _load_base(base) -> np.ndarray:
    """A batch item's base: the array itself, or what its loader returns."""
    return base() if callable(base) else base


class _PathCounts:
    """Tensors and bytes each backend sent through the device kernels and
    through the host path, since it was created (process-wide:
    ``get_backend`` shares one instance per spec), and the device-resident
    bases: base bytes served from the device (``resident_hit_bytes``) and
    put there (``resident_miss_bytes``), bytes held now, entries evicted.
    Both backends report the same keys; the host path holds nothing."""

    def __init__(self):
        self._counts_lock = threading.Lock()
        self._counts = {"device_tensors": 0, "device_bytes": 0,
                        "host_tensors": 0, "host_bytes": 0,
                        "resident_hit_bytes": 0, "resident_miss_bytes": 0,
                        "resident_bytes": 0, "resident_evictions": 0}

    def _count(self, path: str, nbytes: Sequence[int]) -> None:
        with self._counts_lock:
            self._counts[path + "_tensors"] += len(nbytes)
            self._counts[path + "_bytes"] += int(sum(nbytes))

    def path_counts(self) -> Dict[str, int]:
        with self._counts_lock:
            return dict(self._counts)


class NumpyBackend(_PathCounts):
    """Host path: strided-view plane splits on the ingest thread(s). Batched
    entry points degenerate to a loop — numpy gains nothing from fusion, and
    the pipeline only engages its batching stage for backends that declare
    ``supports_batching``. Every tensor counts as host path."""

    name = "numpy"
    supports_batching = False

    def xor_delta_planes(self, base, ft):
        self._count("host", [np.asarray(ft).nbytes])
        return _xor_delta_planes_host(base, ft)

    def byte_planes(self, x):
        self._count("host", [np.asarray(x).nbytes])
        return _byte_planes_host(x)

    def merge_planes_xor(self, planes, base):
        self._count("host", [np.asarray(base).nbytes])
        return _merge_planes_xor_host(planes, base)

    def merge_planes(self, planes, dtype_np, shape):
        self._count("host", [np.dtype(dtype_np).itemsize * int(np.prod(shape))])
        return _merge_planes_host(planes, dtype_np, shape)

    def xor_delta_planes_batch(self, pairs):
        return [self.xor_delta_planes(_load_base(item[0]), item[1])
                for item in pairs]

    def byte_planes_batch(self, xs):
        return [self.byte_planes(x) for x in xs]

    def merge_planes_xor_batch(self, items):
        return [self.merge_planes_xor(p, b) for p, b in items]

    def merge_planes_batch(self, items):
        return [self.merge_planes(p, d, s) for p, d, s in items]

    def release_resident(self, hashes):
        """The host path holds no base: nothing to release."""


class JaxBackend(_PathCounts):
    """Device path over the Pallas kernels (``repro.kernels.ops``).

    Inputs are converted to their unsigned bit views host-side (so int8 and
    bool-free integer tensors work without kernel-side dtype plumbing). The
    XOR split runs one program per tensor shape: each fine-tune goes to the
    device straight from its source view, against its base. The other
    transforms run once per same-width bucket: a batch of N same-dtype
    tensors is concatenated flat and transformed in a single launch, and
    per-tensor planes are sliced back out — bit-identical to the per-tensor
    host path because the transforms are elementwise.

    A keyed encode item's base (``(base_loader, ft, base_hash, family)``)
    stays on the device after its first use, keyed by its content hash and
    bit-view dtype (so stale bytes can never be served); an unkeyed base is
    sent for its one program and not held. Past
    :func:`_resident_budget_bytes` a miss evicts the least recently used
    bases of other families. A family never evicts its own: where its base
    is larger than the budget, the tensors it put there first stay and the
    rest are sent for each use, so a fine-tune that reads the base in the
    same order every time still hits the part held. :meth:`release_resident`
    drops held bases.

    On the CPU backend the kernels execute in interpret mode
    (`ops._interpret`), which is how the equivalence tests validate the
    kernel bodies. 8-byte words take the host implementation unless jax runs
    with x64 enabled (jax would silently truncate uint64 otherwise);
    :meth:`path_counts` reports how many tensors and bytes took each path.
    """

    name = "jax"
    supports_batching = True

    def __init__(self, use_pallas: bool = True):
        super().__init__()
        self.use_pallas = use_pallas
        self._ops_mod = None
        # (base hash, bit-view dtype) -> (device bit view, family), least
        # recent first, and the bytes each family holds; guarded by the
        # counts lock, as their counters are
        self._resident: "OrderedDict[Tuple[str, str], Tuple[object, object]]" = OrderedDict()
        self._family_bytes: Dict[object, int] = {}

    def _ops(self):
        # imported on first use: the host-only store (and the entropy
        # worker processes) never load jax
        if self._ops_mod is None:
            from repro.kernels import ops as ops_mod
            self._ops_mod = ops_mod
        return self._ops_mod

    def _device_ok(self, dtype: np.dtype) -> bool:
        """uint64 needs jax x64; without it jnp.asarray silently truncates."""
        if np.dtype(dtype).itemsize < 8:
            return True
        import jax
        return bool(jax.config.jax_enable_x64)

    # -- single-tensor ops (reference semantics) -----------------------------
    def xor_delta_planes(self, base, ft):
        return self.xor_delta_planes_batch([(base, ft)])[0]

    def byte_planes(self, x):
        return self.byte_planes_batch([x])[0]

    def merge_planes_xor(self, planes, base):
        return self.merge_planes_xor_batch([(planes, base)])[0]

    def merge_planes(self, planes, dtype_np, shape):
        return self.merge_planes_batch([(planes, dtype_np, shape)])[0]

    # -- batched ops: one kernel launch per same-width bucket ----------------
    def _buckets(self, dtypes: Sequence[np.dtype]) -> Dict[str, List[int]]:
        groups: Dict[str, List[int]] = {}
        for i, d in enumerate(dtypes):
            groups.setdefault(np.dtype(d).str, []).append(i)
        return groups

    # -- device-resident bases ----------------------------------------------
    def _device_base(self, item, ft: np.ndarray):
        """The base for encode item ``item``'s one program. A keyed item's
        is the device view held, else it is loaded and put on the device,
        held where :meth:`_admit` lets it; an unkeyed item's is its bit
        view, which the program sends."""
        key = None
        if len(item) > 2:
            key = (item[2], ft.dtype.str)
            with self._counts_lock:
                held = self._resident.get(key)
                if held is not None:
                    self._resident.move_to_end(key)
                    self._counts["resident_hit_bytes"] += ft.nbytes
                    return held[0]
        a = _bit_view_np(np.ascontiguousarray(_load_base(item[0]))).reshape(-1)
        assert a.shape == ft.shape and a.dtype == ft.dtype, \
            (a.shape, ft.shape, a.dtype, ft.dtype)
        if key is None:
            return a
        import jax
        with obs.span("zllm.array.base_put", bytes=a.nbytes):
            arr = jax.device_put(a, may_alias=False)
        self._admit(key, item[3], arr)
        return arr

    def _admit(self, key: Tuple[str, str], family, arr) -> None:
        """Count a miss and hold ``arr`` if its family's bases fit the
        budget, evicting other families' least recently used ones."""
        budget = _resident_budget_bytes()
        with self._counts_lock:
            self._counts["resident_miss_bytes"] += arr.nbytes
            own = self._family_bytes.get(family, 0)
            if key in self._resident or own + arr.nbytes > budget:
                return
            for old in [k for k, (_, f) in self._resident.items() if f != family]:
                if self._counts["resident_bytes"] + arr.nbytes <= budget:
                    break
                self._drop(old)
                self._counts["resident_evictions"] += 1
            self._resident[key] = (arr, family)
            self._family_bytes[family] = own + arr.nbytes
            self._counts["resident_bytes"] += arr.nbytes

    def _drop(self, key: Tuple[str, str]) -> None:
        arr, family = self._resident.pop(key)
        self._counts["resident_bytes"] -= arr.nbytes
        self._family_bytes[family] -= arr.nbytes
        if not self._family_bytes[family]:
            del self._family_bytes[family]

    def release_resident(self, hashes: Iterable[str]) -> None:
        """Drop the resident base views of ``hashes``; a device array still
        in use by a running encode lives until it ends."""
        hashes = set(hashes)
        with self._counts_lock:
            for key in [k for k in self._resident if k[0] in hashes]:
                self._drop(key)

    # Each batch call is one ``zllm.array.encode`` or ``zllm.array.decode``
    # span. An encode is a ``zllm.array.base_put`` for each keyed base not
    # held, then one ``zllm.array.device`` for all its programs (until their
    # results are numpy: host-to-device, the programs, device-to-host). A
    # bucket of the other transforms is a ``zllm.array.concat`` (the host
    # copies into one buffer), a ``zllm.array.device`` and a
    # ``zllm.array.slice`` (the per-tensor copies).
    def xor_delta_planes_batch(self, pairs):
        out: List[Optional[List[np.ndarray]]] = [None] * len(pairs)
        with obs.span("zllm.array.encode") as sp:
            fts = [_bit_view_np(np.ascontiguousarray(item[1])).reshape(-1)
                   for item in pairs]
            sp.set(bytes=sum(b.nbytes for b in fts))
            dev = [i for i, b in enumerate(fts) if self._device_ok(b.dtype)]
            host = [i for i, b in enumerate(fts) if not self._device_ok(b.dtype)]
            self._count("host", [fts[i].nbytes for i in host])
            for i in host:
                base = _bit_view_np(np.ascontiguousarray(_load_base(pairs[i][0])))
                out[i] = _xor_delta_planes_host(base.reshape(-1), fts[i])
            if not dev:
                return out
            self._count("device", [fts[i].nbytes for i in dev])
            bases = [self._device_base(pairs[i], fts[i]) for i in dev]
            encode = self._ops().bitx_encode_planes
            with obs.span("zllm.array.device", bytes=sum(fts[i].nbytes for i in dev)):
                # all dispatched before any result is read, so the transfers
                # of one tensor overlap the programs of the next
                results = [encode(a, fts[i], use_pallas=self.use_pallas)
                           for a, i in zip(bases, dev)]
                for planes in results:
                    for p in planes:
                        p.copy_to_host_async()
                for i, planes in zip(dev, results):
                    out[i] = [np.asarray(p) for p in planes]
        return out

    def byte_planes_batch(self, xs):
        out: List[Optional[List[np.ndarray]]] = [None] * len(xs)
        with obs.span("zllm.array.encode") as sp:
            views = [_bit_view_np(np.ascontiguousarray(x)).reshape(-1) for x in xs]
            sp.set(bytes=sum(v.nbytes for v in views))
            for dstr, idxs in self._buckets([v.dtype for v in views]).items():
                if not self._device_ok(np.dtype(dstr)):
                    self._count("host", [views[i].nbytes for i in idxs])
                    for i in idxs:
                        out[i] = _byte_planes_host(views[i])
                    continue
                self._count("device", [views[i].nbytes for i in idxs])
                with obs.span("zllm.array.concat"):
                    cat = np.concatenate([views[i] for i in idxs])
                with obs.span("zllm.array.device", bytes=cat.nbytes):
                    planes = [np.asarray(p) for p in self._ops().zipnn_split_planes(
                        cat, use_pallas=self.use_pallas)]
                with obs.span("zllm.array.slice"):
                    off = 0
                    for i in idxs:
                        n = views[i].size
                        out[i] = [np.ascontiguousarray(p[off:off + n])
                                  for p in planes]
                        off += n
        return out

    def merge_planes_xor_batch(self, items):
        out: List[Optional[np.ndarray]] = [None] * len(items)
        with obs.span("zllm.array.decode") as sp:
            views = [_bit_view_np(np.ascontiguousarray(base)) for _, base in items]
            sp.set(bytes=sum(v.nbytes for v in views))
            for dstr, idxs in self._buckets([v.dtype for v in views]).items():
                if not self._device_ok(np.dtype(dstr)):
                    self._count("host", [views[i].nbytes for i in idxs])
                    for i in idxs:
                        out[i] = _merge_planes_xor_host(items[i][0], views[i])
                    continue
                self._count("device", [views[i].nbytes for i in idxs])
                nb = np.dtype(dstr).itemsize
                with obs.span("zllm.array.concat"):
                    cat_base = np.concatenate([views[i].reshape(-1) for i in idxs])
                    cat_planes = [
                        np.concatenate([np.ascontiguousarray(np.asarray(items[i][0][pi]))
                                        for i in idxs])
                        for pi in range(nb)]
                with obs.span("zllm.array.device", bytes=cat_base.nbytes):
                    merged = np.asarray(self._ops().bitx_decode_planes(
                        cat_planes, cat_base, use_pallas=self.use_pallas))
                with obs.span("zllm.array.slice"):
                    off = 0
                    for i in idxs:
                        n = views[i].size
                        out[i] = np.ascontiguousarray(
                            merged[off:off + n]).reshape(views[i].shape)
                        off += n
        return out

    def merge_planes_batch(self, items):
        out: List[Optional[np.ndarray]] = [None] * len(items)
        dtypes = [np.dtype(d) for _, d, _ in items]
        with obs.span("zllm.array.decode") as sp:
            sp.set(bytes=sum(d.itemsize * int(np.prod(shape))
                             for d, (_, _, shape) in zip(dtypes, items)))
            for dstr, idxs in self._buckets(dtypes).items():
                dtype_np = np.dtype(dstr)
                nb = dtype_np.itemsize
                sizes = [nb * int(np.prod(items[i][2])) for i in idxs]
                if not self._device_ok(dtype_np):
                    self._count("host", sizes)
                    for i in idxs:
                        out[i] = _merge_planes_host(*items[i])
                    continue
                self._count("device", sizes)
                uview = np.dtype(f"<u{nb}")
                with obs.span("zllm.array.concat"):
                    cat_planes = [
                        np.concatenate([np.ascontiguousarray(np.asarray(items[i][0][pi]))
                                        for i in idxs])
                        for pi in range(nb)]
                total = int(cat_planes[0].size)
                with obs.span("zllm.array.device", bytes=total * nb):
                    merged = np.asarray(self._ops().zipnn_merge_planes(
                        cat_planes, uview, (total,), use_pallas=self.use_pallas))
                with obs.span("zllm.array.slice"):
                    off = 0
                    for i in idxs:
                        shape = items[i][2]
                        n = int(np.prod(shape)) if len(shape) else 1
                        out[i] = np.ascontiguousarray(
                            merged[off:off + n]).view(dtype_np.str).reshape(shape)
                        off += n
        return out


_BACKENDS: Dict[str, ArrayBackend] = {}


def get_backend(spec="auto") -> ArrayBackend:
    """Resolve an array backend: ``"numpy"``, ``"jax"``, ``"auto"``, or an
    :class:`ArrayBackend` instance (passed through).

    ``"auto"`` picks jax only when a TPU is attached
    (``jax.default_backend() == "tpu"``); elsewhere the numpy host path runs
    (interpret-mode kernels are Python emulation). A JAX that fails to
    initialise raises here: it never turns into the host path unnoticed.
    """
    if not isinstance(spec, str):
        return spec
    cached = _BACKENDS.get(spec)
    if cached is not None:
        return cached
    if spec == "numpy":
        backend: ArrayBackend = NumpyBackend()
    elif spec == "jax":
        backend = JaxBackend()
    elif spec == "auto":
        import jax
        backend = JaxBackend() if jax.default_backend() == "tpu" else NumpyBackend()
    else:
        raise ValueError(f"unknown array backend {spec!r} "
                         f"(expected 'numpy', 'jax' or 'auto')")
    _BACKENDS[spec] = backend
    return backend


# ---------------------------------------------------------------------------
# Deprecated free-function aliases (one-release shim): external callers used
# to import the host transforms directly; array math now routes through an
# ArrayBackend so the jax device path is substitutable.
# ---------------------------------------------------------------------------

def _warn_shim(old: str, new: str) -> None:
    warnings.warn(
        f"repro.core.bitx.{old} is deprecated; use "
        f"repro.core.bitx.get_backend(...).{new} instead "
        f"(this shim will be removed next release)",
        DeprecationWarning, stacklevel=3)


def xor_delta_planes_np(base: np.ndarray, ft: np.ndarray) -> List[np.ndarray]:
    """Deprecated alias of ``get_backend("numpy").xor_delta_planes``."""
    _warn_shim("xor_delta_planes_np", "xor_delta_planes")
    return _xor_delta_planes_host(base, ft)


def byte_planes_np(x: np.ndarray) -> List[np.ndarray]:
    """Deprecated alias of ``get_backend("numpy").byte_planes``."""
    _warn_shim("byte_planes_np", "byte_planes")
    return _byte_planes_host(x)


def merge_planes_xor_np(planes: Sequence[np.ndarray], base: np.ndarray) -> np.ndarray:
    """Deprecated alias of ``get_backend("numpy").merge_planes_xor``."""
    _warn_shim("merge_planes_xor_np", "merge_planes_xor")
    return _merge_planes_xor_host(planes, base)


@dataclass
class TensorRecord:
    """Header record for one tensor inside a .bitx container."""

    name: str
    dtype_str: str            # safetensors tag of the original tensor ("BF16", "F32", ...)
    shape: Tuple[int, ...]
    codec: str                # "bitx" | "bitxq" | "zipnn" | "raw" | "stored" | "dedup"
    base_hash: Optional[str]  # CAS hash of the base tensor (bitx/bitxq) / None
    self_hash: str            # CAS hash of this tensor's raw bytes (dedup + verify)
    plane_sizes: List[int] = field(default_factory=list)  # compressed bytes per plane
    raw_size: int = 0
    # quantized-delta (bitxq) stamp — emitted only when set, so containers
    # that never use the lane stay byte-identical to pre-bitxq builds.
    # ``qscale_bits`` is the float32 scale's raw bit pattern (uint32): round-
    # tripping the scale through JSON as a decimal float could perturb the
    # last bit and break the decode-side prediction replay.
    base_dtype: Optional[str] = None   # safetensors tag of the base ("BF16", ...)
    qscale_bits: Optional[int] = None  # float32 bit pattern of the quant scale
    qzero_point: Optional[int] = None  # integer zero point of the quant grid

    def to_json(self) -> Dict:
        d = {
            "name": self.name,
            "dtype": self.dtype_str,
            "shape": list(self.shape),
            "codec": self.codec,
            "base_hash": self.base_hash,
            "self_hash": self.self_hash,
            "plane_sizes": self.plane_sizes,
            "raw_size": self.raw_size,
        }
        if self.base_dtype is not None:
            d["base_dtype"] = self.base_dtype
        if self.qscale_bits is not None:
            d["qscale_bits"] = self.qscale_bits
        if self.qzero_point is not None:
            d["qzero_point"] = self.qzero_point
        return d

    @staticmethod
    def from_json(d: Dict) -> "TensorRecord":
        qs = d.get("qscale_bits")
        qz = d.get("qzero_point")
        return TensorRecord(
            name=d["name"],
            dtype_str=d["dtype"],
            shape=tuple(d["shape"]),
            codec=d["codec"],
            base_hash=d.get("base_hash"),
            self_hash=d["self_hash"],
            plane_sizes=list(d.get("plane_sizes", [])),
            raw_size=int(d.get("raw_size", 0)),
            base_dtype=d.get("base_dtype"),
            qscale_bits=int(qs) if qs is not None else None,
            qzero_point=int(qz) if qz is not None else None,
        )


class BitXCodec:
    """Back-compat facade over the codec registry (kept for one release).

    New code goes through :mod:`repro.core.codecs` directly; this class maps
    the old per-codec ``encode_*``/``decode_*`` methods onto registry lanes
    sharing one :class:`~repro.core.codecs.CodecRuntime`. The runtime owns
    the zstd contexts per worker thread (compressor objects are not
    thread-safe), so a codec instance is still safe to share across a pool.
    ``threads`` is forwarded to ``zstd.ZstdCompressor(threads=...)``.
    """

    def __init__(self, level: int = DEFAULT_ZSTD_LEVEL, threads: int = 0,
                 backend=None):
        self.level = level
        self.threads = threads
        self.runtime = CodecRuntime(level=level, threads=threads,
                                    backend=get_backend(backend or "numpy"))

    @property
    def _cctx(self):
        return self.runtime._compressor()

    @property
    def _dctx(self):
        return self.runtime._decompressor()

    # -- BitX ---------------------------------------------------------------
    def encode_delta(self, base: np.ndarray, ft: np.ndarray) -> Tuple[List[bytes], int]:
        """Returns (compressed plane frames MSB-first, raw byte size)."""
        _, frames, raw = get_codec("bitx").encode(
            self.runtime, EncodeInput(data=ft, base=base))
        return frames, raw

    def decode_delta(
        self, frames: Sequence[bytes], base: np.ndarray
    ) -> np.ndarray:
        planes = [np.frombuffer(self.runtime.decompress(f), np.uint8) for f in frames]
        return self.runtime.backend.merge_planes_xor(planes, base)

    # -- ZipNN fallback (no base available, §4.4.3) ---------------------------
    def encode_planes(self, x: np.ndarray) -> Tuple[List[bytes], int]:
        _, frames, raw = get_codec("zipnn").encode(self.runtime, EncodeInput(data=x))
        return frames, raw

    def decode_planes(self, frames: Sequence[bytes], dtype_np: np.dtype, shape) -> np.ndarray:
        planes = [np.frombuffer(self.runtime.decompress(f), np.uint8) for f in frames]
        return self.runtime.backend.merge_planes(planes, dtype_np, shape)

    # -- raw zstd (non-float / last resort) ----------------------------------
    def encode_raw(self, data: bytes) -> bytes:
        return self.runtime.compress(data)

    def decode_raw(self, frame: bytes) -> bytes:
        return self.runtime.decompress(frame)

    # -- stored (verbatim) ----------------------------------------------------
    @staticmethod
    def choose_raw_codec(data: bytes, frame: bytes) -> Tuple[str, bytes]:
        """Deprecated alias of :func:`repro.core.codecs.raw_or_stored`."""
        return raw_or_stored(data, frame)


class BitXWriter:
    """Streams TensorRecords + frames into a .bitx container."""

    def __init__(self, level: int = DEFAULT_ZSTD_LEVEL, file_metadata: Optional[Dict] = None,
                 threads: int = 0, backend=None):
        self.codec = BitXCodec(level=level, threads=threads, backend=backend)
        self.records: List[TensorRecord] = []
        self.frames: List[bytes] = []
        self.file_metadata = dict(file_metadata or {})

    def add_bitx(
        self, name: str, dtype_str: str, shape, base: np.ndarray, ft: np.ndarray,
        base_hash: str, self_hash: str,
    ) -> int:
        frames, raw = self.codec.encode_delta(base, ft)
        self.records.append(
            TensorRecord(name, dtype_str, tuple(shape), "bitx", base_hash, self_hash,
                         [len(f) for f in frames], raw)
        )
        self.frames.extend(frames)
        return sum(len(f) for f in frames)

    def add_zipnn(self, name: str, dtype_str: str, shape, x: np.ndarray, self_hash: str) -> int:
        frames, raw = self.codec.encode_planes(x)
        self.records.append(
            TensorRecord(name, dtype_str, tuple(shape), "zipnn", None, self_hash,
                         [len(f) for f in frames], raw)
        )
        self.frames.extend(frames)
        return sum(len(f) for f in frames)

    def add_raw(self, name: str, dtype_str: str, shape, data: bytes, self_hash: str) -> int:
        frame = self.codec.encode_raw(data)
        self.records.append(
            TensorRecord(name, dtype_str, tuple(shape), "raw", None, self_hash,
                         [len(frame)], len(data))
        )
        self.frames.append(frame)
        return len(frame)

    def add_dedup(self, name: str, dtype_str: str, shape, self_hash: str, raw_size: int) -> int:
        """Tensor already in the pool — store only the reference (0 payload)."""
        self.records.append(
            TensorRecord(name, dtype_str, tuple(shape), "dedup", None, self_hash, [], raw_size)
        )
        return 0

    def add_precomputed(self, name: str, dtype_str: str, shape, codec: str,
                        base_hash: Optional[str], self_hash: str,
                        frames: Sequence[bytes], raw_size: int,
                        extras: Optional[Dict] = None) -> int:
        """Append a record whose frames were encoded elsewhere (the parallel
        ingest engine encodes off-thread, then merges in tensor order so the
        container bytes match the serial path exactly). ``extras`` carries
        optional stamp fields a lane needs replayed at decode time (the
        quantized-delta lane's ``base_dtype``/``qscale_bits``/``qzero_point``).
        Zero-payload dedup records go through :meth:`add_dedup` instead."""
        assert codec in ("bitx", "bitxq", "zipnn", "raw", "stored"), codec
        self.records.append(
            TensorRecord(name, dtype_str, tuple(shape), codec, base_hash, self_hash,
                         [len(f) for f in frames], raw_size, **(extras or {}))
        )
        self.frames.extend(frames)
        return sum(len(f) for f in frames)

    def tobytes(self) -> bytes:
        header = {
            "metadata": self.file_metadata,
            "backend": ENTROPY_BACKEND,
            "tensors": [r.to_json() for r in self.records],
        }
        hjson = json.dumps(header, separators=(",", ":")).encode()
        out = io.BytesIO()
        out.write(MAGIC)
        out.write(struct.pack("<Q", len(hjson)))
        out.write(hjson)
        for f in self.frames:
            out.write(f)
        return out.getvalue()

    def write(self, path: str, *, fault_hook=None, fsync: bool = False) -> int:
        """Write the container atomically: bytes land at ``path + TMP_SUFFIX``
        first and are renamed into place, so a crash at any instant leaves
        either no file, a ``.part`` temp (orphan-scan debris), or the
        complete container — never a torn file at the final path.

        ``fault_hook(point_name)`` is the crash-injection hook for the
        recovery test harness; it may raise to simulate a kill at that
        point. No cleanup runs when it does — the on-disk state is exactly
        what a real crash would leave (callers that *handle* failures, e.g.
        the ingest rollback, remove both ``path`` and the temp themselves).
        ``fsync=True`` flushes the temp file to stable storage before the
        rename (the compaction path, where the old copies are deleted soon
        after)."""
        blob = self.tobytes()
        if fault_hook is not None:
            fault_hook("writer.before_write")
        tmp = path + TMP_SUFFIX
        with open(tmp, "wb") as f:
            f.write(blob)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        if fault_hook is not None:
            fault_hook("writer.after_temp")
        os.replace(tmp, path)
        if fault_hook is not None:
            fault_hook("writer.after_rename")
        return len(blob)


class BitXReader:
    """Reads a .bitx container; decode requires a base-tensor resolver for
    bitx-coded records and a pool resolver for dedup'd records.

    ``open(path)`` memory-maps the container: only the header is parsed
    eagerly, frames are lazy zero-copy slices of the map
    (:meth:`frames_for` returns memoryviews), so resolving a single tensor
    out of a multi-GB container touches just that tensor's pages. A reader
    is safe to share across decode worker threads (the runtime keeps its
    zstd contexts thread-local); call :meth:`close` to drop the map.

    ``runtime`` selects the entropy settings and array backend used for
    decode (the store passes its own); the default is a numpy-backed
    runtime at default settings — decode output is identical either way.
    """

    def __init__(self, data, runtime: Optional[CodecRuntime] = None):
        view = memoryview(data)
        assert bytes(view[:8]) == MAGIC, "not a BitX container"
        (hlen,) = struct.unpack("<Q", view[8:16])
        header = json.loads(bytes(view[16 : 16 + hlen]))
        backend = header.get("backend", ENTROPY_BACKEND)
        if backend != ENTROPY_BACKEND:
            raise ValueError(
                f"container written with entropy backend {backend!r} but this "
                f"store decodes only {ENTROPY_BACKEND!r} frames")
        self.file_metadata: Dict = header.get("metadata", {})
        self.records = [TensorRecord.from_json(r) for r in header["tensors"]]
        self._name_to_idx: Optional[Dict[str, int]] = None
        self._payload = view[16 + hlen :]
        # absolute file offset where the frame payload begins — frame spans
        # (``frame_span``) are payload-relative and need this to become
        # sendfile-able (path, offset, length) triples
        self.payload_offset = 16 + hlen
        self.path: Optional[str] = None  # set by open(); None for byte-backed
        self._mmap: Optional[mmap.mmap] = None
        self._file = None
        # frame offsets in record order
        self._offsets: List[List[Tuple[int, int]]] = []
        off = 0
        for r in self.records:
            sizes = r.plane_sizes
            spans = []
            for s in sizes:
                spans.append((off, off + s))
                off += s
            self._offsets.append(spans)
        self.runtime = runtime if runtime is not None else CodecRuntime()

    @staticmethod
    def open(path: str, use_mmap: bool = True,
             runtime: Optional[CodecRuntime] = None) -> "BitXReader":
        if not use_mmap:
            with open(path, "rb") as f:
                return BitXReader(f.read(), runtime=runtime)
        f = open(path, "rb")
        mm = None
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
            reader = BitXReader(mm, runtime=runtime)  # may raise (bad magic, backend mismatch)
        except Exception:
            if mm is not None:
                try:
                    mm.close()
                except BufferError:
                    # the raising frame still exports a view over the map;
                    # GC finalizes it once the traceback is released
                    pass
            f.close()  # the fd is the scarce resource — always release it
            raise
        reader._mmap, reader._file = mm, f
        reader.path = path
        return reader

    def close(self) -> None:
        """Release the memory map (no-op for byte-backed readers). Frames
        already handed out keep the map alive until they are collected."""
        self._payload = memoryview(b"")
        if self._mmap is not None:
            try:
                self._mmap.close()
            except BufferError:
                pass  # exported frame views still alive; GC finishes the job
            self._mmap = None
        if self._file is not None:
            self._file.close()
            self._file = None

    @property
    def payload_size(self) -> int:
        """Actual payload bytes behind the header (mmap/bytes length)."""
        return len(self._payload)

    @property
    def expected_payload_size(self) -> int:
        """Payload bytes the header's plane_sizes promise. A container whose
        actual payload is shorter was truncated — fsck flags it corrupt."""
        return sum(s for r in self.records for s in r.plane_sizes)

    def index_of(self, name: str) -> int:
        """Record index for a tensor name (KeyError if absent). The map is
        built lazily once per reader — tensor-granular serving resolves by
        name on every request, so the lookup must not rescan the records.
        Safe under concurrent builders: both compute the same dict and the
        attribute store is atomic."""
        m = self._name_to_idx
        if m is None:
            m = self._name_to_idx = {r.name: i for i, r in enumerate(self.records)}
        return m[name]

    def frames_for(self, idx: int) -> List[memoryview]:
        return [self._payload[b:e] for b, e in self._offsets[idx]]

    def frame_span(self, idx: int) -> Tuple[int, int]:
        """(absolute file offset, length) of record ``idx``'s contiguous
        frame bytes. For ``stored`` records this span IS the tensor's raw
        little-endian bytes on disk — the serving layer's zero-copy
        ``os.sendfile`` source."""
        spans = self._offsets[idx]
        if not spans:
            return self.payload_offset, 0
        return self.payload_offset + spans[0][0], spans[-1][1] - spans[0][0]

    def decode_tensor(self, idx: int, base_resolver, pool_resolver) -> np.ndarray:
        """Decode record ``idx`` to its raw bit-view array via the codec
        registry (an unknown stamped codec raises ``ValueError`` naming it).

        ``base_resolver(base_hash) -> np.ndarray`` and
        ``pool_resolver(self_hash) -> np.ndarray`` fetch dependencies (CAS pool).
        """
        from repro.formats.safetensors import STR_TO_DTYPE

        r = self.records[idx]
        codec = get_codec(r.codec)
        return codec.decode(self.runtime, r, self.frames_for(idx),
                            STR_TO_DTYPE[r.dtype_str], base_resolver, pool_resolver)
