"""Seeded uploads: a base checkpoint shard and fine-tunes of it.

Everything here is a function of ``(seed, stream, tensor, chunk)`` alone, so
any process, with any number of threads, makes the same bytes. Stream 0 is
the base; stream ``k >= 1`` is the k-th fine-tune of a run.

- Base: every element is ``N(0, initializer_range)`` drawn in float32 and
  rounded to the tensor's dtype (to nearest even).
- Fine-tune: each tensor whose name matches ``changed_tensors`` has each
  element, with probability ``delta_density``, set to
  ``round(base * (1 + eps))`` with ``eps ~ N(0, delta_rel)``, computed in
  float32 from the base as stored. Other tensors are the base's bytes.
- An ``F8_E4M3`` weight and its ``weight_scale_inv`` are made together, a
  chunk being whole stripes of block rows. For each block the float32
  values ``w`` (the base's draws; a fine-tune's ``w * (1 + eps)`` as above,
  ``w = q * s`` the base dequantized) are quantized as ``s = max|w| / 448``,
  ``q = e4m3(w / s)``. The scale changes where its weight does.

An upload is a list of byte segments (the safetensors header, then each
tensor) and the sha256 of each segment: the reference that reads are held
to. Nothing here imports JAX or the program.
"""

from __future__ import annotations

import hashlib
import json
import re
import struct
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from bench.spec import Tensor, layer_tensors, scale_shape

CHUNK = 1 << 22  # elements per generator stream chunk
E4M3_MAX = 448.0


def _rng(seed: int, stream: int, tensor: int, chunk: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), stream, tensor, chunk])))


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> bf16 bit pattern (uint16), round to nearest even."""
    u = x.view(np.uint32)
    return ((u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
            >> np.uint32(16)).astype(np.uint16)


def widen_bf16(b: np.ndarray) -> np.ndarray:
    """bf16 bit pattern (uint16) -> float32, exactly."""
    return (b.astype(np.uint32) << np.uint32(16)).view(np.float32)


def _e4m3():
    import ml_dtypes  # imported only where a layout has FP8 weights
    return ml_dtypes.float8_e4m3fn


def round_e4m3(x: np.ndarray) -> np.ndarray:
    """float32 -> e4m3 bit pattern (uint8), round to nearest even."""
    return x.astype(_e4m3()).view(np.uint8)


def widen_e4m3(b: np.ndarray) -> np.ndarray:
    """e4m3 bit pattern (uint8) -> float32, exactly."""
    return b.view(_e4m3()).astype(np.float32)


# safetensors tag -> (storage array type, float32 -> stored, stored -> float32)
STORAGE = {
    "BF16": (np.uint16, round_bf16, widen_bf16),
    "F16": (np.float16, partial(np.asarray, dtype=np.float16),
            partial(np.asarray, dtype=np.float32)),
    "F32": (np.float32, np.asarray, np.asarray),
    "F8_E4M3": (np.uint8, round_e4m3, widen_e4m3),
}


def _new(t: Tensor) -> np.ndarray:
    return np.empty(t.numel, STORAGE[t.dtype][0])


def _chunks(n: int):
    return [(c, s, min(n, s + CHUNK)) for c, s in enumerate(range(0, n, CHUNK))]


def _stripes(t: Tensor, block_rows: int):
    """Chunks of a block-scaled weight: whole stripes of ``block_rows`` rows
    (the last may be short), about ``CHUNK`` elements each."""
    rows, cols = t.shape
    step = block_rows * max(1, CHUNK // (block_rows * cols))
    return [(c, r, min(rows, r + step)) for c, r in enumerate(range(0, rows, step))]


def _per_element(s: np.ndarray, block: Tuple[int, int], shape) -> np.ndarray:
    """Each block's scale at each of its elements."""
    return np.repeat(np.repeat(s, block[0], 0), block[1], 1)[:shape[0], :shape[1]]


def quantize_blocks(w: np.ndarray, block: Tuple[int, int]):
    """float32 rows ``w`` that start a block row -> e4m3 codes (uint8) and one
    float32 scale per block: ``s = max|w| / 448``, ``q = e4m3(w / s)``."""
    nr, nc = scale_shape(w.shape, block)
    a = np.zeros((nr * block[0], nc * block[1]), np.float32)
    a[:w.shape[0], :w.shape[1]] = np.abs(w)
    s = a.reshape(nr, block[0], nc, block[1]).max(axis=(1, 3))
    s /= np.float32(E4M3_MAX)
    full = _per_element(s, block, w.shape)
    x = np.divide(w, full, out=np.zeros_like(w), where=full > 0)
    return round_e4m3(x), s


class Upload(NamedTuple):
    """One safetensors file as byte segments, and the sha256 of each."""
    segments: List[memoryview]
    digests: List[str]
    names: List[str]        # "" for the header, then each tensor's name

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.segments)

    def tensor(self, name: str) -> memoryview:
        return self.segments[self.names.index(name)]


def header_bytes(tensors: List[Tensor]) -> bytes:
    """8-byte length, then the JSON header padded with spaces to 8 bytes."""
    hdr, off = {"__metadata__": {"format": "pt"}}, 0
    for t in tensors:
        hdr[t.name] = {"dtype": t.dtype, "shape": list(t.shape),
                       "data_offsets": [off, off + t.nbytes]}
        off += t.nbytes
    blob = json.dumps(hdr, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    return struct.pack("<Q", len(blob)) + blob


def _digest(view: memoryview) -> str:
    return hashlib.sha256(view).hexdigest()


class Generator:
    """Makes the base once, then any fine-tune of it, on ``threads`` threads."""

    def __init__(self, config: Dict, traffic: Dict, seed: int, threads: int = 4):
        self.tensors = layer_tensors(config)
        self.std = float(config.get("initializer_range", 0.02))
        self.seed = int(seed)
        self.delta_rel = float(traffic.get("delta_rel", 0.01))
        self.density = float(traffic.get("delta_density", 1.0))
        pat = re.compile(traffic.get("changed_tensors", ".*"))
        self.changed = [bool(pat.search(t.name)) for t in self.tensors]
        self.scales = self._pair_scales()
        for i, j in self.scales.items():
            self.changed[j] = self.changed[i]
        self.header = header_bytes(self.tensors)
        self._pool = ThreadPoolExecutor(max_workers=max(1, threads),
                                        thread_name_prefix="gen")
        self.base = self._make(0)
        self.base_digests = list(self._pool.map(
            lambda a: _digest(memoryview(a).cast("B")), self.base))

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    @property
    def changed_bytes(self) -> int:
        """Bytes of the tensors that every fine-tune changes."""
        return sum(t.nbytes for t, ch in zip(self.tensors, self.changed) if ch)

    def _pair_scales(self) -> Dict[int, int]:
        """Index of each ``F8_E4M3`` weight -> index of its scale tensor."""
        index = {t.name: i for i, t in enumerate(self.tensors)}
        scales = {}
        for j, t in enumerate(self.tensors):
            if t.scale_of:
                i = index[t.scale_of]
                w = self.tensors[i]
                if (w.dtype != "F8_E4M3" or t.dtype != "F32"
                        or t.shape != scale_shape(w.shape, t.block)):
                    raise ValueError(f"{t.name} {t.dtype}{list(t.shape)} does "
                                     f"not scale {w.name} {w.dtype}"
                                     f"{list(w.shape)} in blocks {t.block}")
                scales[i] = j
        for i, t in enumerate(self.tensors):
            if t.dtype == "F8_E4M3" and i not in scales:
                raise ValueError(f"F8_E4M3 tensor {t.name} has no weight_scale_inv")
        return scales

    def _base_chunk(self, i: int, out: np.ndarray, c: int, s: int, e: int) -> None:
        x = _rng(self.seed, 0, i, c).standard_normal(e - s, dtype=np.float32)
        x *= np.float32(self.std)
        out[s:e] = STORAGE[self.tensors[i].dtype][1](x)

    def _ft_chunk(self, stream: int, i: int, out: np.ndarray, c: int, s: int,
                  e: int) -> None:
        _, store, widen = STORAGE[self.tensors[i].dtype]
        base = self.base[i][s:e]
        rng = _rng(self.seed, stream, i, c)
        eps = rng.standard_normal(e - s, dtype=np.float32)
        eps *= np.float32(self.delta_rel)
        eps += np.float32(1.0)
        eps *= widen(base)
        new = store(eps)
        if self.density < 1.0:
            keep = rng.random(e - s, dtype=np.float32) >= self.density
            new[keep] = base[keep]
        out[s:e] = new

    def _block_chunk(self, stream: int, i: int, q_out: np.ndarray,
                     s_out: np.ndarray, c: int, r0: int, r1: int) -> None:
        """Rows ``r0:r1`` of a block-scaled weight and their scales."""
        j = self.scales[i]
        block, cols = self.tensors[j].block, self.tensors[i].shape[1]
        nc = self.tensors[j].shape[1]
        rows = slice(r0 * cols, r1 * cols)
        srows = slice(r0 // block[0] * nc, -(-r1 // block[0]) * nc)
        rng = _rng(self.seed, stream, i, c)
        if stream == 0:
            w = rng.standard_normal((r1 - r0, cols), dtype=np.float32)
            w *= np.float32(self.std)
        else:
            s = self.base[j][srows].reshape(-1, nc)
            base = widen_e4m3(self.base[i][rows]).reshape(r1 - r0, cols)
            base *= _per_element(s, block, base.shape)
            w = rng.standard_normal(base.shape, dtype=np.float32)
            w *= np.float32(self.delta_rel)
            w += np.float32(1.0)
            w *= base
            if self.density < 1.0:
                keep = rng.random(base.shape, dtype=np.float32) >= self.density
                w[keep] = base[keep]
        q, s = quantize_blocks(w, block)
        q_out[rows] = q.ravel()
        s_out[srows] = s.ravel()

    def _make(self, stream: int, only: Optional[List[bool]] = None):
        """Arrays of every tensor; ``only[i]`` False reuses the base's."""
        fresh = [only is None or only[i] for i in range(len(self.tensors))]
        arrays = [_new(t) if fresh[i] else self.base[i]
                  for i, t in enumerate(self.tensors)]
        jobs = []
        for i, t in enumerate(self.tensors):
            if not fresh[i] or t.scale_of:  # a scale is made with its weight
                continue
            j = self.scales.get(i)
            if j is not None:
                fill = partial(self._block_chunk, stream, i, arrays[i], arrays[j])
                jobs += [partial(fill, *c)
                         for c in _stripes(t, self.tensors[j].block[0])]
            else:
                fill = (partial(self._base_chunk, i, arrays[i]) if stream == 0
                        else partial(self._ft_chunk, stream, i, arrays[i]))
                jobs += [partial(fill, *c) for c in _chunks(t.numel)]
        for f in [self._pool.submit(j) for j in jobs]:
            f.result()
        return arrays

    def upload(self, stream: int) -> Upload:
        """The base (stream 0) or the ``stream``-th fine-tune, with digests."""
        if stream == 0:
            arrays, digests = self.base, self.base_digests
        else:
            arrays = self._make(stream, self.changed)
            new = [i for i, ch in enumerate(self.changed) if ch]
            fresh = dict(zip(new, self._pool.map(
                lambda i: _digest(memoryview(arrays[i]).cast("B")), new)))
            digests = [fresh.get(i, self.base_digests[i])
                       for i in range(len(self.tensors))]
        segs = [memoryview(self.header)] + [memoryview(a).cast("B") for a in arrays]
        return Upload(segs, [_digest(segs[0])] + digests,
                      [""] + [t.name for t in self.tensors])
