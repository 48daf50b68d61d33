"""Seeded uploads: a base checkpoint shard and fine-tunes of it.

Everything here is a function of ``(seed, stream, tensor, chunk)`` alone, so
any process, with any number of threads, makes the same bytes. Stream 0 is
the base; stream ``k >= 1`` is the k-th fine-tune of a run.

- Base: every element is ``N(0, initializer_range)`` drawn in float32 and
  rounded to the tensor's dtype (bf16 rounds to nearest even).
- Fine-tune: each tensor whose name matches ``changed_tensors`` has each
  element, with probability ``delta_density``, set to
  ``round(base * (1 + eps))`` with ``eps ~ N(0, delta_rel)``, computed in
  float32 from the base as stored. Other tensors are the base's bytes.

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
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from bench.spec import Tensor, layer_tensors

CHUNK = 1 << 22  # elements per generator stream chunk


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


def _store(dtype: str, x: np.ndarray) -> np.ndarray:
    return round_bf16(x) if dtype == "BF16" else x


def _as_f32(dtype: str, stored: np.ndarray) -> np.ndarray:
    return widen_bf16(stored) if dtype == "BF16" else stored


def _new(t: Tensor) -> np.ndarray:
    return np.empty(t.numel, np.uint16 if t.dtype == "BF16" else np.float32)


def _chunks(n: int):
    return [(c, s, min(n, s + CHUNK)) for c, s in enumerate(range(0, n, CHUNK))]


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
        self.header = header_bytes(self.tensors)
        self._pool = ThreadPoolExecutor(max_workers=max(1, threads),
                                        thread_name_prefix="gen")
        self.base = self._make(0, self._base_chunk)
        self.base_digests = list(self._pool.map(
            lambda a: _digest(memoryview(a).cast("B")), self.base))

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    @property
    def changed_bytes(self) -> int:
        """Bytes of the tensors that every fine-tune changes."""
        return sum(t.nbytes for t, ch in zip(self.tensors, self.changed) if ch)

    def _base_chunk(self, i: int, out: np.ndarray, c: int, s: int, e: int) -> None:
        x = _rng(self.seed, 0, i, c).standard_normal(e - s, dtype=np.float32)
        x *= np.float32(self.std)
        out[s:e] = _store(self.tensors[i].dtype, x)

    def _ft_chunk(self, stream: int):
        def run(i: int, out: np.ndarray, c: int, s: int, e: int) -> None:
            t, base = self.tensors[i], self.base[i][s:e]
            rng = _rng(self.seed, stream, i, c)
            eps = rng.standard_normal(e - s, dtype=np.float32)
            eps *= np.float32(self.delta_rel)
            eps += np.float32(1.0)
            eps *= _as_f32(t.dtype, base)
            new = _store(t.dtype, eps)
            if self.density < 1.0:
                keep = rng.random(e - s, dtype=np.float32) >= self.density
                new[keep] = base[keep]
            out[s:e] = new
        return run

    def _make(self, stream: int, fill, only: Optional[List[bool]] = None):
        """Arrays of every tensor; ``only[i]`` False reuses the base's."""
        arrays: List[Optional[np.ndarray]] = [None] * len(self.tensors)
        jobs = []
        for i, t in enumerate(self.tensors):
            if only is not None and not only[i]:
                arrays[i] = self.base[i]
                continue
            arrays[i] = _new(t)
            jobs += [(i, arrays[i], c, s, e) for c, s, e in _chunks(t.numel)]
        for f in [self._pool.submit(fill, *j) for j in jobs]:
            f.result()
        return arrays

    def upload(self, stream: int) -> Upload:
        """The base (stream 0) or the ``stream``-th fine-tune, with digests."""
        if stream == 0:
            arrays, digests = self.base, self.base_digests
        else:
            arrays = self._make(stream, self._ft_chunk(stream), self.changed)
            new = [i for i, ch in enumerate(self.changed) if ch]
            fresh = dict(zip(new, self._pool.map(
                lambda i: _digest(memoryview(arrays[i]).cast("B")), new)))
            digests = [fresh.get(i, self.base_digests[i])
                       for i in range(len(self.tensors))]
        segs = [memoryview(self.header)] + [memoryview(a).cast("B") for a in arrays]
        return Upload(segs, [_digest(segs[0])] + digests,
                      [""] + [t.name for t in self.tensors])
