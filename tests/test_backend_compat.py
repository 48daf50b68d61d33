"""Entropy-backend stamp of ``.bitx`` containers.

A container stamps the entropy coder that wrote it (``zstd``). Frames of
another coder are NOT interchangeable, so opening a container stamped with
any other backend must raise a clear, actionable error — never hand back
garbage bytes. The tests write with zstd and forge another stamp.
"""

import json
import os
import struct

import numpy as np
import pytest

from repro.core.bitx import ENTROPY_BACKEND, MAGIC, BitXReader, BitXWriter
from repro.core.pipeline import ZLLMStore
from repro.formats import safetensors as st

OTHER_BACKEND = "zlib"


def _restamp_backend(path: str, backend: str) -> None:
    """Rewrite a container's header with a forged entropy-backend stamp
    (payload untouched) — simulating a container produced by another
    entropy coder."""
    raw = open(path, "rb").read()
    assert raw[:8] == MAGIC
    (hlen,) = struct.unpack("<Q", raw[8:16])
    header = json.loads(raw[16:16 + hlen])
    header["backend"] = backend
    hjson = json.dumps(header, separators=(",", ":")).encode()
    with open(path, "wb") as f:
        f.write(MAGIC + struct.pack("<Q", len(hjson)) + hjson + raw[16 + hlen:])


def _write_container(tmp_path) -> str:
    rng = np.random.RandomState(3)
    w = BitXWriter()
    w.add_zipnn("t0", "F32", (512,), rng.randn(512).astype(np.float32), "h0")
    path = str(tmp_path / "c.bitx")
    w.write(path)
    return path


def test_same_backend_roundtrip(tmp_path):
    path = _write_container(tmp_path)
    r = BitXReader.open(path)
    assert r.file_metadata == {}
    out = r.decode_tensor(0, None, None)
    assert out.shape == (512,)
    r.close()


def test_backend_mismatch_raises_clear_error(tmp_path):
    path = _write_container(tmp_path)
    _restamp_backend(path, OTHER_BACKEND)
    with pytest.raises(ValueError) as ei:
        BitXReader.open(path)
    msg = str(ei.value)
    # the error must name both backends
    assert OTHER_BACKEND in msg and ENTROPY_BACKEND in msg


def test_store_retrieval_surfaces_backend_mismatch_not_garbage(tmp_path):
    """End to end: a store whose container is stamped for the other backend
    must raise the clear error from retrieve_file/retrieve_tensor AND from
    a fresh process's load_index path — never decode garbage."""
    d = str(tmp_path / "hub" / "org" / "m")
    os.makedirs(d, exist_ok=True)
    rng = np.random.RandomState(5)
    st.save_file({"model.t0.weight": rng.randn(1024).astype(np.float32)},
                 os.path.join(d, "model.safetensors"))
    store = ZLLMStore(str(tmp_path / "store"))
    store.ingest_repo(d, "org/m")
    store.save_index()
    cpath = store.file_index["org/m/model.safetensors"]["path"]
    store.close()

    _restamp_backend(cpath, OTHER_BACKEND)
    s2 = ZLLMStore(str(tmp_path / "store"))
    assert s2.load_index()
    with pytest.raises(ValueError, match="entropy backend"):
        s2.retrieve_file("org/m", "model.safetensors")
    with pytest.raises(ValueError, match="entropy backend"):
        s2.retrieve_tensor("org/m", "model.safetensors", "model.t0.weight")
    # fsck flags it as unreadable rather than crashing
    report = s2.fsck(repair=False, spot_check=None)
    assert any("unreadable container" in msg for _, msg in report.corrupt)
    s2.close()
