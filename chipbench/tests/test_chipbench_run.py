"""A whole run of each traffic mix on the CPU at test size, through the
real store server and client processes, and the runs that must come out
not correct: the lossy control and each fault a cell can have."""

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts the benchmark on the path)
from tinycell import run_tiny


def _zero_planes(store, keep=lambda i, n: False):
    """Fault: the XOR planes of every tensor not kept are zero, so the
    store keeps the base where the upload changed it."""
    enc = store.backend.xor_delta_planes_batch

    def broken(pairs):
        out = enc(pairs)
        for i, planes in enumerate(out):
            if not keep(i, len(out)):
                out[i] = [np.zeros_like(p) for p in planes]
        return out
    store.backend.xor_delta_planes_batch = broken


def state_unchanged(store):
    _zero_planes(store)


def half_left_out(store):
    _zero_planes(store, keep=lambda i, n: i < n // 2)


def answer_altered(store):
    """Fault: one byte of each merged tensor is flipped where it is made."""
    merge = store.backend.merge_planes_xor_batch

    def broken(items):
        out = [a.copy() for a in merge(items)]
        for a in out:
            a.reshape(-1).view(np.uint8)[0] ^= 1
        return out
    store.backend.merge_planes_xor_batch = broken


def one_upload_altered(store):
    """Fault: the store drops the low XOR bit in the third batch it encodes
    and in no other, so one upload of the window differs."""
    enc = store.backend.xor_delta_planes_batch
    seen = []

    def broken(pairs):
        out = enc(pairs)
        seen.append(1)
        if len(seen) == 3:
            for planes in out:
                planes[-1] = planes[-1] & 0xFE
        return out
    store.backend.xor_delta_planes_batch = broken


@pytest.mark.parametrize("traffic", ["ft-ingest", "attn-ft-ingest"])
def test_sound_run_is_correct(traffic, tmp_path):
    run, ok = run_tiny(traffic, str(tmp_path / "w"))
    assert ok, run.checks
    assert run.records and run.checks["failed_requests"]["value"] == 0
    assert run.checks["uploads_read_back"]["value"] >= 1
    assert run.stored1 > run.stored0
    assert 0 < run.setup_s < run.t0 - run.t_start + 1


def test_every_acknowledged_upload_is_read_back(tmp_path):
    run, ok = run_tiny("ft-ingest", str(tmp_path / "w"), seconds=30.0,
                       uploads_per_client=3)
    assert ok, run.checks
    # the pool, not the window, ended the loop: 2 clients x 3 uploads
    assert len(run.records) == 6
    assert {r["client"] for r in run.records} == {0, 1}
    assert max(r["t_ack"] for r in run.records) <= run.t_drain < run.t_end
    assert run.checks["uploads_read_back"]["value"] == 6


@pytest.mark.parametrize("traffic,patch", [
    ("ft-ingest", "lossy"), ("attn-ft-ingest", "lossy"),
    ("ft-ingest", state_unchanged), ("ft-ingest", half_left_out),
    ("ft-ingest", answer_altered), ("ft-ingest", one_upload_altered)],
    ids=["ingest-control", "attn-ingest-control", "ingest-unchanged",
         "ingest-half", "ingest-altered", "ingest-one-upload"])
def test_control_and_faults_are_not_correct(traffic, patch, tmp_path):
    from bench import harness
    fn = harness.lossy_control if patch == "lossy" else patch
    run, ok = run_tiny(traffic, str(tmp_path / "w"), patch=fn)
    assert not ok, run.checks
    assert run.checks["upload_mismatch"]["value"] >= 1
    if patch is one_upload_altered:
        # every acknowledged upload is read back, so one wrong one is found
        assert run.checks["upload_mismatch"]["value"] == 1
        assert run.checks["uploads_read_back"]["value"] >= 3
