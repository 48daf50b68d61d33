"""Share of the base bytes that the window's XOR splits found resident on
the device, from /stats store.array_path (``resident_hit_bytes`` over hits
and misses). None where the store reports no resident bases."""

from bench.readers import delta


def read(run):
    hit = delta(run, "store", "array_path", "resident_hit_bytes")
    miss = delta(run, "store", "array_path", "resident_miss_bytes")
    return 100.0 * hit / (hit + miss) if hit + miss > 0 else None
