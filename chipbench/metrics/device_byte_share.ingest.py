"""Share of the bytes transformed in the window that went through the
device kernels, from /stats store.array_path."""

from bench.readers import delta


def read(run):
    dev = delta(run, "store", "array_path", "device_bytes")
    host = delta(run, "store", "array_path", "host_bytes")
    return 100.0 * dev / (dev + host) if dev + host > 0 else None
