"""Seconds in the array backend's batched encode per GB of user bytes taken in
during the window, summed over threads, from the program's stage counters
(/stats server.stages): ``zllm.array.encode``: the bucket copies, the device
call with its host<->device copies, the per-tensor slices."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.array.encode")
