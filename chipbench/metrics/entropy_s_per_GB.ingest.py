"""Seconds of entropy coding per GB of user bytes taken in during the window,
summed over threads, from the program's stage counters (/stats
server.stages): ``zllm.entropy``, each tensor's zstd job on the pool's
threads."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.entropy")
