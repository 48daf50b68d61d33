"""Seconds of sha256 hashing per GB of user bytes taken in during the window,
summed over threads, from the program's stage counters (/stats
server.stages): ``zllm.hash.file`` (the whole file, stage A) and
``zllm.hash.tensor`` (each tensor, on the pool's threads)."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.hash.file", "zllm.hash.tensor")
