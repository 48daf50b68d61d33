"""Seconds spent committing uploads per GB of user bytes taken in during the
window, summed over threads, from the program's stage counters (/stats
server.stages): ``zllm.container.write`` (the container to disk) and
``zllm.index.save`` (the whole index rewritten)."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.container.write", "zllm.index.save")
