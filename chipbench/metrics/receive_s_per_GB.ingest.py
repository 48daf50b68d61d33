"""Seconds the HTTP front spent receiving and spooling upload bodies per GB of
user bytes taken in during the window, summed over threads, from the
program's stage counters (/stats server.stages): ``zllm.http.receive``,
first body byte to body spooled."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.http.receive")
