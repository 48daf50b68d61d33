"""Seconds uploads waited in the ingest job queue per GB of user bytes taken in
during the window, summed over threads, from the program's stage counters
(/stats server.stages): ``zllm.job.queued``, from enqueue until the one job
worker picks the job up."""

from bench.stages import seconds_per_gb


def read(run):
    return seconds_per_gb(run, "zllm.job.queued")
