"""Seconds from the start of the process to the opening of the window:
JAX start-up, data generation, the base upload, warm-up and compiles."""


def read(run):
    return run.setup_s
