"""User bytes of every upload acknowledged 200 in the window, over the time
from the window's start to the last of those acknowledgements (MB = 1e6 B)."""

from bench.readers import acked_in_window


def read(run):
    acked = acked_in_window(run)
    if not acked:
        return None
    span = max(r["t_ack"] for r in acked) - run.t0
    return sum(r["bytes"] for r in acked) / span / 1e6
