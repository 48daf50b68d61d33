"""The on-chip benchmark's own code: data generation, load clients, the
run of one cell, trace reduction and statistics. It imports nothing of the
program except the system under test, in ``harness``."""
