"""Tiny configurations and a CPU run of a cell, for the benchmark's tests.

The widths here are test sizes only; the benchmark's cells use the
published widths in ``configs/``. Importing this module puts the
benchmark's package and the program's ``src`` on the path."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

DENSE = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 1, "attention_bias": True,
         "norm_dtype": "F32", "initializer_range": 0.02}
MOE = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_hidden_layers": 1, "num_local_experts": 2,
       "initializer_range": 0.02}


def run_tiny(traffic_name: str, workdir: str, *, config=None, seconds=2.0,
             seed=2**33 + 7, patch=None, **traffic_over):
    """One run of a cell at test size on the CPU; returns (run, correct)."""
    from bench import harness, registry
    traffic = {**registry.traffic(traffic_name), "uploads_per_client": 2,
               **traffic_over}
    cfg = config or (MOE if "attn" in traffic_name else DENSE)
    cell = {"name": "tiny." + traffic_name, "config": "tiny",
            "traffic": traffic_name, "chips": 1}
    run = harness.run_cell(cell, cfg, traffic, seed, seconds, workdir,
                           patch=patch, log=lambda m: None, gen_threads=2)
    return run, harness.correct(run.checks)
