"""The whole step's share of the chip's bf16 peak (``serve_mfu``), read
beside the decode roofline in every cell, where the gaps between tokens
are what it moves."""
from bench.metrics.serve_mfu import read  # noqa: F401
