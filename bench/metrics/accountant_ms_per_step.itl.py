"""Power accountant host time per engine step
(``accountant_ms_per_step``) in open-loop cells, where it lengthens the
gaps between tokens."""
from bench.metrics.accountant_ms_per_step import read  # noqa: F401
