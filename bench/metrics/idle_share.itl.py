"""Device idle share (``idle_share``) in open-loop cells, where the
gaps between tokens, not the tokens per second, are what it moves."""
from bench.metrics.idle_share import read  # noqa: F401
