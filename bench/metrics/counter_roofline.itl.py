"""Counter kernel's share of its roofline (``counter_roofline``) in
open-loop cells, where its time lengthens the gaps between tokens."""
from bench.metrics.counter_roofline import read  # noqa: F401
