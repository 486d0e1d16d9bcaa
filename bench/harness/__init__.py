"""The harness: cells, the system under test, the measured loop, reductions."""
