"""Tests of the yardstick that need no chip: `python -m pytest
benchmark/tests -q` (CPU); `python -m benchmark.selftest unit` runs the same
cases, and tier-1 runs that."""
