"""The on-chip benchmark: one data-driven harness (``bench/run.py``) and
the yardstick it measures with (generator, references, trace reduction,
metric readers).  Nothing here is imported by the program under test."""
