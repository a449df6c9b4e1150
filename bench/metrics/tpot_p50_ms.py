"""Median over requests due in the window of (last - first token time) / (tokens - 1)."""
from harness import readers


def read(run):
    return readers.tpot_ms(run, 50)
