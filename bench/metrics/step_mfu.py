"""Model FLOPs of the traced steps' live rows over traced window x peak bf16 FLOP/s."""
from harness import readers


def read(run):
    return readers.step_mfu(run)
