"""Least time the traced moe_gmm_ragged calls need over their device time."""
from harness import readers


def read(run):
    return readers.moe_gmm_roofline(run)
