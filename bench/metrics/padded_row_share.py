"""Share of the rows the window's fused steps computed that were padding."""
from harness import readers


def read(run):
    return readers.padded_row_share(run)
