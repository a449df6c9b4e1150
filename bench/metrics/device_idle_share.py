"""Share of the traced window with no operation running on the device."""
from harness import readers


def read(run):
    return readers.device_idle_share(run)
