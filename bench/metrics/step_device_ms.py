"""Device time per run of the jitted fused paged step, from the trace."""
from harness import readers


def read(run):
    return readers.step_device_ms(run)
