"""Mean host time per fused dispatch in the window (plan, snapshot, enqueue)."""
from harness import readers


def read(run):
    return readers.host_plan_ms(run)
