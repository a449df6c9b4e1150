"""Mean duration of the engine.enqueue span (the call of the jitted fused step) per traced dispatch."""
from harness import spans


def read(run):
    return spans.mean_ms(run, spans.ENQUEUE)
