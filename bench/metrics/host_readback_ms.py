"""Mean duration of the engine.readback span (the host's wait for a step's sampled tokens) per traced step."""
from harness import spans


def read(run):
    return spans.mean_ms(run, spans.READBACK)
