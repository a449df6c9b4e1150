"""Host clock around the dense-to-CMoE conversion in set-up."""


def read(run):
    return run.convert_s
