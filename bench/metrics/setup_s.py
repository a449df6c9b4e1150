"""Set-up: process start to the first released request (host clock)."""


def read(run):
    return run.setup_s
