"""Process start to window start."""


def read(run):
    return run.setup_s
