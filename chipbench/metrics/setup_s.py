"""Process start to window start: imports, planning, weights, compiles
and warm-up."""


def read(run):
    return run["setup_s"]
