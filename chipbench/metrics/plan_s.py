"""Host seconds of the cell's cold plan() or plan_decode_arena() call."""


def read(run):
    return run.get("plan_s")
