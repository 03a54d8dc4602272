"""Window length over the arena-program steps it ran (each ends in
block_until_ready): host clock."""


def read(run):
    if not run.get("steps"):
        return None
    return 1e3 * run["window_s"] / run["steps"]
