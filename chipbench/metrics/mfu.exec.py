"""Steps times the least time of one step under the roofline, over the
window, in percent.  The surrogate numerics do no matrix products, so the
memory bound binds: every tensor written once and read once at the
chip's HBM bandwidth."""


def read(run):
    if not run.get("steps"):
        return None
    return 100.0 * run["least_s"] / run["window_s"]
