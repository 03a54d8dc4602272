"""Host milliseconds of the arena program's call in one ``execute`` call,
up to its return (before ``block_until_ready``): the mean of the program's
``repro.execute.dispatch`` spans over the traced steps (repro.obs)."""

from chipbench.bench.program_trace import mean_ms


def read(run):
    return mean_ms(run, "repro.execute.dispatch")
