"""Host milliseconds of one ``repro.core.execute`` call: the mean of the
program's ``repro.execute`` spans over the traced steps (repro.obs)."""

from chipbench.bench.program_trace import mean_ms


def read(run):
    return mean_ms(run, "repro.execute")
