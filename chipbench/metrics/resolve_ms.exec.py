"""Host milliseconds of input resolution in one ``execute`` call: the mean
of the program's ``repro.execute.resolve_inputs`` spans over the traced
steps (repro.obs)."""

from chipbench.bench.program_trace import mean_ms


def read(run):
    return mean_ms(run, "repro.execute.resolve_inputs")
