"""1 - device busy union / traced window, in percent."""

from chipbench.bench.readers import idle_share


def read(run):
    return idle_share(run)
