"""Share of the traced window (%) in which no operation ran on the
device, averaged over the chips used."""
from bench.metrics._lib import idle_share


def read(run):
    return idle_share(run)
