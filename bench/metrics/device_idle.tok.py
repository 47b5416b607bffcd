"""Share of the window in which no operation ran on the device, from the
device trace (mean over the chips used)."""
from harness.readers import idle_pct


def read(data):
    return idle_pct(data)
