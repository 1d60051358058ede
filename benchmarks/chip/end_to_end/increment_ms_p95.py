"""95th percentile, in ms, of every increment called in the window, from
the call to its return (those that return after the close included)."""
from chipbench.readers import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 95)
