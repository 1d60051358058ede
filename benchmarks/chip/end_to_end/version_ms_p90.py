"""90th percentile, in ms, of every version read submitted in the window,
from ``FrontDoor.submit`` to ``result()`` (those answered after the close
included)."""
from chipbench.readers import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 90)
