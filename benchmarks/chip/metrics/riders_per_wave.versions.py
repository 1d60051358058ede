"""Read requests per read wave: the front door's ``admitted`` over its
``read_waves`` counter. Above 1 when same-field requests ride one wave."""


def read(run):
    c = run.program["frontdoor"]["counters"]
    return c["admitted"] / c["read_waves"] if c["read_waves"] else None
