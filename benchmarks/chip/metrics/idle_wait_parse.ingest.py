"""Percent of the traced window in which the device was idle while the
ingest thread waited for the parser: idle gaps named after the program's
leaf ``gestore.ingest.wait_parse`` (``core/ingest.py``: the parse
queue's ``get``, or a parse future's result)."""
from chipbench.leafgaps import leaf_idle_share


def read(run):
    return leaf_idle_share(run, "ingest.wait_parse")
