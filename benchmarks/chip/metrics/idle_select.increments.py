"""Percent of the traced window in which the device was idle inside the
scan's selection: idle gaps named after the program's leaf
``gestore.scan.select`` (``core/store.py`` ``boundary_cums``: argument
uploads, the scan kernel, the boundary take and where, and the copy of
the counts to the host)."""
from chipbench.leafgaps import leaf_idle_share


def read(run):
    return leaf_idle_share(run, "scan.select")
