"""Percent of the traced window in which the device was idle while the
read path copied gathered values to the host: idle gaps named after the
program's leaf ``gestore.gather.copy`` (``core/store.py``
``gather_finalize``, which waits for the take and copies its result)."""
from chipbench.leafgaps import leaf_idle_share


def read(run):
    return leaf_idle_share(run, "gather.copy")
