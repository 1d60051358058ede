"""Percent of the traced window in which the device was idle in gaps that
no program leaf (``gestore.*``) names: what the program's stages do not
account for."""
from chipbench.leafgaps import untraced_idle_share


def read(run):
    return untraced_idle_share(run)
