"""Entries in every version read answered inside the window, over the
window's seconds."""


def read(run):
    return sum(r.entries for r in run.done_in_window()) / run.seconds
