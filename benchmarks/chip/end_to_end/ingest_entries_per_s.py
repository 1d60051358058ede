"""Entries whose ingest wave was journaled and applied by the window's
close (the program's ``ingest.entries_routed`` counter, counted from the
window's opening), over the window's seconds. Counting by wave keeps
release boundaries from quantizing the rate."""


def read(run):
    return run.at_close["entries_routed"] / run.seconds
