"""Bytes written to storage per ingested entry: the program's
``storage.bytes_written`` counter (every file the store and the ingest
journal write: segments, manifests, segment index, journal chunks and
manifest) over ``ingest.entries_routed``. The op clears the registry
after warm-up, so both count the window and its drain."""


def read(run):
    from repro.obs import REGISTRY
    written = REGISTRY.snapshot().get("storage.bytes_written")
    entries = run.program.get("entries_routed")
    if written is None or not entries:
        return None
    return written / entries
