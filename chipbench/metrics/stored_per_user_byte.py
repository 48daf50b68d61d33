"""Bytes the store's root grew by over the window (containers, index; not
the upload spool or the response cache's spill), over the user bytes of
every upload sent in the window and acknowledged. Both are read after the
last acknowledgement, when nothing is in flight."""


def read(run):
    acked = [r for r in run.records if r["status"] == 200]
    if not acked:
        return None
    return (run.stored1 - run.stored0) / sum(r["bytes"] for r in acked)
