"""Milliseconds of the program's ``copy`` spans (each coherence copy the
ledger records, on any thread), summed over the window and divided by
its frames."""


def read(f):
    if not f.get("spans") or not f.get("frames"):
        return None
    d = [e[5] for e in f["spans"] if e[2] == "copy"]
    if not d:
        return None
    return 1e3 * sum(d) / f["frames"]
