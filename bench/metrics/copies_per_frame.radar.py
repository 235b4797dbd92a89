"""Coherence copies per frame: the ledger's copy count over the window's frames."""


def read(f):
    if not f.get("frames"):
        return None
    return f["copies"] / f["frames"]
