"""Tasks per kernel launch on the accelerator PE: the window's compute
spans on its track over the distinct launches they name (``launch``,
one per launch of one task or of a batch of ready tasks)."""


def read(f):
    spans = f.get("spans")
    if not spans:
        return None
    track = f"pe:{f['acc']}"
    launches = [e[6]["launch"] for e in spans
                if e[2] == "compute" and e[3] == track and e[6] and "launch" in e[6]
                and not e[6].get("prefetch")]
    if not launches:
        return None
    return len(launches) / len(set(launches))
