"""Milliseconds of the program's ``stage`` spans (staging a task's inputs
on its PE), summed over the window and divided by its frames."""


def read(f):
    if not f.get("spans") or not f.get("frames"):
        return None
    total = sum(e[5] for e in f["spans"] if e[2] == "stage")
    return 1e3 * total / f["frames"]
