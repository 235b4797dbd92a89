"""Mean self time of the program's ``submit`` spans (``Session.submit``):
each span's duration less its ``qos`` wait, the runtime's host cost of
one submission."""


def read(f):
    spans = f.get("spans")
    if not spans:
        return None
    submits = [e for e in spans if e[2] == "submit"]
    if not submits:
        return None
    qos = {e[6]["task"]: e[5] for e in spans if e[2] == "qos" and e[6]}
    own = sum(e[5] - qos.get(e[6]["task"], 0.0) for e in submits)
    return 1e6 * own / len(submits)
