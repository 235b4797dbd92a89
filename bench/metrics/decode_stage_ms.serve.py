"""Mean of the program's ``stage`` spans of ``llm_decode`` tasks: staging
the token, position and table buffers and the KV page groups."""


def read(f):
    spans = f.get("spans")
    if not spans:
        return None
    d = [e[5] for e in spans if e[2] == "stage" and e[1].startswith("llm_decode")]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
