"""Milliseconds of the program's ``llm_prefill`` compute spans per prompt
token they took in."""


def read(f):
    spans, tokens = f.get("spans"), f.get("prompt_tokens")
    if not spans or not tokens:
        return None
    busy = n = 0
    for e in spans:
        if e[2] == "compute" and e[1] in tokens:
            busy += e[5]
            n += tokens[e[1]]
    if n == 0:
        return None
    return 1e3 * busy / n
