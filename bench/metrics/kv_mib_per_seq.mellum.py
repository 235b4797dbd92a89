"""Mean MiB of KV pages held per live sequence, over both pools: the
Session's ``kv/<pool>/pages_held`` counters (pages held by live
sequences, added once per decoding step) times each pool's page bytes,
over the sequence-steps of the window (tokens generated)."""


def read(f):
    kv = f.get("kv")
    if not kv or kv["seq_steps"] <= 0:
        return None
    held = sum(kv["page_steps"][p] * kv["page_bytes"][p] for p in kv["page_steps"])
    return held / kv["seq_steps"] / 2**20
