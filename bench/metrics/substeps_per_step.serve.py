"""``llm_decode`` tasks the runtime ran per engine step that decoded: one
per tenant present in the batch."""


def read(f):
    if not f.get("decode_steps"):
        return None
    return f["decode_tasks"] / f["decode_steps"]
