"""Submit a task list built by a ``repro.apps`` builder through a
Session: the one way tasks reach a PE."""


def submit_tasks(s, tasks, *, one_at_a_time=False):
    """Submit ``tasks`` (built on ``s.context``) to session ``s`` in
    order, then wait for the stream to drain.  ``one_at_a_time`` waits
    for each task before submitting the next — one task in flight and
    one task per launch, on the same placement — the reference the
    concurrent stream is held to."""
    for t in tasks:
        s.submit(t.op, t.inputs, out=t.outputs, name=t.name, pin=t.pin,
                 **t.params)
        if one_at_a_time:
            s.barrier()
    s.barrier()
