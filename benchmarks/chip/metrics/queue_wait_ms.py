"""Time a request waits in the queue, in milliseconds: the mean of the
tracer's ``queue_wait`` spans (one per request, on its own track, from
submission to the start of its admission) that lie in the traced window.
Layer: admission and prefill (``serving/engine.py::submit`` ->
``_admit``)."""


def read(r):
    waits = [e["dur"] for e in r.spans if e["name"] == "queue_wait"]
    if not waits:
        return None
    return sum(waits) / len(waits) / 1e3
