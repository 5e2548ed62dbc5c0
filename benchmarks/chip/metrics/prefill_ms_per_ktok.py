"""Prefill time per 1000 prompt tokens, in milliseconds: the tracer's
fenced ``prefill`` spans (one per admitted request, out of band inside
the tick), summed, over the prompt tokens they covered.  Layer:
admission and prefill (``serving/engine.py::_admit`` ->
``serving/lm.py`` prefill)."""


def read(r):
    pre = [e for e in r.spans if e["name"] == "prefill"]
    tokens = sum(e["args"]["prompt_len"] for e in pre)
    if not tokens:
        return None
    return sum(e["dur"] for e in pre) / 1e3 / (tokens / 1e3)
