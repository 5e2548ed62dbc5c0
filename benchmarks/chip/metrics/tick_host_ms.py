"""Host time of one engine tick, in milliseconds: the tracer's ``tick``
span split into host dispatch and harvest (``dispatch_us + harvest_us``),
less the fenced ``prefill`` spans that fall inside it, averaged over the
ticks of the traced window.  Layer: the engine tick
(``serving/engine.py::pump``, ``_postprocess``)."""


def read(r):
    ticks = [e for e in r.spans if e["name"] == "tick"]
    if not ticks:
        return None
    pre = [e for e in r.spans if e["name"] == "prefill"]
    total = 0.0
    for t in ticks:
        a, b = t["ts"], t["ts"] + t["dur"]
        inside = sum(p["dur"] for p in pre if a <= p["ts"] and p["ts"] + p["dur"] <= b)
        total += t["args"]["dispatch_us"] + t["args"]["harvest_us"] - inside
    return total / len(ticks) / 1e3
