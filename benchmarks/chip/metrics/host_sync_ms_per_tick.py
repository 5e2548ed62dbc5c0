"""Host time blocked on device-to-host reads per engine tick, in
milliseconds: the tracer's ``sync.*`` spans (page state, join position,
first token, tokens, speculative commits, fingerprints, damage) summed,
over the ``tick`` spans of the traced window.  ``sync.step_reports`` is
left out: it waits for the step itself.  Layer: the engine tick
(``serving/engine.py::_sync``, ``serving/paging.py::pre_tick``)."""

WAITS_FOR_STEP = "sync.step_reports"


def read(r):
    ticks = sum(1 for e in r.spans if e["name"] == "tick")
    syncs = [e for e in r.spans if e["name"].startswith("sync.")]
    if not ticks or not syncs:
        return None
    blocked = sum(e["dur"] for e in syncs if e["name"] != WAITS_FOR_STEP)
    return blocked / 1e3 / ticks
