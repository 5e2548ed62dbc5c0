"""Device time of the slot surgery and page growth programs per engine
tick, in milliseconds: the ``jit_paged_*`` and ``jit_slot_*`` programs
(install, scrub, copy, adopt, grow, fingerprints, damage) in the device
trace, averaged over the chips, over the tracer's ``tick`` spans in the
window.  Layer: slot surgery and paging (``serving/paging.py``,
``serving/slots.py``)."""

import re

PROGRAMS = r"^jit_(paged|slot)_"


def read(r):
    ticks = sum(1 for e in r.spans if e["name"] == "tick")
    if r.device is None or not ticks:
        return None
    if not any(re.search(PROGRAMS, k) for k in r.device.modules):
        return None
    secs = r.device.module_seconds(PROGRAMS) / r.device.devices
    return secs * 1e3 / ticks
