"""Share of engine ticks whose decode step wrote the decoder state (the
paged KV pool among it) over its own input buffers, in percent: the
tracer's ``tick`` spans with ``pool_in_place`` 1, over the ``tick`` spans
of the traced window.  Layer: the model step (``serving/engine.py::_swap``
choosing donation, ``models/transformer.py::decode_step`` carrying the
pool through the layer loop)."""


def read(r):
    ticks = [e for e in r.spans if e["name"] == "tick"]
    marked = [e["args"]["pool_in_place"] for e in ticks if "pool_in_place" in e["args"]]
    if not ticks or not marked:
        return None
    return 100.0 * sum(marked) / len(ticks)
