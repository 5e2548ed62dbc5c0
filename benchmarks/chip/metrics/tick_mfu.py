"""Model operations of the tokens the engine processed, over the chips'
bf16 peak for the ticks' summed wall time, in percent.  Counted: every
prompt token admitted and every token decoded, once per request (replica
rows are not useful work), with attention over the keys each one reads.
Layer: the model step (``models/``, ``serving/lm.py``)."""


def read(r):
    if not r.book:
        return None
    ref = r.reference
    flops = 0.0
    for t in r.book:
        flops += sum(ref.prefill_flops(r.model, n) for n in t.prefill)
        flops += sum(ref.decode_flops(r.model, keys) for keys, _ in t.decode)
    secs = sum(t.t1 - t.t0 for t in r.book)
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / (secs * r.chips * r.peak["bf16_flops_per_s"])
