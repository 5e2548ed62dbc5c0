"""Roofline share of the paged decode attention kernel, in percent: the
least time the chip needs for the live context of every slot it decoded
(keys and values of each attended position once, plus q and o: bound by
HBM bandwidth, the operations being few), over the kernel's summed
device time in the trace.  Layer: kernels
(``kernels/paged_decode.py::paged_gqa_attention``)."""

KERNEL = r"paged_gqa_attention"


def read(r):
    if r.device is None or not r.book:
        return None
    secs = r.device.op_seconds(KERNEL)
    if secs <= 0:
        return None
    ref = r.reference
    nbytes = flops = 0.0
    for t in r.book:
        for keys, slots in t.decode:
            nbytes += slots * ref.paged_attention_bytes(r.model, keys)
            flops += slots * ref.paged_attention_flops(r.model, keys)
    least = max(nbytes / r.peak["hbm_bytes_per_s"], flops / r.peak["bf16_flops_per_s"])
    return 100.0 * least / secs
