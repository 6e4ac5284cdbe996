"""The paged decode-attention kernel's share of its roofline: the K/V
bytes an average call has to read (pages the engine counted, a layer's
worth) over HBM bandwidth, against the kernel's mean time per call in
the trace. Memory-bound: one operation a byte."""
from benchmark import flops_bytes
from benchmark import kernel_events as kernels

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    r = run.records
    if run.trace is None or r["kind"] != "serve":
        return None
    sec, calls = kernels.seconds_and_calls(run.trace, "paged_attention")
    iters = r["counters"]["decode_iterations"]
    if not calls or iters <= 0:
        return None
    m = flops_bytes.dims(run.config)
    page = int(r["serving"]["page_size"])
    pages = r["counters"]["kv_pages_read"] / iters   # per call (a layer)
    flops, nbytes = flops_bytes.paged_decode(
        pages * page, pages, page, m["heads"], m["head_dim"])
    least, _bound = flops_bytes.roofline_seconds(flops, nbytes, run.peaks)
    return 100.0 * least / (sec / calls)
