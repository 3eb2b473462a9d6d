"""masked_rerank_roofline: percent of pass 2's least time, the larger of
2*q*n*d flops over the chip's peak flops and the corpus, norms and codes
read once over its peak bytes/s, in the device time of the
``masked_rerank`` Pallas kernel."""
from tacobench import counts

#: the kernel's op name in the trace (``%masked_rerank_pallas.1 = ... custom-call``)
KERNEL = ("masked_rerank_pallas",)


def read(run):
    if run.trace is None or run.peak is None:
        return None
    secs, launches = run.trace.kernel_seconds(*KERNEL)
    batches = run.counters.get("taco_engine_batches_total", 0)
    if not launches or secs <= 0 or not batches:
        return None
    q = run.counters.get("taco_engine_requests_total{outcome=executed}", 0) / batches
    s = run.shape
    least = counts.least_seconds(
        counts.rerank_flops(q, s["n"], s["d"]),
        counts.rerank_bytes(q, s["n"], s["d"], s["n_sub"], s["sqrt_k"]),
        run.peak)
    return 100.0 * launches * least / secs
