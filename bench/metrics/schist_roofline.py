"""schist_roofline: percent of pass 1's least time, HBM bytes of the codes
and tables over the chip's peak bytes/s (its compares and counts have no
published peak), in the device time of the ``schist`` Pallas kernel."""
from tacobench import counts

#: the kernel's op name in the trace (``%schist_pallas.1 = ... custom-call``)
KERNEL = ("schist_pallas",)


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
        0.0, counts.schist_bytes(q, s["n"], s["n_sub"], s["sqrt_k"]), run.peak)
    return 100.0 * launches * least / secs
