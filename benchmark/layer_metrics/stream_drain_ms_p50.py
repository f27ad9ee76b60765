"""executor: per request, the `stream.drain` span (from the last chunk's
launch to the stream's result ready: how far the device and the
transfers lag the host at the stream's end); the median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "stream.drain")
