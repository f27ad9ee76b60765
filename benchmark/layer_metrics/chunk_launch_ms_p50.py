"""executor: per request, the sum of the `chunk.launch` spans (the
dictionary guard and the chunk program's call returning: an enqueue,
not the device's time); the median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "chunk.launch")
