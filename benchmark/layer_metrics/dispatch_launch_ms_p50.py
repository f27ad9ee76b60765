"""executor: per request, the `dispatch.launch` spans (the compiled
stage's call returning: on a warm stage the enqueue of its program);
the median over requests."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "dispatch.launch")
