"""executor: the rows the window's joins put out, per request: growth
of the counter `join_output_rows` (the sum of every dispatched stage's
`join_rows_*`: each join's true output rows, a creation-side semi-join
of a runtime filter among them) over the window's requests. A whole
number that a seed fixes: the join's work, as `exchange_mb_per_request`
is the exchange's. A program without the counter reads nothing."""

COUNTER = "spark_tpu_join_output_rows"


def read(run):
    if COUNTER not in run["counters_after"] or not run["requests"]:
        return None
    grown = run["counters_after"][COUNTER] \
        - run["counters_before"].get(COUNTER, 0.0)
    return grown / len(run["requests"])
