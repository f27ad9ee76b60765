"""executor: the widest join a request made: growth of the counter
`join_widest_rows` (each query adds the largest `join_rows_*` of its
stages, a creation-side semi-join of a runtime filter among them, PR
41) over the window's requests. What the join order decided, read
where it lands: a whole number that a seed fixes. A program without
the counter reads nothing."""

COUNTER = "spark_tpu_join_widest_rows"


def read(run):
    if COUNTER not in run["counters_after"] or not run["requests"]:
        return None
    grown = run["counters_after"][COUNTER] \
        - run["counters_before"].get(COUNTER, 0.0)
    return grown / len(run["requests"])
