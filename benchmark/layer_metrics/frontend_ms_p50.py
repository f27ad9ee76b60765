"""HTTP front end and codec: per request, the front end's spans on the
handler's thread: `http.accept` (the accept to the handler's first
line: its thread started, the request line and the headers parsed),
`http.read` (the body read and parsed), `parse` (the text to a
`QueryExecution`), `finish` (the query's return to the answer's
encoding: session and slot released, the record's bookkeeping, the
service's event), `encode` (the rows to JSON or an Arrow stream) and
`http.write` (status line, headers, body); the median over requests.
`queue` (quota, pool, session lock, admission slot) is not among them.
A program without these spans (before PR 39) reads nothing."""

from benchmark.layer_metrics import span_sums


def read(run):
    return span_sums.median_ms(run, "http.accept", "http.read", "parse",
                               "finish", "encode", "http.write")
