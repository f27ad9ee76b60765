"""mesh: how much fuller the fullest shard was than an even share of
the rows, over the window: growth of the counter `shard_rows_max` (of
every per-shard row vector a mesh stage's exchanges report, and of the
rows each shard folded over a mesh stream: the fullest shard's rows)
times the shards, over growth of `shard_rows_total` (all shards'
rows), less 1, in %. 0 where every shard held the same; 100 where one
held twice its share. The shards are the cell's chips. A program
without the counters, or a window in which no mesh stage ran, reads
nothing."""

FULLEST = "spark_tpu_shard_rows_max"
TOTAL = "spark_tpu_shard_rows_total"


def read(run):
    before, after = run["counters_before"], run["counters_after"]
    if FULLEST not in after or TOTAL not in after:
        return None
    total = after[TOTAL] - before.get(TOTAL, 0.0)
    fullest = after[FULLEST] - before.get(FULLEST, 0.0)
    if total <= 0:
        return None
    return 100.0 * (fullest * run["cell"].chips / total - 1.0)
