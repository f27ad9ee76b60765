"""The DataFrame of upstream's "Aggregate w keys" (a copy of
`chip_smoke._linear_keys`): `pmod(id, groups)` equals `id & (groups-1)`
for a range from 0 and a power of two."""


def build(spark, config):
    from spark_tpu import functions as F
    from spark_tpu.functions import col
    return (spark.range(int(config["rows"]))
            .select(F.pmod(col("id"), int(config["groups"])).alias("k"))
            .group_by(col("k")).agg(F.sum(col("k")).alias("sum(k)")))
