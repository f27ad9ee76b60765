select
    1 as one,
    max_revenue
from (
    select
        max(total_revenue) as max_revenue
    from (
        select
            l_suppkey as supplier_no,
            sum(l_extendedprice * (1 - l_discount)) as total_revenue
        from
            lineitem
        where
            l_shipdate >= date '1996-01-01'
            and l_shipdate < date '1996-01-01' + interval '3' month
        group by
            l_suppkey
    ) revenue
) q15max
