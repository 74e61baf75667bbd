select l.l_orderkey, sum(l.l_extendedprice * (1 - l.l_discount)) as revenue,
       o.o_orderdate, o.o_shippriority
from {SCHEMA}.customer c, {SCHEMA}.orders o, {SCHEMA}.lineitem l
where c.c_mktsegment = '{SEGMENT}' and c.c_custkey = o.o_custkey
  and l.l_orderkey = o.o_orderkey and o.o_orderdate < date '{DATE}'
  and l.l_shipdate > date '{DATE}'
group by l.l_orderkey, o.o_orderdate, o.o_shippriority
order by revenue desc, o.o_orderdate limit 10
