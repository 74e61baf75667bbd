select n.n_name, sum(l.l_extendedprice * (1 - l.l_discount)) as revenue
from {SCHEMA}.customer c, {SCHEMA}.orders o, {SCHEMA}.lineitem l,
     {SCHEMA}.supplier s, {SCHEMA}.nation n, {SCHEMA}.region r
where c.c_custkey = o.o_custkey and l.l_orderkey = o.o_orderkey
  and l.l_suppkey = s.s_suppkey and c.c_nationkey = s.s_nationkey
  and s.s_nationkey = n.n_nationkey and n.n_regionkey = r.r_regionkey
  and r.r_name = '{REGION}' and o.o_orderdate >= date '{DATE}'
  and o.o_orderdate < date '{DATE}' + interval '1' year
group by n.n_name
order by revenue desc
