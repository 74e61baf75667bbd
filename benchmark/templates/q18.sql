select c.c_name, c.c_custkey, o.o_orderkey, o.o_orderdate, o.o_totalprice,
       sum(l.l_quantity)
from {SCHEMA}.customer c, {SCHEMA}.orders o, {SCHEMA}.lineitem l
where o.o_orderkey in (
    select l_orderkey from {SCHEMA}.lineitem
    group by l_orderkey having sum(l_quantity) > {QUANTITY})
  and c.c_custkey = o.o_custkey and o.o_orderkey = l.l_orderkey
group by c.c_name, c.c_custkey, o.o_orderkey, o.o_orderdate, o.o_totalprice
order by o.o_totalprice desc, o.o_orderdate limit 100
