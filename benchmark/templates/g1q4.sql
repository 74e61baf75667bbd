select id4, avg(v1) as v1, avg(v2) as v2, avg(v3) as v3 from {SCHEMA}.x group by id4 order by id4
