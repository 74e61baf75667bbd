select id1, sum(v1) as v1 from {SCHEMA}.x group by id1 order by id1
