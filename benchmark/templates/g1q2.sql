select id1, id2, sum(v1) as v1 from {SCHEMA}.x group by id1, id2 order by id1, id2
