select id6, sum(v1) as v1, sum(v2) as v2, sum(v3) as v3 from {SCHEMA}.x group by id6 order by id6
