select id3, sum(v1) as v1, avg(v3) as v3 from {SCHEMA}.x group by id3 order by id3
