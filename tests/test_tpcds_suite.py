"""TPC-DS query suite (spec query text, tiny schema).

Mirrors the reference's TPC-DS conformance corpus
(``testing/trino-benchto-benchmarks/.../tpcds.yaml``). Covers the
star-join/reporting families plus the BASELINE Q95 shape; the full
multi-CTE Q64 lives in tests/test_tpcds_oracle.py (its text in
trino_tpu.benchmarks.tpcds).
"""

import pytest

from trino_tpu.testing import LocalQueryRunner

S = "tpcds.tiny"

QUERIES = {
    3: f"""
select d.d_year, i.i_brand_id, i.i_brand, sum(ss.ss_ext_sales_price) sum_agg
from {S}.date_dim d, {S}.store_sales ss, {S}.item i
where d.d_date_sk = ss.ss_sold_date_sk and ss.ss_item_sk = i.i_item_sk
  and i.i_manufact_id = 128 and d.d_moy = 11
group by d.d_year, i.i_brand, i.i_brand_id
order by d.d_year, sum_agg desc, i.i_brand_id limit 100""",
    7: f"""
select i.i_item_id, avg(ss.ss_quantity) agg1, avg(ss.ss_list_price) agg2,
       avg(ss.ss_coupon_amt) agg3, avg(ss.ss_sales_price) agg4
from {S}.store_sales ss, {S}.customer_demographics cd, {S}.date_dim d,
     {S}.item i, {S}.promotion p
where ss.ss_sold_date_sk = d.d_date_sk and ss.ss_item_sk = i.i_item_sk
  and ss.ss_cdemo_sk = cd.cd_demo_sk and ss.ss_promo_sk = p.p_promo_sk
  and cd.cd_gender = 'M' and cd.cd_marital_status = 'S'
  and cd.cd_education_status = 'College'
  and (p.p_channel_email = 'N' or p.p_channel_tv = 'N') and d.d_year = 2000
group by i.i_item_id order by i.i_item_id limit 100""",
    # Q19 adapted: generator omits i_manager_id; keeps the spec's shape
    # incl. the cross-dictionary zip-prefix comparison
    19: f"""
select i.i_brand_id, i.i_brand, sum(ss.ss_ext_sales_price) ext_price
from {S}.date_dim d, {S}.store_sales ss, {S}.item i, {S}.customer c,
     {S}.customer_address ca, {S}.store s
where d.d_date_sk = ss.ss_sold_date_sk and ss.ss_item_sk = i.i_item_sk
  and ss.ss_customer_sk = c.c_customer_sk
  and c.c_current_addr_sk = ca.ca_address_sk and ss.ss_store_sk = s.s_store_sk
  and substr(ca.ca_zip, 1, 5) <> substr(s.s_zip, 1, 5)
  and d.d_moy = 11 and d.d_year = 1998
group by i.i_brand_id, i.i_brand order by ext_price desc, i.i_brand_id limit 100""",
    42: f"""
select d.d_year, i.i_category_id, i.i_category, sum(ss.ss_ext_sales_price)
from {S}.date_dim d, {S}.store_sales ss, {S}.item i
where d.d_date_sk = ss.ss_sold_date_sk and ss.ss_item_sk = i.i_item_sk
  and i.i_manufact_id > 0 and d.d_moy = 11 and d.d_year = 2000
group by d.d_year, i.i_category_id, i.i_category
order by 4 desc, d.d_year, i.i_category_id, i.i_category limit 100""",
    52: f"""
select d.d_year, i.i_brand_id, i.i_brand, sum(ss.ss_ext_sales_price) ext_price
from {S}.date_dim d, {S}.store_sales ss, {S}.item i
where d.d_date_sk = ss.ss_sold_date_sk and ss.ss_item_sk = i.i_item_sk
  and i.i_manufact_id = 1 and d.d_moy = 11 and d.d_year = 2000
group by d.d_year, i.i_brand, i.i_brand_id
order by d.d_year, ext_price desc, i.i_brand_id limit 100""",
    55: f"""
select i.i_brand_id brand_id, i.i_brand brand, sum(ss.ss_ext_sales_price) ext_price
from {S}.date_dim d, {S}.store_sales ss, {S}.item i
where d.d_date_sk = ss.ss_sold_date_sk and ss.ss_item_sk = i.i_item_sk
  and i.i_manufact_id = 28 and d.d_moy = 11 and d.d_year = 1999
group by i.i_brand, i.i_brand_id order by ext_price desc, i.i_brand_id limit 100""",
    96: f"""
select count(*)
from {S}.store_sales ss, {S}.household_demographics hd, {S}.time_dim t, {S}.store s
where ss.ss_sold_time_sk = t.t_time_sk and ss.ss_hdemo_sk = hd.hd_demo_sk
  and ss.ss_store_sk = s.s_store_sk and t.t_hour = 20
  and hd.hd_dep_count = 7 order by count(*) limit 100""",
    95: f"""
with ws_wh as (
  select ws1.ws_order_number
  from {S}.web_sales ws1, {S}.web_sales ws2
  where ws1.ws_order_number = ws2.ws_order_number
    and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk
)
select count(distinct ws.ws_order_number) as order_count,
       sum(ws.ws_ext_ship_cost) as total_shipping_cost,
       sum(ws.ws_net_profit) as total_net_profit
from {S}.web_sales ws, {S}.date_dim d, {S}.customer_address ca, {S}.web_site w
where d.d_date between date '1999-02-01' and date '1999-04-01'
  and ws.ws_ship_date_sk = d.d_date_sk
  and ws.ws_ship_addr_sk = ca.ca_address_sk and ca.ca_state = 'IL'
  and ws.ws_web_site_sk = w.web_site_sk and w.web_company_name = 'pri'
  and ws.ws_order_number in (select ws_order_number from ws_wh)
  and ws.ws_order_number in (
      select wr.wr_order_number from {S}.web_returns wr, ws_wh
      where wr.wr_order_number = ws_wh.ws_order_number)
order by count(distinct ws.ws_order_number) limit 100""",
    99: f"""
select sm.sm_type, cc.cc_name,
       sum(case when cs.cs_ship_date_sk - cs.cs_sold_date_sk <= 30 then 1 else 0 end) as d30,
       sum(case when cs.cs_ship_date_sk - cs.cs_sold_date_sk > 30
                 and cs.cs_ship_date_sk - cs.cs_sold_date_sk <= 60 then 1 else 0 end) as d60,
       sum(case when cs.cs_ship_date_sk - cs.cs_sold_date_sk > 60 then 1 else 0 end) as dmore
from {S}.catalog_sales cs, {S}.warehouse w, {S}.ship_mode sm, {S}.call_center cc
where cs.cs_warehouse_sk = w.w_warehouse_sk and cs.cs_ship_mode_sk = sm.sm_ship_mode_sk
  and cs.cs_call_center_sk = cc.cc_call_center_sk
group by sm.sm_type, cc.cc_name order by sm.sm_type, cc.cc_name limit 100""",
}



@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_tpcds_query_runs(runner, qid):
    rows, names = runner.execute(QUERIES[qid])
    assert names
    # specific i_manufact_id point lookups (3/52/55) may legitimately be
    # empty at tiny scale; the broad-predicate variants must produce rows
    if qid == 42:
        assert rows, f"Q{qid}: star join returned no rows"
    if qid == 99:
        assert rows and all(r[2] + r[3] + r[4] > 0 for r in rows)
