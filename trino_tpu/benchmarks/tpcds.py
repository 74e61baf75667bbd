"""TPC-DS benchmark queries (spec text), parameterized by schema.

Reference: ``testing/trino-benchto-benchmarks/src/main/resources/benchmarks/
presto/tpcds.yaml`` — here the BASELINE config-3 pair (Q64/Q95), the
conformance corpus of tests/test_tpcds_oracle.py. Constants are adapted
to the tiny generator domains where noted in the test corpus.
"""


def queries(schema: str = "tpcds.tiny") -> dict[int, str]:
    S = schema
    q64 = f"""
with cs_ui as (
  select cs_item_sk,
         sum(cs_ext_list_price) as sale,
         sum(cr_refunded_cash + cr_reversed_charge + cr_store_credit)
           as refund
  from {S}.catalog_sales, {S}.catalog_returns
  where cs_item_sk = cr_item_sk and cs_order_number = cr_order_number
  group by cs_item_sk
  having sum(cs_ext_list_price) >
         2 * sum(cr_refunded_cash + cr_reversed_charge + cr_store_credit)),
cross_sales as (
  select i_product_name product_name, i_item_sk item_sk,
         s_store_name store_name, s_zip store_zip,
         ad1.ca_street_number b_street_number,
         ad1.ca_street_name b_street_name,
         ad1.ca_city b_city, ad1.ca_zip b_zip,
         ad2.ca_street_number c_street_number,
         ad2.ca_street_name c_street_name,
         ad2.ca_city c_city, ad2.ca_zip c_zip,
         d1.d_year as syear, d2.d_year as fsyear, d3.d_year s2year,
         count(*) cnt,
         sum(ss_wholesale_cost) s1, sum(ss_list_price) s2,
         sum(ss_coupon_amt) s3
  from {S}.store_sales, {S}.store_returns, cs_ui,
       {S}.date_dim d1, {S}.date_dim d2, {S}.date_dim d3,
       {S}.store, {S}.customer,
       {S}.customer_demographics cd1, {S}.customer_demographics cd2,
       {S}.promotion,
       {S}.household_demographics hd1, {S}.household_demographics hd2,
       {S}.customer_address ad1, {S}.customer_address ad2,
       {S}.income_band ib1, {S}.income_band ib2, {S}.item
  where ss_store_sk = s_store_sk and ss_sold_date_sk = d1.d_date_sk
    and ss_customer_sk = c_customer_sk and ss_cdemo_sk = cd1.cd_demo_sk
    and ss_hdemo_sk = hd1.hd_demo_sk and ss_addr_sk = ad1.ca_address_sk
    and ss_item_sk = i_item_sk
    and ss_item_sk = sr_item_sk and ss_ticket_number = sr_ticket_number
    and ss_item_sk = cs_ui.cs_item_sk
    and c_current_cdemo_sk = cd2.cd_demo_sk
    and c_current_hdemo_sk = hd2.hd_demo_sk
    and c_current_addr_sk = ad2.ca_address_sk
    and c_first_sales_date_sk = d2.d_date_sk
    and c_first_shipto_date_sk = d3.d_date_sk
    and ss_promo_sk = p_promo_sk
    and hd1.hd_income_band_sk = ib1.ib_income_band_sk
    and hd2.hd_income_band_sk = ib2.ib_income_band_sk
    and cd1.cd_marital_status <> cd2.cd_marital_status
    and i_color in ('purple', 'gold', 'red', 'cyan', 'blue', 'green')
    and i_current_price between 20 and 120
    and i_current_price between 21 and 130
  group by i_product_name, i_item_sk, s_store_name, s_zip,
           ad1.ca_street_number, ad1.ca_street_name, ad1.ca_city,
           ad1.ca_zip, ad2.ca_street_number, ad2.ca_street_name,
           ad2.ca_city, ad2.ca_zip, d1.d_year, d2.d_year, d3.d_year)
select cs1.product_name, cs1.store_name, cs1.store_zip,
       cs1.b_street_number, cs1.b_street_name, cs1.b_city, cs1.b_zip,
       cs1.c_street_number, cs1.c_street_name, cs1.c_city, cs1.c_zip,
       cs1.syear, cs1.cnt,
       cs1.s1 as s11, cs1.s2 as s21, cs1.s3 as s31,
       cs2.s1 as s12, cs2.s2 as s22, cs2.s3 as s32,
       cs2.syear as syear2, cs2.cnt as cnt2
from cross_sales cs1, cross_sales cs2
where cs1.item_sk = cs2.item_sk and cs1.syear = 2000
  and cs2.syear = 2000 + 1 and cs2.cnt <= cs1.cnt
  and cs1.store_name = cs2.store_name and cs1.store_zip = cs2.store_zip
order by cs1.product_name, cs1.store_name, cnt2, s11, s12"""
    q95 = f"""
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
order by count(distinct ws.ws_order_number) limit 100"""
    return {64: q64, 95: q95}
