select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= [DATE]
  and l_shipdate < [DATE_END]
  and l_discount between [DISCOUNT_LOW] and [DISCOUNT_HIGH]
  and l_quantity < [QUANTITY]
