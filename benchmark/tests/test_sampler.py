"""The parameter sampler on each query's domains."""
import pytest

import sampler
from cells import Query

QUERIES = ["tpch/q1", "tpch/q6", "tpch/q12", "tpch/q14"]


@pytest.mark.parametrize("name", QUERIES)
def test_same_seed_same_draws_and_seeds_differ(name):
    q = Query(name)
    draws = lambda seed: [sampler.draw(q.parameters, sampler.rng(seed, name, i))  # noqa: E731
                          for i in range(40)]
    assert draws(3000000001) == draws(3000000001)
    assert draws(3000000001) != draws(3000000002)


@pytest.mark.parametrize("name", QUERIES)
def test_every_draw_inside_the_spec_domain(name):
    q = Query(name)
    seen = set()
    for i in range(300):
        v = sampler.draw(q.parameters, sampler.rng(2**31 + 5, name, i))
        assert sampler.in_domain(q.parameters, v), v
        seen.add(tuple(sorted(v.items())))
    assert len(seen) > 10      # the draws do spread over the domain
    for name_, spec in q.parameters.items():     # a value off its domain
        off = {"integer": 10**6, "decimal": "0.005", "date": "2001-02-03",
               "choice": "NO SUCH VALUE"}.get(spec["kind"], "2001-02-03")
        assert not sampler.in_domain(q.parameters, dict(v, **{name_: off}))


def test_q6_text_and_prepared_form_carry_the_same_literals():
    q = Query("tpch/q6")
    v = {"DATE": "1994-01-01", "DISCOUNT": "0.06", "QUANTITY": 24,
         "DATE_END": "1995-01-01", "DISCOUNT_LOW": "0.05",
         "DISCOUNT_HIGH": "0.07"}
    assert sampler.in_domain(q.parameters, v)
    text = sampler.inline(q, v)
    assert "l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'" in text
    assert "between 0.05 and 0.07 and l_quantity < 24" in text
    assert sampler.prepare_statement(q).count("?") == 5
    assert sampler.execute_statement(q, v) == (
        "execute tpch_q6 using date '1994-01-01', date '1995-01-01', "
        "0.05, 0.07, 24")


def test_q12_ship_modes_differ_and_q1_delta_is_subtracted():
    q12, q1 = Query("tpch/q12"), Query("tpch/q1")
    for i in range(100):
        v = sampler.draw(q12.parameters, sampler.rng(9, i))
        assert v["SHIPMODE1"] != v["SHIPMODE2"]
    v = sampler.draw(q1.parameters, sampler.rng(1, 2))
    assert sampler.in_domain(q1.parameters, v)
    assert sampler.in_domain(q1.parameters, {"DELTA": 90, "SHIPDATE_MAX": "1998-09-02"})
    assert not sampler.in_domain(q1.parameters, {"DELTA": 90, "SHIPDATE_MAX": "1998-09-03"})


def test_plan_is_fixed_by_the_seed_and_pool_by_the_mix():
    import load
    from cells import read_json, BENCH
    traffic = read_json(BENCH, "traffic", "scan-power.json")
    queries = {t: Query(t) for t in traffic["templates"]}
    a, b = load.Plan(traffic, queries, 11), load.Plan(traffic, queries, 12)
    assert a.pool == b.pool                      # same tuples for every seed
    assert all(len(p) == traffic["parameters"]["pool_size"] for p in a.pool.values())
    seq = lambda p: [p.request(0, i)[2] for i in range(24)]  # noqa: E731
    assert seq(a) == seq(load.Plan(traffic, queries, 11))
    assert seq(a) != seq(b)                      # another order
    assert [a.template(0, i) for i in range(4)] == ["tpch/q6", "tpch/q1"] * 2
