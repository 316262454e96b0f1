"""One general sampler of substitution parameters: a query's
`.params.json` gives each parameter's domain as the spec's clause states
it, and this draws from it.  The same seed gives the same draws."""
import datetime
import random
from decimal import Decimal

from cells import SLOT


def rng(*key) -> random.Random:
    """A generator of its own for every (seed, stream, position), so that
    a draw does not depend on which thread asked first."""
    return random.Random(":".join(str(k) for k in key))


def _draw_one(spec: dict, r: random.Random, drawn: dict):
    kind = spec["kind"]
    if kind == "integer":
        return r.randint(spec["min"], spec["max"])
    if kind == "decimal":
        lo, hi, step = (Decimal(spec[k]) for k in ("min", "max", "step"))
        return str(lo + step * r.randint(0, int((hi - lo) / step)))
    if kind == "date":          # the first day of a drawn month of a drawn year
        year = r.randint(*spec["years"])
        month = r.randint(*spec["months"])
        return datetime.date(year, month, 1).isoformat()
    if kind == "choice":
        values = [v for v in spec["values"]
                  if v != drawn.get(spec.get("distinct_from"))]
        return r.choice(values)
    # derived from a parameter drawn before it: draws nothing
    if kind == "date_minus_days":
        base = datetime.date.fromisoformat(spec["date"])
        return (base - datetime.timedelta(days=drawn[spec["days"]])).isoformat()
    if kind == "date_plus_months":
        d = datetime.date.fromisoformat(drawn[spec["of"]])
        months = d.year * 12 + d.month - 1 + spec["months"]
        return d.replace(year=months // 12, month=months % 12 + 1).isoformat()
    if kind == "decimal_plus":
        return str(Decimal(drawn[spec["of"]]) + Decimal(spec["plus"]))
    raise ValueError(f"unknown parameter kind {kind!r}")


def draw(parameters: dict, r: random.Random) -> dict:
    """Every parameter of one query, in the file's order (a derived or
    distinct parameter names one that comes before it)."""
    drawn = {}
    for name, spec in parameters.items():
        drawn[name] = _draw_one(spec, r, drawn)
    return drawn


def in_domain(parameters: dict, values: dict) -> bool:
    for name, spec in parameters.items():
        v, kind = values[name], spec["kind"]
        if kind == "integer":
            ok = isinstance(v, int) and spec["min"] <= v <= spec["max"]
        elif kind == "decimal":
            d = Decimal(v)
            ok = (Decimal(spec["min"]) <= d <= Decimal(spec["max"])
                  and (d - Decimal(spec["min"])) % Decimal(spec["step"]) == 0)
        elif kind == "date":
            d = datetime.date.fromisoformat(v)
            ok = (spec["years"][0] <= d.year <= spec["years"][1]
                  and spec["months"][0] <= d.month <= spec["months"][1]
                  and d.day == 1)
        elif kind == "choice":
            ok = v in spec["values"] \
                and v != values.get(spec.get("distinct_from"))
        else:
            ok = v == _draw_one(spec, None, values)
        if not ok:
            return False
    return True


def literal(query, name: str, values: dict) -> str:
    return query.parameters[name].get("literal", "{}").format(values[name])


def inline(query, values: dict) -> str:
    """The query's text with every slot replaced by its literal."""
    return SLOT.sub(lambda m: literal(query, m.group(1), values), query.sql)


def prepared_text(query) -> str:
    """The query's text with a ? for every slot."""
    return SLOT.sub("?", query.sql)


def prepare_statement(query) -> str:
    return f"prepare {query.prepared_name} from {prepared_text(query)}"


def execute_statement(query, values: dict) -> str:
    using = ", ".join(literal(query, s, values) for s in query.slots)
    return f"execute {query.prepared_name} using {using}"
