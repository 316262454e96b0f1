"""Decides `correct`: what the clients fetched in the window against the
plain reference's answer to the same parameters, row for row, exactly."""
import importlib

import sampler


def sample(requests, size: int, seed: int):
    """The completed requests to compare: all of them where they are few,
    else `size` drawn from the seed, the slowest among them."""
    done = [r for r in requests if r["ok"]]
    if len(done) <= size:
        return done
    slowest = max(done, key=lambda r: r["wall_s"])
    rest = [r for r in done if r is not slowest]
    return [slowest] + sampler.rng(seed, "check").sample(rest, size - 1)


class Reference:
    """The reference's tables, made once, and its answers, each computed
    once per distinct (query, parameters)."""

    def __init__(self, queries: dict, sf: float):
        wanted = {}
        for q in queries.values():
            suite = q.name.split("/")[0]
            for table, cols in q.tables.items():
                wanted.setdefault(suite, {}).setdefault(table, []).extend(cols)
        self.tables = {
            suite: importlib.import_module(f"reference.{suite}_data")
            .tables(w, sf) for suite, w in wanted.items()}
        self.memo, self.answers = {}, {}
        self.memo_short, self.short = {}, {}

    def answer(self, template: str, values: dict, control=None):
        """The reference's answer; with `control`, the answer of the
        reference with one guarantee of the configuration broken:
        "float32_sums" accumulates in float32 where decimals are exact,
        "scan_stops_a_batch_short" leaves the last 1/1024 of the query's
        largest table unread (one 64K-row batch of lineitem at SF10)."""
        key = (template, tuple(sorted(values.items())), control)
        if key not in self.answers:
            suite, query = template.split("/")
            module = importlib.import_module(f"reference.{suite}.{query}")
            tables, memo = self.tables[suite], self.memo
            if control == "scan_stops_a_batch_short":
                tables, memo = self._short(suite), self.memo_short
            elif control not in (None, "float32_sums"):
                raise ValueError(f"unknown control {control!r}")
            self.answers[key] = module.answer(
                tables, values, memo, control == "float32_sums")
        return self.answers[key]

    def _short(self, suite: str):
        if suite not in self.short:
            full = self.tables[suite]
            largest = max(full, key=lambda t: len(next(iter(full[t].values()))))
            n = len(next(iter(full[largest].values())))
            self.short[suite] = dict(full, **{largest: {
                c: a[:n - max(1, n // 1024)] for c, a in full[largest].items()}})
        return self.short[suite]


def compare(requests, picked, reference: Reference, control=None) -> dict:
    """The numbers compared, each with its limit.  With `control`, the
    reference with a guarantee broken stands in the program's place."""
    wrong, first = 0, None
    for r in picked:
        want = reference.answer(r["template"], r["values"])
        got = reference.answer(r["template"], r["values"], control) \
            if control else r["rows"]
        if [list(row) for row in got] != [list(row) for row in want]:
            wrong += 1
            first = first or {"template": r["template"],
                              "values": r["values"],
                              "got": str(got)[:400], "want": str(want)[:400]}
    failed = sum(1 for r in requests if not r["ok"])
    numbers = {
        "answers_compared": {"value": len(picked), "limit": 1,
                             "holds": len(picked) >= 1},
        "answers_wrong": {"value": wrong, "limit": 0, "holds": wrong == 0},
        "requests_failed": {"value": failed, "limit": 0,
                            "holds": failed == 0},
    }
    return {"numbers": numbers, "first_wrong": first,
            "correct": all(n["holds"] for n in numbers.values())}
