"""Finds a cell's files by the names BENCHMARK.json gives: the
configuration, the traffic mix, the queries and their parameter domains.
Nothing here knows a particular cell."""
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SLOT = re.compile(r"\[([A-Z][A-Z0-9_]*)\]")


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Query:
    """One query file and its parameter domains (queries/<suite>/<q>.*)."""

    def __init__(self, name: str):
        self.name = name                       # "<suite>/<query>"
        base = os.path.join(BENCH, "queries", *name.split("/"))
        with open(base + ".sql") as f:
            self.sql = " ".join(f.read().split())
        spec = read_json(base + ".params.json")
        self.parameters = spec["parameters"]
        self.tables = spec["tables"]
        self.slots = SLOT.findall(self.sql)    # in order of appearance
        unknown = set(self.slots) - set(self.parameters)
        if unknown:
            raise ValueError(f"{name}: slots without a domain: {unknown}")

    @property
    def prepared_name(self) -> str:
        return re.sub(r"\W", "_", self.name)


class Cell:
    """`<config>.<mix>`: the entry of BENCHMARK.json where there is one,
    else (a cell kept for later, or one being tried) the two files that
    the name spells."""

    def __init__(self, workload: str):
        bench = read_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            config_name, _, mix = workload.partition(".")
            config_file = os.path.join("benchmark", "configs",
                                       config_name + ".json")
        else:
            mix = entry["traffic"]
            config_file = next(c["file"] for c in bench["configs"]
                               if c["name"] == entry["config"])
        self.name = workload
        self.listed = entry is not None
        self.config = read_json(ROOT, config_file)
        self.chips = entry["chips"] if entry else self.config["chips"]
        self.traffic = read_json(BENCH, "traffic", mix + ".json")
        self.queries = {t: Query(t) for t in self.traffic["templates"]}
        if self.listed:
            mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
            self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
            self.per_layer = [m for m in bench["per_layer"] if mine(m)]
        else:   # the mix says what it reports; every reader gets a look
            self.end_to_end = [m for m in bench["end_to_end"]
                               if m["name"] in self.traffic["end_to_end"]]
            self.per_layer = bench["per_layer"]
