"""One general traffic generator.  A mix (traffic/<mix>.json) says how
many closed-loop clients, which query templates in which order, whether
statements go inline or as EXECUTE ... USING over prepared templates, and
how the seed draws their parameters; nothing here knows a query."""
import threading
import time

import sampler

START_STAGGER_S = 0.02


class Plan:
    """What client c sends as its i-th request, fixed by the seed alone."""

    def __init__(self, traffic: dict, queries: dict, seed: int):
        self.traffic, self.queries, self.seed = traffic, queries, seed
        self.templates = traffic["templates"]
        self.prepared = traffic["statements"] == "prepared"
        draw = traffic["parameters"]
        self.pool = None
        if draw["draw"] == "pool":
            # the same few tuples for every seed; the seed picks among them
            self.pool = {t: [sampler.draw(queries[t].parameters,
                                          sampler.rng("pool", draw["pool_seed"], t, k))
                             for k in range(draw["pool_size"])]
                         for t in self.templates}

    def template(self, client: int, i: int) -> str:
        order = self.traffic["order"]
        if order == "alternate":
            return self.templates[(client + i) % len(self.templates)]
        if order == "weighted":
            return sampler.rng(self.seed, "order", client, i).choices(
                self.templates, self.traffic["weights"])[0]
        raise ValueError(f"unknown order {order!r}")

    def values(self, template: str, *key) -> dict:
        r = sampler.rng(self.seed, template, *key)
        if self.pool is not None:
            return r.choice(self.pool[template])
        return sampler.draw(self.queries[template].parameters, r)

    def statement(self, template: str, values: dict) -> str:
        q = self.queries[template]
        return sampler.execute_statement(q, values) if self.prepared \
            else sampler.inline(q, values)

    def request(self, client: int, i: int):
        t = self.template(client, i)
        v = self.values(t, client, i)
        return t, v, self.statement(t, v)


def send(client, template: str, values: dict, sql: str, t0: float,
         tag: dict) -> dict:
    """One request, submit to last fetched row, by the host's clock."""
    import jax
    rec = {"template": template, "values": values, **tag}
    started = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench:" + template):
            result = client.execute(sql)
        rec.update(ok=True, rows=result.rows, query_id=result.query_id,
                   server_elapsed_ms=result.stats.get("elapsedTimeMillis"))
    except Exception as e:   # noqa: BLE001 -- a failed request is counted
        rec.update(ok=False, rows=None, error=f"{type(e).__name__}: {e}")
    done = time.perf_counter()
    rec.update(submit_s=started - t0, done_s=done - t0, wall_s=done - started)
    return rec


def closed_loop(servers, plan: Plan, clients: list, seconds: float,
                on_done=None):
    """Every client sends its next request when the last one's rows are
    in, until `seconds` have passed; a request begun inside the window is
    finished and counted, and the window ends with the last of them.
    `on_done(record)` is called on the client's thread after each request.
    Returns (requests, window seconds)."""
    records = [[] for _ in clients]
    barrier = threading.Barrier(len(clients) + 1)
    t0 = [0.0]

    def loop(c):
        barrier.wait()
        # independent panels do not connect in the same microsecond (the
        # server's listen backlog holds 5)
        time.sleep(c * START_STAGGER_S)
        i = 0
        while time.perf_counter() - t0[0] < seconds:
            t, v, sql = plan.request(c, i)
            records[c].append(send(clients[c], t, v, sql, t0[0],
                                   {"client": c, "seq": i}))
            if on_done is not None:
                on_done(records[c][-1])
            i += 1

    threads = [threading.Thread(target=loop, args=(c,), name=f"client-{c}")
               for c in range(len(clients))]
    for t in threads:
        t.start()
    t0[0] = time.perf_counter()
    barrier.wait()
    for t in threads:
        t.join()
    requests = [r for per_client in records for r in per_client]
    window_s = max([r["done_s"] for r in requests], default=0.0)
    return requests, max(window_s, seconds)


def burst(servers, plan: Plan, template: str, width: int, key) -> list:
    """`width` requests of one template at the same moment (set-up: lets
    the server form, and compile, a batch of that width)."""
    clients = [servers.client(f"warm-{k}") for k in range(width)]
    if plan.prepared:
        for c in clients:
            c.prepared[plan.queries[template].prepared_name] = \
                sampler.prepared_text(plan.queries[template])
    out = [None] * width
    barrier = threading.Barrier(width)

    def one(k):
        v = plan.values(template, "warm", key, k)
        barrier.wait()
        # `width` connections at once can overflow the server's listen
        # backlog of 5; set-up asks again where the window would count it
        for _attempt in range(3):
            out[k] = send(clients[k], template, v,
                          plan.statement(template, v), 0.0,
                          {"client": f"warm-{k}", "seq": key})
            if out[k]["ok"] or "Connection" not in out[k]["error"]:
                break

    threads = [threading.Thread(target=one, args=(k,)) for k in range(width)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out
