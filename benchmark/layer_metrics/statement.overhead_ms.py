"""Mean over the window's requests of client wall minus the server's own
`elapsedTimeMillis` (StatementResult.stats): what the statement protocol
and the client's polling add around the query."""


def read(run):
    gaps = [r["wall_s"] * 1000 - r["server_elapsed_ms"]
            for r in run["requests"]
            if r["ok"] and r.get("server_elapsed_ms") is not None]
    return sum(gaps) / len(gaps) if gaps else None
