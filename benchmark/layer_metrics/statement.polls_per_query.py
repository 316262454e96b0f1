"""Mean per query of the polls the coordinator answered for it
(`statementPolls`, counted by worker/statement.py for every GET
.../queued or .../executing; the POST's own answer is none): how many
round trips the client needed to learn that its query had started, had
rows, and was drained.  A poll that ran out its wait and sent the client
back with nothing new is one of them (`statementPollTimeouts` counts
those apart).

None where no query of the span carries the key (a program that does
not count its polls)."""
from span_stats import instrumented


def read(run):
    polls = [stats["statementPolls"].get("sum", 0)
             for stats in instrumented(run) if "statementPolls" in stats]
    if not polls:
        return None
    return sum(polls) / len(polls)
