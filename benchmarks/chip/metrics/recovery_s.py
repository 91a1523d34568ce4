"""Mean wall seconds of one recovery in the window: the benchmark's
spans around `expected_migration` / `unexpected_failure`, each ended
when every training machine's state is ready (host clock)."""


def read(run):
    n = run.span_count("migration") + run.span_count("failure")
    if not n:
        return None
    return (run.span_total("migration") + run.span_total("failure")) / n
