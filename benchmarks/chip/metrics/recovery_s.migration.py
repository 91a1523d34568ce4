"""Mean wall seconds of the window's expected migrations (the
benchmark's span around `Controller.expected_migration`)."""


def read(run):
    n = run.span_count("migration")
    return run.span_total("migration") / n if n else None
