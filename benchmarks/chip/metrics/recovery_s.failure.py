"""Mean wall seconds of the window's unexpected failures (the
benchmark's span around `Controller.unexpected_failure`)."""


def read(run):
    n = run.span_count("failure")
    return run.span_total("failure") / n if n else None
