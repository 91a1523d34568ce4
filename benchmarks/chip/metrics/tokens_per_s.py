"""Tokens of every iteration completed in the window, over the window's
wall seconds (host clock; the window ends when every training machine's
state is ready)."""


def read(run):
    return run.tokens_per_s
