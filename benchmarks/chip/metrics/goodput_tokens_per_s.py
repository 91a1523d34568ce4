"""Goodput of a cell with interruptions, read as `tokens_per_s` is: the
tokens of every iteration completed in the window over its wall
seconds, recoveries included."""
from chipbench import spec

read = spec.reader("tokens_per_s")
