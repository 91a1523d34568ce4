"""Wall seconds from the process's start to the window's start: imports,
building the job (weights on the device), compiling or loading every
program, step 0 and the traffic mix's warm-up (host clock)."""


def read(run):
    return run.setup_s
