"""Device milliseconds per iteration of the stage programs (`fwd`,
`mid_bwd`, `last_bwd` of `engine.make_stage_fns`) run inside the
window's training calls (device trace)."""
from chipbench import trace_reduce

PROGRAMS = r"jit_(fwd|mid_bwd|last_bwd)"


def read(run):
    if run.trace is None or not run.iterations:
        return None
    sec, n = trace_reduce.program_s(run.trace, PROGRAMS, within=("train",))
    return sec * 1e3 / run.iterations if n else None
