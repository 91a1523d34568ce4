"""Share of its HBM roofline that the flat Adam update program
(`engine.make_flat_update`, jitted as `upd_flat`) reaches inside the
window's training calls, in %: the least bytes the algorithm moves per
iteration (chipbench.flops.iteration_update_bytes) over the chip's HBM
bandwidth, over the program's measured device time per iteration. The
update does a few FLOPs per byte, so bandwidth bounds it."""
from chipbench import flops, trace_reduce

PROGRAM = r"jit_upd_flat"


def read(run):
    if run.trace is None or not run.iterations:
        return None
    sec, n = trace_reduce.program_s(run.trace, PROGRAM, within=("train",))
    if not n:
        return None
    least = flops.iteration_update_bytes(run.cfg) * run.iterations \
        / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / sec
