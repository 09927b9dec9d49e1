"""Device time of the reconfiguring roster per simulated step: the leaf
ops under the program's `lark_roster` scope (the recruit and the seat
gathers of the up mask) in the traced window over its steps, the mean
over the chips."""
from larkbench import stages


def read(ctx):
    return stages.stage_ms_per_step(ctx, "lark_roster")
