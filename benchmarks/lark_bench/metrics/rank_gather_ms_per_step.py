"""Device time of the rank-space gather per simulated step: the leaf ops
under the program's `lark_rank_gather` scope (`up[:, succ]`, the pack
into words and the relayout into the eval's layout) in the traced window
over its steps, the mean over the chips."""
from larkbench import stages


def read(ctx):
    return stages.stage_ms_per_step(ctx, "lark_rank_gather")
