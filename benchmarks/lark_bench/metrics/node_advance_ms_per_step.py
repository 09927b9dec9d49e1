"""Device time of the node advance per simulated step: the leaf ops under
the program's `lark_node_advance` scope (counter RNG, geometric redraws
by `searchsorted`, event times) in the traced window over its steps, the
mean over the chips."""
from larkbench import stages


def read(ctx):
    return stages.stage_ms_per_step(ctx, "lark_node_advance")
