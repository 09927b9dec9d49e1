"""Device time of the catch-up node counts per simulated step: the leaf
ops under the program's `lark_node_counts` scope (each catch-up's ingest
node, the in-flight count at it and the bandwidth share it grants) in
the traced window over its steps, the mean over the chips."""
from larkbench import stages


def read(ctx):
    return stages.stage_ms_per_step(ctx, "lark_node_counts")
