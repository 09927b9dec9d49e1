"""Device-idle time at each chunk boundary of the traced engine call:
for each of the program's `lark.drain` spans, the longest idle gap on
the device that overlaps it (the last op of chunk k to the first of
chunk k+1), summed and divided by the chunks; the mean over the chips,
in ms."""
from larkbench import stages


def read(ctx):
    return stages.drain_idle_ms_per_chunk(ctx)
