"""Device-idle time while the traced engine call sets up and builds its
chunk program: the seconds of the program's `lark.setup` and
`lark.chunk_program` spans that no device op covers, the mean over the
chips."""
from larkbench import stages


def read(ctx):
    return stages.call_setup_idle_s(ctx)
