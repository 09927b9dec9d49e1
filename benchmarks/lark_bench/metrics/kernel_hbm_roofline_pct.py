"""Share of the HBM roofline the kernels reach: the least time their
bytes take at the chip's HBM bandwidth (peaks/) over the time they took,
bytes per call (kernels/*.py) times the calls in the trace.  The mean
over the chips; nothing when the trace holds no kernel event or a kernel
has no byte count."""


def read(ctx):
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    shares = []
    for d in ctx["summary"]["devices"].values():
        t_ns = sum(t for t, _ in d["kernels"].values())
        if t_ns <= 0:
            continue
        need = 0.0
        for kind, (_, calls) in d["kernels"].items():
            b = ctx["kernel_bytes"].get(kind, 0)
            if b <= 0:
                return None
            need += b * calls
        shares.append(100.0 * (need / bw) / (t_ns / 1e9))
    if not shares:
        return None
    return sum(shares) / len(shares)
