"""Device time of the Pallas kernels per simulated step: the summed
durations of the kernel events (kernels/*.py MATCH) over the steps, the
mean over the chips."""


def read(ctx):
    devs = list(ctx["summary"]["devices"].values())
    times = [sum(t for t, _ in d["kernels"].values()) for d in devs]
    if not devs or not any(times) or ctx["steps"] <= 0:
        return None
    return sum(times) / len(times) / ctx["steps"] / 1e6
