"""Device time of one simulated step: the union of the device-op
intervals of the traced window over its steps, the mean over the chips."""


def read(ctx):
    devs = list(ctx["summary"]["devices"].values())
    if not devs or ctx["steps"] <= 0:
        return None
    busy = sum(d["busy_ns"] for d in devs) / len(devs)
    if busy <= 0:
        return None
    return busy / ctx["steps"] / 1e6
