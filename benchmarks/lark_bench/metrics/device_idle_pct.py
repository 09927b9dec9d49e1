"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals / window, the mean over the chips."""


def read(ctx):
    s = ctx["summary"]
    devs = list(s["devices"].values())
    if not devs or s["window_ns"] <= 0:
        return None
    idle = [1.0 - d["busy_ns"] / s["window_ns"] for d in devs]
    return 100.0 * sum(idle) / len(idle)
