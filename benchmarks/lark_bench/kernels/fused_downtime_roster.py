"""HBM bytes of one call of the packed commit-pause kernel with the
reconfiguring roster (kernels/fused_step.py: fused_downtime_eval with a
roster), from its operands and results at the cell's shapes; every row
is int32 at the kernel's boundary.

Copied from the fused reconfig branch of the program's
kernels/ops.step_hbm_bytes, so that the yardstick cannot move with the
program, with one change: the protocol-zoo rows (the hermes membership
mask, the spinnaker leader) that ops leaves out are counted.  The node
counts come in only where catch-ups share a finite bandwidth.  It counts
the kernel alone, never the step.  In a trace the kernel is the Mosaic
custom call that takes the (trials, rf, partitions) roster.
"""
import math

KIND = "fused_downtime_roster"


def match(name: str, cell: dict, trials: int) -> bool:
    roster = f"s32[{trials},{cell.get('rf')},{cell.get('partitions')}]"
    return cell["engine"] == "downtime" \
        and 'custom_call_target="tpu_custom_call"' in name \
        and roster in name


def bytes_per_call(cell: dict, trials: int) -> int:
    if cell["engine"] != "downtime" or cell["rebuild_model"] != "reconfig":
        return 0
    B, P, rf, n = trials, cell["partitions"], cell["rf"], cell["n"]
    W = -(-n // 32)
    words = 3 * B * W * P * 4          # up, full in; creps out (uint32)
    roster = B * rf * P * 4            # seat ranks
    extras = sum(e in cell["protocols"] for e in ("hermes", "spinnaker"))
    rows = (5 + extras) * B * P * 4    # lark, qmaj, leader, lfull, nrep, ..
    counts = 0
    if math.isfinite(cell["node_bandwidth_gibps"]):
        n_lanes = n + (-n % 128)
        counts = 2 * B * P * 4 + B * n_lanes * 4   # recruit, active; counts
    return words + roster + rows + counts
