"""HBM bytes of one call of the packed availability kernel
(kernels/fused_step.py: fused_pac_eval), from its operands and results
at the cell's shapes: up and holder words in; lark and majority rows
(int32 at the kernel's boundary) and the refreshed holder words out.

Copied from the fused branch of the program's kernels/ops.step_hbm_bytes
so that the yardstick cannot move with the program.  It counts the
kernel alone, never the step: the gather and pack around it are not in
it.  In a trace the kernel is the cell's one Mosaic custom call.
"""
KIND = "fused_pac"


def match(name: str, cell: dict, trials: int) -> bool:
    return cell["engine"] == "availability" \
        and 'custom_call_target="tpu_custom_call"' in name


def bytes_per_call(cell: dict, trials: int) -> int:
    if cell["engine"] != "availability":
        return 0
    B, P = trials, cell["partitions"]
    W = -(-cell["n"] // 32)
    words = 3 * B * W * P * 4          # up, full in; creps out (uint32)
    rows = 2 * B * P * 4               # lark, maj
    return words + rows
