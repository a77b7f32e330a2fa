"""The whole solve's share of the HBM roofline: the least time the chip
could take for the bytes the algorithm must move, over the device busy
time per call. The bytes a row are the operand kind's
(``least_bytes_per_row`` in ``bench/operands/<kind>.py``), whatever
implements it. The solve does a few tens of vector flops a row and no
matrix work, so the bound counted is bandwidth's; ``bench/peaks.json``
holds no fp32 vector rate."""


def read(run):
    red = run.reduction
    if red is None or red.busy_ns <= 0 or run.least_bytes_per_row <= 0:
        return None
    least_s = run.rows_per_call * run.least_bytes_per_row / run.peaks["hbm_bytes_per_s"]
    busy_s = red.busy_ns / red.calls / 1e9
    return 100.0 * least_s / busy_s
