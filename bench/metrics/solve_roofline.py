"""The whole solve's share of the HBM roofline: the least time the chip
could take for the bytes the algorithm must move, over the device busy
time per call. The algorithm reads four fp32 words per row (dl, d, du, b)
and writes one (x), so 20 bytes a row, whatever implements it. The solve
does a few tens of vector flops a row and no matrix work, so the bound
counted is bandwidth's; ``bench/peaks.json`` holds no fp32 vector rate."""

BYTES_PER_ROW = 5 * 4


def read(run):
    red = run.reduction
    if red is None or red.busy_ns <= 0:
        return None
    least_s = run.rows_per_call * BYTES_PER_ROW / run.peaks["hbm_bytes_per_s"]
    busy_s = red.busy_ns / red.calls / 1e9
    return 100.0 * least_s / busy_s
