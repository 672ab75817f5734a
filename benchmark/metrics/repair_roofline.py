"""repair_roofline: the least HBM time the repair's work needs, over the
repair program's measured device time per run.

The work is counted the same whatever implements it: read the provided
cells once (``available`` x 512 B, the ``repair_block`` trace's arg),
write the EDS once (4k^2 * 512 B) and write the 4k axis roots (4k * 90
B), at the device's HBM bandwidth from peaks.json.  At k=128 with
49,152 cells available that is 58,766,336 B, 71.8 us at 819 GB/s.  No
operation count is added: the decode is GF(2^8) arithmetic and the
roots SHA-256, which have no FLOP peak to divide by, as for
extend_levels_roofline.  None where the program has no repair trace or
the pass ran no repair program."""

import repair_device_ms
from _batch_spans import mean, root_args
from _repair_spans import repair_traces


def least_bytes(k: int, available: float) -> float:
    return available * 512 + 4 * k * k * 512 + 4 * k * 90


def read(ctx):
    device_ms = repair_device_ms.read(ctx)
    available = mean(root_args(bt).get("available", 0) for bt in repair_traces(ctx))
    if not device_ms or not available:
        return None
    least_s = least_bytes(ctx.k, available) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_ms / 1000.0)
