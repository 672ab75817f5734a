"""sharded_batch_roofline: the least HBM time of one window call's
extend work over the device time of that call, on the busiest chip.

The work is counted as ``extend_levels_roofline.least_bytes`` counts it,
whatever implements it, once per real square of the window (pads left
out): n * (k^2*512 + 4k^2*512 + 4k*90) B, 335,912,960 B for n=8, k=128.
The peak is the cell's chips times the HBM bandwidth of peaks.json:
102.5 us at 4 x 819 GB/s.  The divisor is the device time of every
program the profiled pass ran (the batched sharded program, the
reassembly and the slices; every per-block extend hits the cache), per
run of the batched program, on the chip with the most: moving work out
of the sharded program into the reassembly cannot raise the share.  No
operation count is added, as for extend_levels_roofline.  None where
the program has no ``extend_batch`` trace or the pass ran no batched
program."""

from collections import defaultdict

import extend_levels_roofline
from _batch_spans import batch_traces, mean, root_args

PROGRAM = "_sharded_extend_and_roots"


def least_bytes(n: int, k: int) -> int:
    return n * extend_levels_roofline.least_bytes(k)


def read(ctx):
    prog = ctx.device["programs"].get(PROGRAM)
    squares = mean(root_args(bt).get("warmed", 0) for bt in batch_traces(ctx))
    if not prog or not prog["runs"] or not squares:
        return None
    per_chip = defaultdict(float)
    for p in ctx.device["programs"].values():
        for chip, s in p["device_s"].items():
            per_chip[chip] += s
    device_s = max(per_chip.values()) / prog["runs"]
    least_s = least_bytes(squares, ctx.k) / (ctx.chips * ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / device_s
