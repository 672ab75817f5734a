"""repair_device_ms: device seconds of the repair program per run
(ops/rs.py ``_repair_verify``, XLA module ``jit_repair_verify``: the
peel's decodes, the extension of the recovered Q0 with the NMT and
root-tree levels, and the codeword, provided-share and root checks),
from the profiler's XLA Modules line on the first chip, inside the
window."""

PROGRAM = "repair_verify"


def read(ctx):
    prog = ctx.device["programs"].get(PROGRAM)
    if not prog or not prog["runs"]:
        return None
    chip = min(prog["device_s"])
    return prog["device_s"][chip] / prog["runs"] * 1000.0
