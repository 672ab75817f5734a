"""extend_host_ms: mean per block of the ``extend`` span (leg=process)
less its ``extend.wait`` span (da/device_plane.py ``extend_and_header``,
the host waiting on the device for the data root): the extend's host
side, that is the cache lookup, the upload, the launch, the axis-root
fetch and the DAH assembly.  None where the program has no
``extend.wait`` span."""

from _spans import mean_ms_per_block


def read(ctx):
    if not any(s[0] == "extend.wait" for bt in ctx.blocks for s in bt["spans"]):
        return None
    extend = mean_ms_per_block(ctx, "extend", leg="process")
    wait = mean_ms_per_block(ctx, "extend.wait")
    if extend is None or wait is None:
        return None
    return extend - wait
