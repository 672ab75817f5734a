"""gc_ms: mean per block of the ``gc`` spans inside a ProcessProposal
trace: Python's collector pauses, which the tracer records under the span
that was running (utils/tracing.py ``Tracer.enable``).  A block with no
collection reads 0.  None where the program's tracer has no collector
hook."""

from _spans import mean_ms_per_block


def read(ctx):
    from celestia_tpu.utils import tracing

    if not hasattr(tracing, "gc_hook_installed"):
        return None
    return mean_ms_per_block(ctx, "gc")
