"""repair_host_ms: mean per call of the ``repair_block`` root less its
``repair.wait`` span, where the host waits on the repair program: the
repair's host side, that is the DAH check, the peel, the upload, the
launch, the verdict and Q0 fetch, ``deconstruct`` and the registration.
None where the program has no ``repair.wait`` span."""

from _batch_spans import mean, root_ms, span_ms
from _repair_spans import repair_traces


def read(ctx):
    traces = repair_traces(ctx)
    if not any(s[0] == "repair.wait" for bt in traces for s in bt["spans"]):
        return None
    return mean(root_ms(bt) - span_ms(bt, "repair.wait") for bt in traces)
