"""catchup_warm_host_ms: mean per window call of the ``extend_batch``
root less its ``extend.sharded.wait`` spans, where the host blocks on
the batched program: the warm leg's host side, that is the square
rebuilds, the EDS keys, the stack, the upload, the launch, the
reassembly and the root fetch.  None where the program has no
``extend_batch`` trace or no ``extend.sharded.wait`` span."""

from _batch_spans import batch_traces, mean, root_ms, span_ms


def read(ctx):
    traces = batch_traces(ctx)
    if not any(s[0] == "extend.sharded.wait" for bt in traces for s in bt["spans"]):
        return None
    return mean(root_ms(bt) - span_ms(bt, "extend.sharded.wait") for bt in traces)
