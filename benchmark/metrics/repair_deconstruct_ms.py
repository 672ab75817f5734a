"""repair_deconstruct_ms: mean per call of the ``repair.deconstruct``
span (state/app.py ``App.repair_block``): the block's txs recovered
from the repaired Q0 (da/square.py ``deconstruct``) and laid out again
to check that they give the same square.  None where the program has no
such span."""

from _batch_spans import mean, span_ms
from _repair_spans import repair_traces


def read(ctx):
    traces = repair_traces(ctx)
    if not any(s[0] == "repair.deconstruct" for bt in traces for s in bt["spans"]):
        return None
    return mean(span_ms(bt, "repair.deconstruct") for bt in traces)
