"""repair_ms: mean per call of the ``repair_block`` root span
(state/app.py ``App.repair_block``): the DAH check, the host peel, the
upload, the repair program, the verdict and Q0 fetch, ``deconstruct``
and the registration.  None where the program has no such trace."""

from _batch_spans import mean, root_ms
from _repair_spans import repair_traces


def read(ctx):
    return mean(root_ms(bt) for bt in repair_traces(ctx))
