"""catchup_warm_ms: mean per window call of the ``extend_batch`` root
(state/app.py ``warm_extends_batched``): the catch-up warm leg, from the
per-block square rebuilds and EDS keys through the batched sharded
dispatch to the EDS cache puts.  None where the program has no
``extend_batch`` trace."""

from _batch_spans import batch_traces, mean, root_ms


def read(ctx):
    return mean(root_ms(bt) for bt in batch_traces(ctx))
