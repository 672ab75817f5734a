"""catchup_validate_ms: mean per block of the ``process_proposal`` root
in a catch-up cell, whose extend hits the EDS cache the window's
``extend_batch`` filled: per-block validation (ante, square rebuild,
EDS key, the cache lookup, the root compare).  Compare it with the
one-chip cells' per-block path less ``extend_ms``.  None where the
program has no ``extend_batch`` trace."""

from _batch_spans import batch_traces
from _spans import mean_ms_per_block


def read(ctx):
    if not batch_traces(ctx):
        return None
    return mean_ms_per_block(ctx, "process_proposal")
