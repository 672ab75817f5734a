"""commitments_ms: mean per block of the ``ante.commitments`` span
(state/app.py ``_decode_proposal_txs``): the batched share-commitment
recompute of every blob, ``inclusion.warm_commitments``.  None where the
program has no such span."""

from _spans import mean_ms_per_block

SPAN = "ante.commitments"


def read(ctx):
    if not any(s[0] == SPAN for bt in ctx.blocks for s in bt["spans"]):
        return None
    return mean_ms_per_block(ctx, SPAN)
