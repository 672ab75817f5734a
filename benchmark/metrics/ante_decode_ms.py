"""ante_decode_ms: mean per block of the ``ante.decode`` spans
(state/app.py ``_decode_proposal_txs``): each tx's SHA-256 cache key, the
decoded-tx cache probe and the envelope unmarshal (``stage=unmarshal``),
then ``validate_blob_tx`` (``stage=validate``), on either side of the
commitment batch.  None where the program has no such span."""

from _spans import mean_ms_per_block

SPAN = "ante.decode"


def read(ctx):
    if not any(s[0] == SPAN for bt in ctx.blocks for s in bt["spans"]):
        return None
    return mean_ms_per_block(ctx, SPAN)
