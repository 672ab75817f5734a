"""sig_verify_ms: mean per block of the ``ante.sig_verify`` span
(state/app.py ``_decode_proposal_txs``): the signature-cache probe, each
tx's sign bytes and the batched secp256k1 verify.  None where the program
has no such span."""

from _spans import mean_ms_per_block

SPAN = "ante.sig_verify"


def read(ctx):
    if not any(s[0] == SPAN for bt in ctx.blocks for s in bt["spans"]):
        return None
    return mean_ms_per_block(ctx, SPAN)
