"""ante_chain_ms: mean per block of the ``ante.chain`` span
(state/app.py ``_process_proposal_traced``): the ante decorators run over
every decoded tx on the proposal's state branch.  None where the program
has no such span."""

from _spans import mean_ms_per_block

SPAN = "ante.chain"


def read(ctx):
    if not any(s[0] == SPAN for bt in ctx.blocks for s in bt["spans"]):
        return None
    return mean_ms_per_block(ctx, SPAN)
