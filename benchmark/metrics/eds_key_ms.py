"""eds_key_ms: mean per block of the ``eds_cache.key`` span
(state/app.py ``_extend_block_cached``): the EDS cache key, a second
SHA-256 over every tx byte of the block, taken before ``extend`` opens.
None where the program has no such span."""

from _spans import mean_ms_per_block

SPAN = "eds_cache.key"


def read(ctx):
    if not any(s[0] == SPAN for bt in ctx.blocks for s in bt["spans"]):
        return None
    return mean_ms_per_block(ctx, SPAN, leg="process")
