"""The ``repair_block`` block traces (state/app.py ``App.repair_block``,
one per window call), for the repair readers; the arithmetic over their
spans is ``_batch_spans``'.  A tree without that trace reads None."""


def repair_traces(ctx):
    return [bt for bt in ctx.blocks if bt["name"] == "repair_block"]
