"""process_self_ms: mean per block of the ``process_proposal`` root span
less its children ``decode_and_ante``, ``square_build``,
``eds_cache.key`` and ``extend``: the time of a ProcessProposal that no
named span covers.  None where the program has no ``eds_cache.key``
span."""

CHILDREN = ("decode_and_ante", "square_build", "eds_cache.key", "extend")


def read(ctx):
    per_block = []
    for bt in ctx.blocks:
        if bt["name"] != "process_proposal":
            continue
        spans = bt["spans"]
        if not any(s[0] == "eds_cache.key" for s in spans):
            continue
        root = sum(t1 - t0 for name, t0, t1, _a in spans if name == "process_proposal")
        children = sum(t1 - t0 for name, t0, t1, _a in spans if name in CHILDREN)
        per_block.append((root - children) * 1000.0)
    return sum(per_block) / len(per_block) if per_block else None
