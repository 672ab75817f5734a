"""Shared arithmetic of the catch-up readers: the ``extend_batch`` block
traces (state/app.py ``warm_extends_batched``, one per window call) and
the spans inside them.  A tree without that trace reads None."""

BATCH = "extend_batch"


def batch_traces(ctx):
    return [bt for bt in ctx.blocks if bt["name"] == BATCH]


def span_ms(bt, span: str) -> float:
    return sum((t1 - t0) * 1000.0 for name, t0, t1, _a in bt["spans"] if name == span)


def root_ms(bt) -> float:
    return span_ms(bt, bt["name"])


def root_args(bt) -> dict:
    return next((a for name, _t0, _t1, a in bt["spans"] if name == bt["name"]), {})


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None
