"""ante_cpu_ms: mean per block of the ``cpu_ms`` arg of the
``decode_and_ante`` span: the process CPU time, all threads, while the
block's decode, commitments, signatures and ante ran.  Beside ``ante_ms``
it tells more work from more waiting.  None where the span has no such
arg."""


def read(ctx):
    per_block = []
    for bt in ctx.blocks:
        if bt["name"] != "process_proposal":
            continue
        cpu = [a["cpu_ms"] for name, _t0, _t1, a in bt["spans"]
               if name == "decode_and_ante" and "cpu_ms" in a]
        if cpu:
            per_block.append(sum(cpu))
    return sum(per_block) / len(per_block) if per_block else None
