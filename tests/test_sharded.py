"""Sharded (multi-chip) extension tests — run in an isolated process.

The actual tests live in tests/_sharded_isolated.py (not collected by the
parent run).  They are executed here through a fresh child interpreter
because jaxlib's CPU backend on some hosts segfaults inside
``backend_compile_and_load`` when the large 8-device shard_map executable is
compiled late in a long-lived process that has already JIT-compiled dozens
of other programs (observed twice in full-suite runs; the identical tests
pass in a fresh process, and __graft_entry__.dryrun_multichip re-execs into
a clean child for the same reason).  Subprocess isolation keeps the
multi-chip coverage without exposing the suite to that jaxlib crash.

The structure test below only lowers the program (no backend compile),
so it runs in this process.
"""

import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp

from celestia_tpu.ops import gf256
from celestia_tpu.parallel import sharded


def _run_isolated(select: str) -> None:
    inner = os.path.join(os.path.dirname(__file__), "_sharded_isolated.py")
    env = dict(os.environ)
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", inner, "-k", select],
        capture_output=True,
        text=True,
        timeout=3000,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    sys.stdout.write(proc.stdout[-3000:])
    assert proc.returncode == 0, (
        f"isolated sharded suite failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    )


def test_sharded_suite_in_fresh_process():
    _run_isolated("not full_size")


def test_sharded_full_size_in_fresh_process():
    # the 128x128 shard_map program is big enough that compiling it AFTER
    # the small-k programs in one process trips the same late-compile
    # jaxlib fragility the wrapper exists for — it gets its own child
    _run_isolated("full_size")


_ALL_TO_ALL = re.compile(
    r'"stablehlo\.all_to_all".*split_count = (\d+).*: \(tensor<([0-9x]+)xui8>\)'
)


def test_column_parity_is_an_exchange_not_a_reduction():
    """The batched program on the cell's 1x4 factoring moves the packed
    rows in all_to_alls and reduces nothing across chips, and the
    ``ici_bytes`` its span reports is what those all_to_alls send."""
    k, n, R = 8, 2, 4
    mesh = sharded.make_mesh(jax.devices()[:R], data=1, row=R)
    fn = sharded._sharded_fn(mesh, k, True, gf256.active_codec())
    text = fn.lower(jax.ShapeDtypeStruct((n, k, k, 512), jnp.uint8)).as_text()
    assert "all_reduce" not in text and "reduce_scatter" not in text
    sent = []
    for count, dims in _ALL_TO_ALL.findall(text):
        block = math.prod(int(d) for d in dims.split("x"))
        sent.append(block * (int(count) - 1) // int(count))
    # one (k/R, 2k, 512) block per square out, one back
    assert sent == [n * (k // R) * 2 * k * 512 * (R - 1) // R] * 2
    assert sharded.ici_bytes(k, R, n) == sum(sent) == 49152
    assert sharded.ici_bytes(k, 1) == 0
