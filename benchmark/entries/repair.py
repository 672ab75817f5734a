"""Entry ``repair``: a full node rebuilds one block per call from the
shares it gathered, ``TestNode.repair_block``, cycling the pool, with
every proposal cache cleared before each call.  The node is handed the
block's data root, its DAH, the shares it holds and the mask of them;
never the txs.  The functions are those of ``entries/cons_process.py``.

The traffic file's ``withheld`` is the share of a block's 2k x 2k cells
the node does not hold: that many exactly, drawn uniformly from a
generator seeded by the block's data root, so the mask changes with the
run's seed.  ``phases`` is how many peeling phases (rows, then columns)
every mask must take; ``build_pool`` asserts both and never draws a
mask again.  The pool items stay ``(block_txs, square_size,
data_root)`` for the harness's comparison with the reference; the
masked squares are kept here beside them.
"""

import numpy as np

_HELD = {}  # data root -> (dah, shares the node holds, mask)
_ON_DEVICE = []  # per call: repairs the node ran on the device program


def peel_phases(avail: np.ndarray) -> int:
    """The phases a repair of this mask takes: each solves every row,
    then every column, that holds at least half its cells."""
    avail = avail.copy()
    k = avail.shape[0] // 2
    phases = 0
    while not avail.all():
        held = int(avail.sum())
        avail[avail.sum(axis=1) >= k] = True
        avail[:, avail.sum(axis=0) >= k] = True
        if int(avail.sum()) == held:
            raise ValueError("the mask cannot repair")
        phases += 1
    return phases


def mask(data_root: bytes, n2: int, withheld: int) -> np.ndarray:
    """bool[n2, n2], True where the node holds the cell."""
    rng = np.random.default_rng(int.from_bytes(data_root, "big"))
    avail = np.ones(n2 * n2, dtype=bool)
    avail[rng.choice(n2 * n2, withheld, replace=False)] = False
    return avail.reshape(n2, n2)


def build_pool(app, blocks, k, traffic):
    """Each block from the proposer's PrepareProposal, asserted to be
    exactly k with every tx kept, and the shares a full node holds of
    it."""
    from celestia_tpu.node.testnode import TestNode

    if not hasattr(TestNode, "repair_block"):
        raise RuntimeError("this program has no TestNode.repair_block: no repairing full node to drive")
    n2 = 2 * k
    withheld = round(float(traffic["withheld"]) * n2 * n2)
    pool = []
    for i, txs in enumerate(blocks):
        prop = app.prepare_proposal(txs)
        if prop.square_size != k or len(prop.block_txs) != len(txs):
            raise ValueError(
                f"pool block {i}: square {prop.square_size} with {len(prop.block_txs)} of "
                f"{len(txs)} txs; the traffic must give exactly k={k} with every tx kept"
            )
        avail = mask(prop.data_root, n2, withheld)
        phases = peel_phases(avail)
        if int((~avail).sum()) != withheld or phases != int(traffic["phases"]):
            raise ValueError(
                f"pool block {i}: {int((~avail).sum())} cells withheld, {phases} phase(s); "
                f"the traffic asks {withheld} and {traffic['phases']}"
            )
        eds = np.asarray(prop.eds.shares)
        _HELD[prop.data_root] = (prop.dah, np.where(avail[:, :, None], eds, np.uint8(0)), avail)
        pool.append((list(prop.block_txs), prop.square_size, prop.data_root))
    return pool


def calls(pool, traffic):
    return [[i] for i in range(len(pool))]


def call(node, pool, idx):
    (i,) = idx
    data_root = pool[i][2]
    dah, shares, avail = _HELD[data_root]
    counters = node.app.telemetry.counters
    before = counters.get("repair_device", 0)
    verdict = node.repair_block(data_root, dah, shares, avail)
    _ON_DEVICE.append(counters.get("repair_device", 0) - before)
    return [verdict]


def guards(d, run, traffic):
    """Every window call repaired on the device program and registered a
    device-plane entry, on one chip."""
    return {
        "repairs_off_device": (run.calls - sum(_ON_DEVICE[-run.calls :]), 0),
        "extends_off_device_plane": (run.blocks - d["device_plane_puts"], 0),
        "sharded_extends": (d["sharded_extends"], 0),
    }
