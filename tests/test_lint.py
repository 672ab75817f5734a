"""celint self-test: every rule fires on its bad fixture, stays quiet on
its good fixture, directive hygiene is enforced, and — the actual gate —
the real tree lints clean.  This file is what wires `make lint` into
tier-1: a new hand-rolled cache, an unguarded mutation of annotated
state, a wall-clock read in state/ or da/, or a literal thread count
fails the SUITE, not review.
"""

import textwrap

from celestia_tpu.lint import (
    ALIASES,
    REGISTRY,
    failing,
    lint_program,
    lint_source,
    resolve_rules,
    run_lint,
)

# resolve_rules(None) imports the rule module and populates REGISTRY
resolve_rules(None)


def _lint(src: str, relpath: str = "celestia_tpu/node/fixture.py", rules=None):
    return lint_source(textwrap.dedent(src), relpath, rules)


def _ids(findings, *, include_suppressed=False):
    return [
        f.rule
        for f in findings
        if include_suppressed or not f.suppressed
    ]


# ---------------------------------------------------------------------------
# R1 guarded-by
# ---------------------------------------------------------------------------

R1_BAD_GLOBAL = """
    import threading

    _LOCK = threading.Lock()
    _CACHE = {}  # celint: guarded-by(_LOCK)


    def put(key, value):
        _CACHE[key] = value
"""

R1_BAD_METHODS = """
    import threading


    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []  # celint: guarded-by(self._lock)

        def bad_append(self, x):
            self._items.append(x)

        def bad_rebind(self):
            self._items = []

        def bad_augment(self, xs):
            self._items += xs
"""

R1_GOOD = """
    import threading

    _LOCK = threading.Lock()
    _CACHE = {}  # celint: guarded-by(_LOCK)


    def put(key, value):
        with _LOCK:
            _CACHE[key] = value


    def drop(key):
        with _LOCK:
            del _CACHE[key]


    def _evict_locked(key):
        # caller-holds-lock convention: *_locked names are exempt
        _CACHE.pop(key, None)


    def read(key):
        return _CACHE.get(key)  # reads are not mutations


    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []  # celint: guarded-by(self._lock)

        def good_append(self, x):
            with self._lock:
                self._items.append(x)
"""


def test_r1_fires_on_unlocked_global_mutation():
    out = _lint(R1_BAD_GLOBAL)
    assert _ids(out) == ["guarded-by"], out


def test_r1_fires_on_each_unlocked_method_mutation():
    out = [f for f in _lint(R1_BAD_METHODS) if f.rule == "guarded-by"]
    assert len(out) == 3, out  # append, rebind, augmented assign


def test_r1_quiet_on_locked_mutations_and_reads():
    assert _ids(_lint(R1_GOOD)) == []


def test_r1_flags_dangling_annotation():
    out = _lint(
        """
        # celint: guarded-by(_LOCK)
        print("no assignment here")
        """
    )
    assert _ids(out) == ["guarded-by"]


# ---------------------------------------------------------------------------
# R2 no-handrolled-cache
# ---------------------------------------------------------------------------

R2_BAD = """
    from collections import OrderedDict

    _CACHE = OrderedDict()
    _MAX = 16


    def put(key, value):
        _CACHE[key] = value
        _CACHE.move_to_end(key)
        while len(_CACHE) > _MAX:
            _CACHE.popitem(last=False)


    def put_fifo(cache, key, value):
        while len(cache) >= _MAX:
            cache.pop(next(iter(cache)))
        cache[key] = value
"""

R2_GOOD = """
    from functools import lru_cache

    from celestia_tpu.utils.lru import LruCache

    _CACHE = LruCache("fixture", 16)


    def put(key, value):
        _CACHE.put(key, value)


    @lru_cache(maxsize=None)
    def compiled(k):
        # functools memoization of compiled programs is not the pattern
        return k


    def unbounded_index(d, key, value):
        d[key] = value  # a plain dict with no eviction loop is fine
"""


def test_r2_fires_on_every_handrolled_fragment():
    got = _ids(_lint(R2_BAD))
    # OrderedDict import + move_to_end + while-evict + popitem (inside the
    # loop) + while-evict FIFO + pop(next(iter()))
    assert got.count("no-handrolled-cache") >= 5, got


def test_r2_quiet_on_lru_cache_and_plain_dicts():
    assert _ids(_lint(R2_GOOD)) == []


def test_r2_exempts_the_sanctioned_module():
    out = lint_source(
        "from collections import OrderedDict\n",
        "celestia_tpu/utils/lru.py",
    )
    assert _ids(out) == []


# ---------------------------------------------------------------------------
# R3 consensus-determinism
# ---------------------------------------------------------------------------

R3_BAD = """
    import os
    import random
    import time as _time

    import numpy as np


    def stamp():
        return _time.time(), _time.time_ns()


    def entropy():
        return os.urandom(32), random.random(), np.random.default_rng()


    def fold(items):
        out = b""
        for x in set(items):
            out += x
        return out
"""

R3_GOOD_SAME_CODE_OUTSIDE_CONSENSUS = R3_BAD

R3_GOOD = """
    from celestia_tpu.utils.telemetry import clock


    def stamp():
        return clock()  # the sanctioned telemetry channel


    def fold(items):
        out = b""
        for x in sorted(set(items)):
            out += x
        return out
"""


def test_r3_fires_in_state_and_da():
    for rel in ("celestia_tpu/state/fixture.py", "celestia_tpu/da/fixture.py"):
        got = _ids(_lint(R3_BAD, rel))
        # time.time, time.time_ns, os.urandom, random.random,
        # np.random.default_rng, set iteration
        assert got.count("consensus-determinism") == 6, (rel, got)


def test_r3_scoped_to_consensus_modules():
    out = _lint(
        R3_GOOD_SAME_CODE_OUTSIDE_CONSENSUS, "celestia_tpu/node/fixture.py"
    )
    assert _ids(out) == []


def test_r3_quiet_on_sanctioned_clock_and_sorted_sets():
    assert _ids(_lint(R3_GOOD, "celestia_tpu/state/fixture.py")) == []


def test_r3_allow_with_reason_suppresses():
    src = """
        import numpy as np

        # celint: allow(consensus-determinism) — seeded sampling RNG
        _RNG = np.random.default_rng(7)
    """
    out = _lint(src, "celestia_tpu/da/fixture.py")
    assert _ids(out) == []
    suppressed = [f for f in out if f.suppressed]
    assert len(suppressed) == 1
    assert suppressed[0].suppress_reason == "seeded sampling RNG"


# the sanctioned-channel extension (PR 8): the tracer/telemetry modules
# are the ONE place wall-clock reads are the design; the entropy bans
# still apply inside them (a random span id would launder nondeterminism
# through the open door)

R3_CHANNEL_CLOCK_OK = """
    import time


    def clock():
        return time.time()


    def stamp_span():
        return time.perf_counter()
"""

R3_CHANNEL_ENTROPY_BAD = """
    import random
    import time


    def clock():
        return time.time()


    def span_id():
        return random.getrandbits(64)
"""


def test_r3_sanctioned_channels_may_read_clocks():
    from celestia_tpu.lint.rules import SANCTIONED_CHANNELS

    assert "celestia_tpu/utils/tracing.py" in SANCTIONED_CHANNELS
    assert "celestia_tpu/utils/telemetry.py" in SANCTIONED_CHANNELS
    # PR 11: the device half + the continuous-telemetry ring read the
    # clock through the same door and carry the same entropy bans
    assert "celestia_tpu/utils/devprof.py" in SANCTIONED_CHANNELS
    assert "celestia_tpu/utils/timeseries.py" in SANCTIONED_CHANNELS
    # PR 13: the host sampling profiler + the flight recorder stamp
    # sample/incident timestamps through the same sanctioned clock
    assert "celestia_tpu/utils/hostprof.py" in SANCTIONED_CHANNELS
    assert "celestia_tpu/utils/flight.py" in SANCTIONED_CHANNELS
    for rel in SANCTIONED_CHANNELS:
        assert _ids(_lint(R3_CHANNEL_CLOCK_OK, rel)) == [], rel


def test_r3_sanctioned_channels_still_ban_entropy():
    got = _ids(_lint(R3_CHANNEL_ENTROPY_BAD, "celestia_tpu/utils/tracing.py"))
    # random.getrandbits flagged; the clock read sanctioned
    assert got == ["consensus-determinism"], got


def test_r3_channel_scan_does_not_leak_to_other_utils():
    # a non-channel utils module keeps the old scope: not scanned at all
    assert _ids(_lint(R3_CHANNEL_ENTROPY_BAD, "celestia_tpu/utils/x.py")) == []


# the clock-offset probe (PR 9): the RPC midpoint estimator reads the
# wall clock twice per sample — sanctioned INSIDE the channel modules
# (tracing.estimate_clock_offset lives there), a finding anywhere a
# consensus module tries to hand-roll it

R3_OFFSET_PROBE = """
    import time


    def estimate_clock_offset(probe_fn):
        t0 = time.time()
        peer_ts = probe_fn()
        t1 = time.time()
        return peer_ts - (t0 + t1) / 2.0
"""

R3_OFFSET_PROBE_VIA_CHANNEL = """
    from celestia_tpu.utils.telemetry import clock


    def estimate_clock_offset(probe_fn):
        t0 = clock()
        peer_ts = probe_fn()
        t1 = clock()
        return peer_ts - (t0 + t1) / 2.0
"""


def test_r3_offset_probe_sanctioned_in_channel_modules():
    # the probe's direct clock reads are the design inside the channel
    assert _ids(_lint(R3_OFFSET_PROBE, "celestia_tpu/utils/tracing.py")) == []


def test_r3_offset_probe_flagged_in_consensus_modules():
    # a consensus module hand-rolling the midpoint probe reads the wall
    # clock twice: two findings, not a silent pass
    got = _ids(_lint(R3_OFFSET_PROBE, "celestia_tpu/da/fixture.py"))
    assert got == ["consensus-determinism"] * 2, got
    # routed through the sanctioned clock() it is clean anywhere
    assert _ids(
        _lint(R3_OFFSET_PROBE_VIA_CHANNEL, "celestia_tpu/da/fixture.py")
    ) == []


# ---------------------------------------------------------------------------
# R4 hostpool-discipline
# ---------------------------------------------------------------------------

R4_BAD = """
    from celestia_tpu.utils import native


    def extend(square):
        return native.extend_block_cpu(square, nthreads=4)


    def helper(x, nthreads=2):
        return x
"""

R4_GOOD = """
    from celestia_tpu.utils import hostpool, native


    def extend(square, nthreads=None):
        return native.extend_block_cpu(square, nthreads=nthreads)


    def extend_explicit(square):
        return native.extend_block_cpu(
            square, nthreads=hostpool.cpu_threads()
        )
"""


def test_r4_fires_on_literal_thread_counts():
    got = _ids(_lint(R4_BAD))
    assert got == ["hostpool-discipline", "hostpool-discipline"], got


def test_r4_quiet_on_pool_sourced_counts():
    assert _ids(_lint(R4_GOOD)) == []


# ---------------------------------------------------------------------------
# R5 sanctioned-retry
# ---------------------------------------------------------------------------

R5_BAD_SWALLOW = """
    def pump(node):
        try:
            node.tick()
        except Exception:
            pass
        try:
            node.close()
        except:
            pass
"""

R5_BAD_SLEEP_LOOP = """
    import time


    def wait(node, h):
        while node.height < h:
            time.sleep(0.05)
"""

R5_BAD_SLEEP_ALIASES = """
    import time as _time
    from time import sleep


    def wait(node, h):
        for _ in range(10):
            _time.sleep(0.1)
        while True:
            sleep(0.1)
"""

R5_GOOD = """
    from celestia_tpu.utils import faults


    def pump(node):
        try:
            node.tick()
        except Exception as e:
            faults.note("gossip.pump", e)
        except ValueError:
            pass


    def wait(node, h):
        faults.RetryPolicy(base_s=0.05, deadline_s=30.0).poll(
            lambda: node.height >= h, what="height"
        )


    def once():
        import time

        time.sleep(0.1)  # not in a loop: plain pacing is fine
"""

R5_SUPPRESSED = """
    import time


    def pace():
        while True:
            # celint: allow(sanctioned-retry) — fixed-cadence pacing tick
            time.sleep(1.0)
"""


def test_r5_fires_on_silent_swallows():
    got = _ids(_lint(R5_BAD_SWALLOW))
    assert got == ["sanctioned-retry", "sanctioned-retry"], got


def test_r5_fires_on_sleep_retry_loops():
    assert _ids(_lint(R5_BAD_SLEEP_LOOP)) == ["sanctioned-retry"]
    got = _ids(_lint(R5_BAD_SLEEP_ALIASES))
    assert got == ["sanctioned-retry", "sanctioned-retry"], got


def test_r5_quiet_on_recorded_failures_and_policy_waits():
    assert _ids(_lint(R5_GOOD)) == []


def test_r5_suppression_with_reason_holds():
    out = _lint(R5_SUPPRESSED)
    assert _ids(out) == []
    assert any(f.suppressed for f in out)


def test_r5_sanctions_faults_module_itself():
    assert (
        _ids(_lint(R5_BAD_SLEEP_LOOP, relpath="celestia_tpu/utils/faults.py"))
        == []
    )


# ---------------------------------------------------------------------------
# directive hygiene
# ---------------------------------------------------------------------------


def test_allow_without_reason_is_a_finding():
    out = _lint(
        """
        x = 1  # celint: allow(hostpool-discipline)
        """
    )
    assert _ids(out) == ["bad-suppression"]


def test_unused_allow_is_a_finding():
    out = _lint(
        """
        x = 1  # celint: allow(hostpool-discipline) — stale excuse
        """
    )
    assert _ids(out) == ["unused-suppression"]


def test_comment_line_allow_attaches_to_next_statement():
    src = """
        from celestia_tpu.utils import native


        def extend(square):
            return native.extend_block_cpu(
                square,
                # celint: allow(hostpool-discipline) — fixture reason
                nthreads=4,
            )
    """
    out = _lint(src)
    assert _ids(out) == []
    assert any(f.suppressed for f in out)


def test_rule_aliases_resolve():
    assert {
        ALIASES[a] for a in ("r1", "r2", "r3", "r4", "r5", "r6", "r7", "r8")
    } == set(REGISTRY)


def test_rules_subset_runs_only_named_rules():
    out = _lint(R2_BAD, rules=["r3"])
    assert _ids(out) == []  # R2 findings only exist when R2 is enabled


# ---------------------------------------------------------------------------
# R6 lock-order (whole-program: fixtures go through lint_program)
# ---------------------------------------------------------------------------

R6_MOD_A_CYCLE = """
    import threading

    from celestia_tpu.node import fixture_b as b

    A_LOCK = threading.Lock()


    def grab_a():
        with A_LOCK:
            pass


    def a_then_b():
        with A_LOCK:
            b.grab_b()
"""

R6_MOD_B_CYCLE = """
    import threading

    from celestia_tpu.node import fixture_a as a

    B_LOCK = threading.Lock()


    def grab_b():
        with B_LOCK:
            pass


    def b_then_a():
        with B_LOCK:
            a.grab_a()
"""

R6_MOD_B_CONSISTENT = """
    import threading

    from celestia_tpu.node import fixture_a as a

    B_LOCK = threading.Lock()


    def grab_b():
        with B_LOCK:
            pass


    def also_a_then_b():
        # same order as fixture_a: a consistent hierarchy, no cycle
        with a.A_LOCK:
            grab_b()
"""


def _lint_pair(src_a: str, src_b: str, rules=("r6",)):
    return lint_program(
        {
            "celestia_tpu/node/fixture_a.py": textwrap.dedent(src_a),
            "celestia_tpu/node/fixture_b.py": textwrap.dedent(src_b),
        },
        rules,
    )


def test_r6_fires_on_cross_module_two_lock_cycle():
    out = _lint_pair(R6_MOD_A_CYCLE, R6_MOD_B_CYCLE)
    got = _ids(out)
    assert got == ["lock-order"], out
    msg = out[0].message
    # the finding carries the full acquisition chain, both hops sited
    assert "A_LOCK" in msg and "B_LOCK" in msg and "fixture" in msg, msg


def test_r6_quiet_on_consistent_cross_module_order():
    assert _ids(_lint_pair(R6_MOD_A_CYCLE, R6_MOD_B_CONSISTENT)) == []


R6_SELF_DEADLOCK = """
    import threading

    _LOCK = threading.Lock()


    def outer():
        with _LOCK:
            inner()


    def inner():
        with _LOCK:
            pass
"""


def test_r6_flags_plain_lock_self_deadlock():
    out = _lint(R6_SELF_DEADLOCK, rules=["r6"])
    assert _ids(out) == ["lock-order"], out
    assert "self-deadlock" in out[0].message


def test_r6_rlock_reacquisition_is_legal():
    src = R6_SELF_DEADLOCK.replace("threading.Lock()", "threading.RLock()")
    assert _ids(_lint(src, rules=["r6"])) == []


R6_LOCKED_CONVENTION = """
    import threading


    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def put(self, x):
            with self._lock:
                self._insert_locked(x)

        def _insert_locked(self, x):
            # caller holds self._lock; acquiring nothing here is the
            # convention working — no self-edge, no finding
            self._items.append(x)
"""

R6_LOCKED_CONVENTION_VIOLATED = """
    import threading


    class Box:
        def __init__(self):
            self._lock = threading.Lock()

        def put(self, x):
            with self._lock:
                self._insert_locked(x)

        def _insert_locked(self, x):
            # *_locked promises the caller holds the lock; re-acquiring
            # it here is the self-deadlock the suffix exists to prevent
            with self._lock:
                pass
"""


def test_r6_locked_convention_honored():
    assert _ids(_lint(R6_LOCKED_CONVENTION, rules=["r6"])) == []


def test_r6_locked_function_reacquiring_is_flagged():
    out = _lint(R6_LOCKED_CONVENTION_VIOLATED, rules=["r6"])
    assert _ids(out) == ["lock-order"], out


R6_ANNOTATION_ONLY = """
    import threading

    _OTHER = threading.Lock()
    _STATE = {}  # celint: guarded-by(_EXTERNAL_LOCK)


    def a_then_b():
        with _EXTERNAL_LOCK:
            with _OTHER:
                pass


    def b_then_a():
        with _OTHER:
            with _EXTERNAL_LOCK:
                pass
"""


def test_r6_annotation_only_locks_participate():
    # _EXTERNAL_LOCK is never constructed here (guarded-by names it);
    # the AB/BA nesting must still form a cycle
    out = _lint(R6_ANNOTATION_ONLY, rules=["r6"])
    assert _ids(out) == ["lock-order"], out


def test_r6_lock_graph_exposes_decl_sites():
    from celestia_tpu.lint.engine import ModuleContext, Program
    from celestia_tpu.lint.lockorder import build_lock_graph, lock_decl_sites

    src = textwrap.dedent(R6_MOD_A_CYCLE)
    program = Program(
        [ModuleContext("celestia_tpu/node/fixture_a.py", src)]
    )
    graph = build_lock_graph(program)
    sites = lock_decl_sites(graph)
    line = src.splitlines().index("A_LOCK = threading.Lock()") + 1
    assert ("celestia_tpu/node/fixture_a.py", line) in sites
    assert sites[("celestia_tpu/node/fixture_a.py", line)].endswith("A_LOCK")


# NOTE: specs/lock_hierarchy.md drift needs no dedicated test — R6
# emits a drift finding on every full-tree run, so the repo gate below
# fails with the regeneration command in its message.


# ---------------------------------------------------------------------------
# R7 host-sync
# ---------------------------------------------------------------------------

R7_BAD_EACH_FORM = """
    import jax
    import jax.numpy as jnp
    import numpy as np


    def bad_item(xs):
        arr = jnp.asarray(xs)
        return arr.sum().item()


    def bad_block(xs):
        arr = jnp.asarray(xs)
        jax.block_until_ready(arr)
        return arr


    def bad_asarray(xs):
        out = jnp.cumsum(jnp.asarray(xs))
        return np.asarray(out)


    def bad_np_array(xs):
        dev = jax.device_put(xs)
        return np.array(dev)


    def bad_scalar(xs):
        total = jnp.asarray(xs)
        return float(total), int(total), bool(total)
"""

R7_GOOD_BRACKETED = """
    import jax.numpy as jnp
    import numpy as np

    from celestia_tpu.utils import devprof


    def good(xs, fn):
        arr = jnp.asarray(xs)
        d = devprof.dispatch("fixture", n=1)
        out = d.done(fn(arr))
        return np.asarray(out)  # drained through the bracket: fine


    def good_unpack(xs, fn):
        d = devprof.dispatch("fixture", n=1)
        out = d.done(fn(jnp.asarray(xs)))
        roots, data = out
        return np.asarray(roots), np.asarray(data)


    def good_statement_form(xs, fn):
        arr = jnp.asarray(xs)
        d = devprof.dispatch("fixture", n=1)
        out = fn(arr)
        d.done(out)
        return np.asarray(out)
"""

R7_JIT_HANDLE = """
    import numpy as np


    def bad(square, _extend_fn):
        fn = _extend_fn
        out = fn(square)
        return out


    def bad_factory(square):
        fn = _build_extend_fn(16)
        out = fn(square)
        return np.asarray(out)
"""


def test_r7_fires_on_each_banned_sync_form():
    got = _ids(_lint(R7_BAD_EACH_FORM, "celestia_tpu/da/fixture.py", ["r7"]))
    # .item, block_until_ready, np.asarray, np.array, float+int+bool
    assert got.count("host-sync") == 7, got


def test_r7_quiet_when_bracketed_through_devprof():
    assert (
        _ids(_lint(R7_GOOD_BRACKETED, "celestia_tpu/da/fixture.py", ["r7"]))
        == []
    )


def test_r7_infers_jit_handles():
    got = _ids(_lint(R7_JIT_HANDLE, "celestia_tpu/ops/fixture.py", ["r7"]))
    assert got == ["host-sync"], got  # np.asarray(out) via the *_fn factory


def test_r7_scoped_to_hot_path_packages():
    # the same code outside da/ops/state is not scanned
    assert _ids(_lint(R7_BAD_EACH_FORM, "celestia_tpu/node/fixture.py", ["r7"])) == []
    assert _ids(_lint(R7_BAD_EACH_FORM, "celestia_tpu/utils/fixture.py", ["r7"])) == []


def test_r7_allow_with_reason_suppresses():
    src = """
        import jax


        def sync_point(arr):
            # celint: allow(host-sync) — fixture: deliberate timing boundary
            jax.block_until_ready(arr)
            return arr
    """
    out = _lint(src, "celestia_tpu/ops/fixture.py", ["r7"])
    assert _ids(out) == []
    assert any(f.suppressed for f in out)


# ---------------------------------------------------------------------------
# R8 layering
# ---------------------------------------------------------------------------


def test_r8_flags_state_importing_node():
    out = _lint(
        "from celestia_tpu.node.bft import Vote\n",
        "celestia_tpu/state/fixture.py",
        ["r8"],
    )
    assert _ids(out) == ["layering"], out


def test_r8_flags_lazy_back_edge_imports():
    src = """
        def helper():
            from celestia_tpu.client.remote import RemoteNode

            return RemoteNode
    """
    out = _lint(src, "celestia_tpu/node/fixture.py", ["r8"])
    assert _ids(out) == ["layering"], out


def test_r8_allows_forward_edges():
    src = """
        from celestia_tpu.appconsts import SHARE_SIZE
        from celestia_tpu.da.dah import DataAvailabilityHeader
        from celestia_tpu.ops import rs
        from celestia_tpu.utils import hostpool
    """
    assert _ids(_lint(src, "celestia_tpu/state/fixture.py", ["r8"])) == []


def test_r8_same_package_imports_are_free():
    out = _lint(
        "from celestia_tpu.node.mempool import Mempool\n",
        "celestia_tpu/node/fixture.py",
        ["r8"],
    )
    assert _ids(out) == []


def test_r8_catches_package_root_and_relative_spellings():
    # the package the alias names, not node.module, carries the layer
    out = _lint(
        "from celestia_tpu import node\n",
        "celestia_tpu/state/fixture.py",
        ["r8"],
    )
    assert _ids(out) == ["layering"], out
    # relative import resolved against the file's own package
    out = _lint(
        "from ..node import bft\n",
        "celestia_tpu/state/fixture.py",
        ["r8"],
    )
    assert _ids(out) == ["layering"], out
    # relative import of a LOWER layer stays clean
    out = _lint(
        "from ..utils import hostpool\n",
        "celestia_tpu/state/fixture.py",
        ["r8"],
    )
    assert _ids(out) == []


# ---------------------------------------------------------------------------
# machine-readable output + stats
# ---------------------------------------------------------------------------


def test_json_format_carries_stats_and_suppression_state():
    import json

    from celestia_tpu.lint import LintStats, render_json

    stats = LintStats()
    findings = lint_program(
        {
            "celestia_tpu/da/fixture.py": (
                "import time\n"
                "# celint: allow(consensus-determinism) — fixture reason\n"
                "T = time.time()\n"
            )
        },
        stats=stats,
    )
    doc = json.loads(render_json(findings, stats=stats))
    assert doc["failing"] == 0 and doc["suppressed"] == 1
    sup = [f for f in doc["findings"] if f["suppressed"]]
    assert sup and sup[0]["suppress_reason"] == "fixture reason"
    assert doc["stats"]["files"] == 1
    assert "consensus-determinism" in doc["stats"]["rules"]
    assert doc["stats"]["total_wall_ms"] > 0


def test_sarif_format_is_valid_and_stable():
    import json

    from celestia_tpu.lint import render_sarif

    findings = lint_source(
        textwrap.dedent(R5_BAD_SLEEP_LOOP)
        + "# celint: allow(sanctioned-retry) — x\ny = 1\n",
        "celestia_tpu/node/fixture.py",
    )
    doc = json.loads(render_sarif(findings))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "celint"
    results = run["results"]
    assert results, "expected at least one SARIF result"
    r = results[0]
    assert r["ruleId"] and r["locations"][0]["physicalLocation"][
        "artifactLocation"
    ]["uri"].startswith("celestia_tpu/")
    assert r["locations"][0]["physicalLocation"]["region"]["startLine"] >= 1
    # suppressed findings are carried as SARIF suppressions, not dropped
    unused = [x for x in results if x["ruleId"] == "unused-suppression"]
    assert unused  # the dangling allow above surfaces


def test_cli_format_flag_and_exit_codes():
    import json as _json

    from celestia_tpu.lint.__main__ import main

    # a clean directory in json format exits 0 and parses
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["celestia_tpu/lint", "--format", "json"])
    assert rc == 0
    doc = _json.loads(buf.getvalue())
    assert doc["failing"] == 0
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["celestia_tpu/lint", "--format", "sarif"])
    assert rc == 0
    assert _json.loads(buf.getvalue())["version"] == "2.1.0"


# ---------------------------------------------------------------------------
# the real gate + the runtime guard (ONE shared full-tree pass: each
# 8-rule pass costs ~2.5 s and tier-1 truncates at 870 s, so the gate,
# the wall budget and the suppression audit all read the same run)
# ---------------------------------------------------------------------------

_FULL_RUN: dict = {}


def _full_tree_run():
    if not _FULL_RUN:
        from celestia_tpu.lint import LintStats

        stats = LintStats()
        _FULL_RUN["findings"] = run_lint(stats=stats)
        _FULL_RUN["stats"] = stats
    return _FULL_RUN["findings"], _FULL_RUN["stats"]


def test_repo_tree_lints_clean_with_all_rules():
    # all eight rules, incl. the specs/lock_hierarchy.md drift check
    # (an R6 finding carrying the regeneration command)
    findings, _ = _full_tree_run()
    bad = failing(findings)
    assert not bad, "celint findings:\n" + "\n".join(f.format() for f in bad)


def test_full_tree_lint_stays_inside_wall_budget():
    _, stats = _full_tree_run()
    # generous bound: the full 8-rule pass runs ~2-3 s today; an order
    # of magnitude is the alarm threshold, not the target — the whole-
    # program pass must never become a visible slice of tier-1
    assert stats.total_wall_ms < 30_000, stats.to_dict()
    assert stats.files > 50
    # per-rule timing is populated for every registered rule
    assert set(stats.to_dict()["rules"]) >= set(REGISTRY)


def test_every_tree_suppression_is_explained():
    findings, _ = _full_tree_run()
    for f in findings:
        if f.suppressed:
            assert f.suppress_reason, f.format()
