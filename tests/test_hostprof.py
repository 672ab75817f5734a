"""Host sampling profiler (utils/hostprof.py): sampling, span
attribution, folded/Chrome exports, bounds, and the disarmed-overhead
pin.  Everything here runs on tiny fixtures — the tier-1 budget
(tools/t1_budget.py) is a hard 30 s per test."""

import hashlib
import threading
import time

import pytest

from celestia_tpu.utils import hostprof, tracing
from celestia_tpu.utils.telemetry import clock


@pytest.fixture(autouse=True)
def _clean():
    hostprof.stop()
    hostprof.clear()
    tracing.disable()
    tracing.clear()
    yield
    hostprof.stop()
    hostprof.clear()
    tracing.disable()
    tracing.clear()


def _busy_until(deadline_s):
    x = 0
    while clock() < deadline_s:
        for i in range(2000):
            x += i * i
    return x


# ---------------------------------------------------------------------------
# sampling basics
# ---------------------------------------------------------------------------


def test_sample_once_records_other_threads_not_self():
    hostprof.start(0.1)  # armed, but the thread tick is ~10 s away:
    # sample_once() drives sampling deterministically
    n = hostprof.sample_once()
    assert n >= 1  # at least this test's thread
    me = threading.get_ident()
    for s in hostprof.samples():
        assert s["tid"] != 0
        assert s["stack"], "empty stack recorded"
        assert s["thread"]
    # the sampler thread never profiles itself (its tid is not ours to
    # assert directly; sample_once ran on THIS thread, so this thread's
    # own frames ARE expected — taken via sys._current_frames)
    assert any(s["tid"] == me for s in hostprof.samples())


def test_sampler_thread_collects_continuously():
    # modest expectations on purpose: this runs on a contended 1-core
    # CI host mid-suite, where GIL pressure can starve the sampler
    # thread's wakeups — the test proves the thread LIVES and collects;
    # the 2% overhead contract is a quiet-host figure, not pinned here
    hostprof.start(250.0)
    deadline = clock() + 0.3
    _busy_until(deadline)
    hostprof.stop()
    st = hostprof.stats()
    assert st["samples_total"] >= 3, st
    assert st["ticks"] >= 3, st
    assert st["samples_per_s"] > 0
    # sanity ceiling only (a tick over a handful of threads is ~tens of
    # µs; even heavily contended it cannot approach the window)
    assert st["overhead_pct"] < 25.0, st


def test_disarmed_is_noop_and_records_nothing():
    assert not hostprof.enabled()
    assert hostprof.sample_once() == 0
    assert hostprof.samples() == []
    assert hostprof.folded_stacks() == {}
    assert hostprof.top_frames() == []
    assert hostprof.chrome_events() == []
    assert hostprof.exposition_lines() == []


def test_disarmed_overhead_under_one_percent():
    """The disarmed profiler must be invisible next to real work (same
    style as tracing's disabled-overhead pin): the measured cost of 10k
    disarmed sample_once() calls — one module-bool check each — must be
    under 1% of a 10k-iteration hashing loop's wall.  The two are timed
    SEPARATELY (cost-of-calls vs cost-of-work): subtracting two long
    loop timings would measure host-load jitter, not the 40 ns check."""
    assert not hostprof.enabled()
    payload = b"\xab" * 49152

    t0 = clock()
    for _ in range(10_000):
        hashlib.sha256(payload).digest()
    t_loop = clock() - t0

    t0 = clock()
    for _ in range(10_000):
        hostprof.sample_once()  # disarmed: one bool check
    t_calls = clock() - t0

    # absolute: tracing's own disabled bound (10k entries < 50 ms)
    assert t_calls < 0.05, f"disarmed sampler: {t_calls * 1e3:.1f} ms / 10k"
    # relative: under 1% of the 10k-iteration work loop
    ratio = t_calls / t_loop
    assert ratio < 0.01, (
        f"disarmed sampler cost {ratio * 100:.2f}% of the 10k loop "
        f"(calls {t_calls * 1e3:.2f} ms vs work {t_loop * 1e3:.1f} ms)"
    )


# ---------------------------------------------------------------------------
# span attribution (the tracing.thread_span join)
# ---------------------------------------------------------------------------


def test_samples_join_to_the_sampled_threads_active_span():
    tracing.enable(4)
    hostprof.start(0.1)
    stop_evt = threading.Event()

    def worker():
        with tracing.span("attr.work", cat="test"):
            stop_evt.wait(2.0)

    t = threading.Thread(target=worker, name="attr-worker")
    t.start()
    try:
        # wait for the worker to enter its span, then sample it
        deadline = clock() + 2.0
        joined = []
        while clock() < deadline and not joined:
            hostprof.sample_once()
            joined = [
                s for s in hostprof.samples() if s["span"] == "attr.work"
            ]
        assert joined, "no sample joined to the worker's active span"
        s = joined[-1]
        assert s["span_id"] > 0
        assert s["thread"] == "attr-worker"
        # the folded key carries the span segment so flamegraphs group
        # untraced frames UNDER the span that owns them
        keys = [k for k in hostprof.folded_stacks() if "span:attr.work" in k]
        assert keys and keys[0].startswith("attr-worker;span:attr.work;")
    finally:
        stop_evt.set()
        t.join()


def test_hostpool_task_frames_land_under_its_run_span():
    """The ISSUE's attribution join: a busy hostpool task's frames must
    land under its ``hostpool.task`` span."""
    from celestia_tpu.utils import hostpool

    # pin a 2-thread pool: on a 1-core CI host run_sharded would run
    # inline and no worker thread would ever exist to sample
    hostpool.set_cpu_threads(2)
    try:
        tracing.enable(4)
        hostprof.start(500.0)

        def task(i):
            deadline = clock() + 0.15
            return _busy_until(deadline)

        with tracing.span("pool.parent", cat="test"):
            hostpool.run_sharded(task, [0, 1])
    finally:
        hostpool.set_cpu_threads(None)
    hostprof.stop()
    joined = [
        s for s in hostprof.samples() if s["span"] == "hostpool.task"
    ]
    assert joined, (
        "no sample landed under a hostpool.task span; spans seen: "
        f"{sorted({s['span'] for s in hostprof.samples() if s['span']})}"
    )
    assert any(s["thread"].startswith("celestia-host") for s in joined)


def test_between_spans_attribution_is_empty():
    tracing.enable(4)
    hostprof.start(0.1)
    hostprof.sample_once()
    me = threading.get_ident()
    mine = [s for s in hostprof.samples() if s["tid"] == me]
    assert mine and mine[-1]["span_id"] == 0 and mine[-1]["span"] == ""


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------


def test_folded_text_format_and_ordering():
    hostprof.start(0.1)
    for _ in range(3):
        hostprof.sample_once()
    text = hostprof.folded_text()
    assert text
    lines = text.strip().splitlines()
    counts = []
    for ln in lines:
        stack, _, count = ln.rpartition(" ")
        assert stack and ";" in stack or stack  # thread-only stacks legal
        counts.append(int(count))
    assert counts == sorted(counts, reverse=True)


def test_chrome_events_schema_and_merged_dump():
    tracing.enable(4)
    hostprof.start(0.1)
    with tracing.span("merge.span", cat="test"):
        hostprof.sample_once()
    evs = hostprof.chrome_events()
    assert evs
    for ev in evs:
        assert ev["ph"] == "i" and ev["cat"] == "sample"
        assert {"name", "ts", "pid", "tid"} <= set(ev)
    dump = hostprof.merged_trace_dump()
    assert tracing.validate_chrome_trace(dump) == []
    cats = [e for e in dump["traceEvents"] if e.get("cat") == "sample"]
    assert cats, "merged dump lost the sample events"
    assert dump["otherData"]["host_samples"] == len(evs)
    # sampled-but-unspanned threads still get a thread_name metadata row
    named_tids = {
        e["tid"]
        for e in dump["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    for ev in cats:
        assert ev["tid"] in named_tids


def test_top_frames_self_time_ranking():
    hostprof.start(0.1)
    for _ in range(5):
        hostprof.sample_once()
    top = hostprof.top_frames(3)
    assert top
    assert top == sorted(top, key=lambda e: -e["samples"])
    assert all(0 <= e["pct"] <= 100 for e in top)


def test_exposition_lines_parse():
    from celestia_tpu.utils.telemetry import validate_exposition

    hostprof.start(0.1)
    hostprof.sample_once()
    lines = hostprof.exposition_lines()
    assert any("celestia_tpu_hostprof_samples_total" in ln for ln in lines)
    assert validate_exposition("\n".join(lines) + "\n") == []


# ---------------------------------------------------------------------------
# bounds
# ---------------------------------------------------------------------------


def test_sample_ring_is_bounded():
    hostprof.start(0.1)
    for _ in range(40):
        hostprof.sample_once()
    assert len(hostprof.samples()) <= hostprof.MAX_SAMPLES
    st = hostprof.stats()
    assert st["samples_kept"] <= hostprof.MAX_SAMPLES
    assert st["folded_unique"] <= hostprof.MAX_FOLDED


def test_stack_depth_is_bounded():
    def deep(n):
        if n == 0:
            hostprof.sample_once()
            return
        deep(n - 1)

    hostprof.start(0.1)
    deep(hostprof.MAX_STACK_DEPTH + 40)
    me = threading.get_ident()
    mine = [s for s in hostprof.samples() if s["tid"] == me]
    assert mine
    stack = mine[-1]["stack"]
    assert len(stack) <= hostprof.MAX_STACK_DEPTH
    # the LEAF end (the code on-CPU) survives truncation
    assert stack[-1].endswith(".sample_once") or "deep" in stack[-1]


def test_stats_window_freezes_on_stop():
    hostprof.start(200.0)
    deadline = clock() + 0.1
    _busy_until(deadline)
    hostprof.stop()
    st1 = hostprof.stats()
    time.sleep(0.15)
    st2 = hostprof.stats()
    assert st2["window_s"] == st1["window_s"]
    assert st2["overhead_pct"] == st1["overhead_pct"]
