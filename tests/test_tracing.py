"""Block-lifecycle span tracing (utils/tracing.py) + bounded histograms.

Covers the PR-8 observability plane end to end:

* tracer unit behavior — contextvar nesting, explicit cross-thread
  parents, monotonic ids, ring bounds, disabled-path no-ops;
* Log2Histogram quantiles + the Prometheus exposition (every emitted
  line must parse — the format-validity gate for cache names with dots
  and dashes);
* the instrumented block lifecycle on a live TestNode: the span tree
  contains prepare -> square_build -> extend -> roots, hostpool task
  spans nest under the extend phase in the host-fallback regime, the
  EDS-cache hit shows up on the warm process leg;
* the Metrics / TraceDump RPC plane over a real gRPC server;
* structural determinism: two runs of the same block sequence under the
  same chaos seed produce identical span trees (names + parentage +
  counts; durations explicitly excluded).
"""

import json
import threading

import numpy as np
import pytest

from celestia_tpu.utils import tracing
from celestia_tpu.utils.telemetry import (
    BUCKET_BOUNDS,
    Log2Histogram,
    Telemetry,
    escape_label_value,
    sanitize_metric_name,
)


@pytest.fixture
def tracer():
    """Fresh, enabled tracer; guaranteed teardown (tracing is process
    state, same discipline as the chaos fixture)."""
    tracing.disable()
    tracing.clear()
    tracing.enable(8)
    yield tracing
    tracing.disable()
    tracing.clear()


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------


def test_span_nesting_and_parentage(tracer):
    with tracing.block_span("prepare_proposal", height=7):
        with tracing.span("filter_txs"):
            pass
        with tracing.span("extend"):
            with tracing.span("roots"):
                tracing.instant("eds_cache.miss", leg="prepare")
    traces = tracing.block_traces()
    assert len(traces) == 1
    tr = traces[0]
    assert tr.height == 7 and tr.complete
    # tree() sorts children by name (completion order is timing, not
    # structure): "extend" sorts before "filter_txs"
    assert tr.tree() == {
        "name": "prepare_proposal",
        "children": [
            {
                "name": "extend",
                "children": [{"name": "roots", "children": []}],
            },
            {"name": "filter_txs", "children": []},
        ],
    }
    assert len(tr.instants) == 1
    assert tr.instants[0]["name"] == "eds_cache.miss"


def test_span_ids_monotonic_never_random(tracer):
    ids = []
    with tracing.block_span("prepare_proposal", height=1) as root:
        ids.append(root.span_id)
        for _ in range(5):
            with tracing.span("x") as s:
                ids.append(s.span_id)
    assert ids == sorted(ids)
    assert len(set(ids)) == len(ids)


def test_cross_thread_parenting(tracer):
    """Pool-style explicit parent capture: the worker's spans nest under
    the submitting thread's span even though contextvars don't cross."""
    with tracing.block_span("prepare_proposal", height=2):
        with tracing.span("extend") as parent:
            def worker():
                with tracing.span("hostpool.task", parent=parent, index=0):
                    pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
    tr = tracing.block_traces()[0]
    extend = [n for n in tr.tree()["children"] if n["name"] == "extend"][0]
    assert {"name": "hostpool.task", "children": []} in extend["children"]


def test_ring_buffer_keeps_last_n(tracer):
    tracing.enable(3)
    for h in range(1, 8):
        with tracing.block_span("prepare_proposal", height=h):
            pass
    heights = [tr.height for tr in tracing.block_traces()]
    assert heights == [5, 6, 7]
    assert [tr.height for tr in tracing.block_traces(last=2)] == [6, 7]


def test_per_block_span_cap_counts_drops(tracer):
    with tracing.block_span("prepare_proposal", height=1):
        for _ in range(tracing.MAX_SPANS_PER_BLOCK + 10):
            with tracing.span("x"):
                pass
    tr = tracing.block_traces()[0]
    # the root is exempt from the cap (it finishes last; dropping it
    # would orphan every child), so an over-full block keeps cap+1
    assert len(tr.spans) <= tracing.MAX_SPANS_PER_BLOCK + 1
    assert tr.dropped >= 10
    assert tr.tree()["name"] == "prepare_proposal"
    assert tr.tree()["children"], "overflow must truncate, not empty, the tree"
    assert tracing.TRACER.phase_breakdown(tr)["total_ms"] > 0.0


def test_disabled_is_noop_and_allocation_free():
    tracing.disable()
    tracing.clear()
    assert tracing.span("x") is tracing.NULL_SPAN
    assert tracing.block_span("y", height=1) is tracing.NULL_SPAN
    assert tracing.current() is None
    tracing.instant("z")  # no-op, no error
    with tracing.span("x") as s:
        s.annotate(anything="goes")  # NULL_SPAN absorbs annotations
    assert tracing.block_traces() == []


def test_disabled_overhead_under_microseconds():
    """The <50 ms prepare gate must not notice a disabled tracer: 10k
    disabled span entries must cost well under a millisecond total."""
    import time

    tracing.disable()
    t0 = time.perf_counter()
    for _ in range(10_000):
        with tracing.span("hot"):
            pass
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.05, f"disabled tracer cost {elapsed*1e3:.1f} ms / 10k spans"


def test_error_in_span_annotates_and_propagates(tracer):
    with pytest.raises(ValueError):
        with tracing.block_span("prepare_proposal", height=1):
            with tracing.span("extend"):
                raise ValueError("boom")
    tr = tracing.block_traces()[0]
    extend = [s for s in tr.spans if s.name == "extend"][0]
    assert "boom" in extend.args["error"]


def test_trace_dump_schema_valid(tracer):
    with tracing.block_span("prepare_proposal", height=3):
        with tracing.span("extend"):
            tracing.instant("eds_cache.miss")
    dump = tracing.trace_dump()
    assert tracing.validate_chrome_trace(dump) == []
    json.dumps(dump)  # serializable as-is for Perfetto
    names = [e["name"] for e in dump["traceEvents"] if e["ph"] == "X"]
    assert "prepare_proposal" in names and "extend" in names


def test_background_spans_outside_blocks(tracer):
    with tracing.span("das_sample", cat="serving", height=1, row=0, col=0):
        pass
    dump = tracing.trace_dump()
    names = [e["name"] for e in dump["traceEvents"] if e.get("ph") == "X"]
    assert "das_sample" in names


# ---------------------------------------------------------------------------
# bounded histograms + exposition hygiene
# ---------------------------------------------------------------------------


def test_log2_histogram_bounds_and_quantiles():
    h = Log2Histogram()
    for ms in (1, 1, 2, 4, 8, 100):
        h.observe(ms / 1000.0)
    s = h.summary()
    assert s["count"] == 6
    assert s["max_ms"] == pytest.approx(100.0)
    # log2 buckets: within-2x accuracy is the contract
    assert 0.5 <= s["p50_ms"] <= 4.0
    assert 8.0 <= s["p99_ms"] <= 200.0
    assert s["p50_ms"] <= s["p90_ms"] <= s["p95_ms"] <= s["p99_ms"] <= s["max_ms"]


def test_log2_histogram_is_bounded_memory():
    h = Log2Histogram()
    for i in range(100_000):
        h.observe((i % 977) / 10_000.0)
    assert len(h.counts) == len(BUCKET_BOUNDS) + 1
    assert h.count == 100_000


def test_histogram_prometheus_lines_cumulative():
    h = Log2Histogram()
    h.observe(0.001)
    h.observe(0.5)
    lines = h.prometheus_lines("m_seconds")
    assert lines[0] == "# TYPE m_seconds histogram"
    bucket_counts = [
        int(ln.rsplit(" ", 1)[1]) for ln in lines if "_bucket" in ln
    ]
    assert bucket_counts == sorted(bucket_counts)  # cumulative
    assert bucket_counts[-1] == 2  # +Inf holds everything
    assert any(ln.startswith("m_seconds_sum ") for ln in lines)
    assert "m_seconds_count 2" in lines


def test_metric_name_sanitization():
    assert sanitize_metric_name("prepare_proposal.filter_ms") == (
        "prepare_proposal_filter_ms"
    )
    assert sanitize_metric_name("row-memo.v2") == "row_memo_v2"
    assert sanitize_metric_name("9lives") == "_9lives"
    assert sanitize_metric_name("ok_name") == "ok_name"
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


def _assert_exposition_valid(text: str):
    # the ONE validator (shared with make trace-smoke): every line must
    # be blank, a TYPE/HELP comment, or a sample — the parse gate the
    # satellite task demands
    from celestia_tpu.utils.telemetry import validate_exposition

    bad = validate_exposition(text)
    assert bad == [], f"malformed exposition lines: {bad!r}"


def test_exposition_validator_rejects_malformed_lines():
    from celestia_tpu.utils.telemetry import validate_exposition

    assert validate_exposition('m{cache="a"} 1\nm_count 2\n') == []
    assert validate_exposition('m{cache="a"b"} 1') != []  # unescaped quote
    assert validate_exposition("weird.name 1") != []  # bad metric name
    assert validate_exposition("m --..e") != []  # junk value
    assert validate_exposition("m 1.5e-03") == []  # scientific value ok


def test_export_prometheus_every_line_parses():
    from celestia_tpu.utils.lru import LruCache

    t = Telemetry()
    t.incr("blocks")
    t.incr("weird.name-with/chars")
    t.gauge("height", 42)
    t.measure_since("prepare_proposal", __import__("time").time() - 0.05)
    t.observe("prepare_proposal.filter_ms", 12.0)
    # a cache whose NAME carries dots and dashes: must come out as an
    # escaped label value, never a malformed metric name
    cache = LruCache("weird.cache-name", 4)
    cache.put(b"k", b"v")
    cache.get(b"k")
    cache.get(b"missing")
    text = t.export_prometheus()
    _assert_exposition_valid(text)
    assert 'cache="weird.cache-name"' in text
    assert "celestia_tpu_prepare_proposal_seconds_bucket" in text
    assert 'le="+Inf"' in text
    del cache  # release the registry slot


def test_summary_reports_p99(tracer):
    t = Telemetry()
    for ms in range(1, 101):
        t.observe("op", float(ms))
    s = t.summary()
    assert set(s["op"]) >= {"count", "p50_ms", "p90_ms", "p95_ms", "p99_ms", "max_ms"}
    assert s["op"]["count"] == 100
    assert s["op"]["p99_ms"] >= s["op"]["p50_ms"]
    # span aggregates ride along when the tracer is on
    with tracing.block_span("prepare_proposal", height=1):
        pass
    assert "prepare_proposal" in t.summary()["spans"]


def test_export_concurrent_with_writers():
    """The Metrics RPC made export/summary a remote surface invoked
    while producer threads insert first-time metric names: the scrape
    must never raise 'dictionary changed size during iteration'."""
    t = Telemetry()
    stop = threading.Event()
    errors = []

    def writer(n):
        i = 0
        while not stop.is_set():
            t.incr(f"c{n}_{i % 64}")
            t.observe(f"m{n}_{i % 64}", 1.0)
            i += 1

    def scraper():
        try:
            for _ in range(100):
                t.export_prometheus()
                t.summary()
        except Exception as e:  # pragma: no cover - the failure we pin
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(n,)) for n in range(2)]
    for w in writers:
        w.start()
    s = threading.Thread(target=scraper)
    s.start()
    s.join()
    stop.set()
    for w in writers:
        w.join()
    assert errors == []


def test_histogram_empty_summary_and_quantile():
    h = Log2Histogram()
    assert h.summary()["count"] == 0
    assert h.quantile(0.5) == 0.0
    _assert_exposition_valid("\n".join(h.prometheus_lines("empty_seconds")))


# ---------------------------------------------------------------------------
# instrumented block lifecycle on a live node
# ---------------------------------------------------------------------------


def _names(node):
    """Flatten a tree() node into a set of span names."""
    out = {node["name"]}
    for c in node["children"]:
        out |= _names(c)
    return out


def _find(node, name):
    if node["name"] == name:
        return node
    for c in node["children"]:
        hit = _find(c, name)
        if hit is not None:
            return hit
    return None


def _make_node_and_send(seed: bytes):
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.state.tx import MsgSend
    from celestia_tpu.utils.secp256k1 import PrivateKey

    key = PrivateKey.from_seed(seed)
    node = TestNode(
        funded_accounts=[(key, 10**12)],
        genesis_time_ns=1_700_000_000_000_000_000,
        auto_produce=False,
    )
    signer = Signer(node, key)
    return node, signer, MsgSend(signer.address, b"\x11" * 20, 1000)


def _broadcast(signer, msgs):
    """Sign + broadcast WITHOUT the confirm poll (these nodes have
    auto_produce off; the tests produce blocks explicitly)."""
    return signer._broadcast(lambda: signer.sign_tx(msgs).marshal())


def test_block_lifecycle_span_tree(tracer):
    """The acceptance tree: prepare -> square_build -> extend -> roots,
    and the warm process leg annotated with the EDS-cache hit."""
    from celestia_tpu.da import eds_cache

    eds_cache.clear()
    node, signer, msg = _make_node_and_send(b"trace-lifecycle")
    res = _broadcast(signer, [msg])
    assert res.code == 0, res.log
    node.produce_block()
    traces = tracing.block_traces()
    prep = [t for t in traces if t.name == "prepare_proposal"][-1]
    proc = [t for t in traces if t.name == "process_proposal"][-1]
    tree = prep.tree()
    assert {"filter_txs", "square_build", "extend", "roots"} <= _names(tree)
    extend = _find(tree, "extend")
    assert _find(extend, "roots") is not None, "roots must nest under extend"
    # the proposer's own process leg hits the content-addressed EDS
    # cache: its extend span is a lookup, annotated as such
    proc_extend = [s for s in proc.spans if s.name == "extend"]
    assert proc_extend and proc_extend[0].args.get("eds_cache") == "hit"
    # the proposer's own leg extended the square: a miss, on the same arg
    prep_extend = [s for s in prep.spans if s.name == "extend"]
    assert prep_extend and prep_extend[0].args.get("eds_cache") == "miss"
    # heights recorded on the roots
    assert prep.height == node.height and proc.height == node.height


def test_hostpool_task_spans_nest_under_extend(tracer, monkeypatch):
    """Host DAH leg with no native library: new_data_availability_header
    fans the numpy roots over the hostpool, and each task's queue-wait +
    run spans nest under the extend phase — the phase-tail gap made
    visible."""
    from celestia_tpu.da import dah as dah_mod
    from celestia_tpu.utils import hostpool
    from celestia_tpu.utils import native as native_mod

    if hostpool.cpu_threads() < 2:
        pytest.skip("needs a multi-worker pool for pool-fanned roots")
    monkeypatch.setattr(native_mod, "available", lambda: False)
    monkeypatch.setattr(dah_mod, "_host_native_available", lambda: True)
    k = 4
    rng = np.random.default_rng(3)
    eds = dah_mod.ExtendedDataSquare(
        rng.integers(0, 256, (2 * k, 2 * k, 512), dtype=np.uint8)
    )
    tracing.clear()
    with tracing.block_span("prepare_proposal", height=2):
        with tracing.span("extend"):
            dah_mod.new_data_availability_header(eds)
    tr = tracing.block_traces()[0]
    tree = tr.tree()
    extend = _find(tree, "extend")
    roots = _find(extend, "roots")
    assert roots is not None
    child_names = [c["name"] for c in roots["children"]]
    assert "hostpool.task" in child_names, child_names
    assert "hostpool.queue_wait" in child_names, child_names
    # queue-wait spans live on the SUBMITTER's track (they start at
    # submit time; the worker's own track would garble its run spans)
    sub_tid = threading.get_ident()
    waits = [s for s in tr.spans if s.name == "hostpool.queue_wait"]
    assert waits and all(s.tid == sub_tid for s in waits)
    tasks = [s for s in tr.spans if s.name == "hostpool.task"]
    assert tasks and any(s.tid != sub_tid for s in tasks), (
        "run spans should sit on worker threads"
    )
    # queue waits overlap on the submitter's track, so they export as
    # async b/e pairs — still a schema-valid Chrome document
    dump = tracing.trace_dump()
    assert tracing.validate_chrome_trace(dump) == []
    async_begins = [
        e for e in dump["traceEvents"]
        if e.get("ph") == "b" and e["name"] == "hostpool.queue_wait"
    ]
    assert async_begins and all("id" in e for e in async_begins)
    # the intra-extend pipeline tail is surfaced per phase
    bd = tracing.TRACER.phase_breakdown(tr)
    assert "extend_untraced_ms" in bd and bd["extend_untraced_ms"] >= 0.0


def test_trace_determinism_same_chaos_seed(tracer):
    """Two runs of the same block sequence under the same chaos seed
    produce structurally identical span trees — names, parentage, span
    counts.  Durations differ; structure must not."""
    from celestia_tpu.da import eds_cache
    from celestia_tpu.utils import faults

    def run_once():
        eds_cache.clear()
        faults.disarm()
        # same seed => same injection schedule => same degraded paths
        faults.arm("lru.put", "fail_rate", rate=0.5, seed=1234)
        tracing.clear()
        try:
            node, signer, msg = _make_node_and_send(b"determinism")
            res = _broadcast(signer, [msg])
            assert res.code == 0, res.log
            node.produce_block()
            res = _broadcast(
                signer, [type(msg)(signer.address, b"\x22" * 20, 500)]
            )
            assert res.code == 0, res.log
            node.produce_block()
            # collector pauses are timing, not structure: left out
            return [
                (tr.name, tr.height, len(tr.structural_spans()), tr.tree())
                for tr in tracing.block_traces()
            ]
        finally:
            faults.disarm()

    first = run_once()
    second = run_once()
    assert first == second
    assert len(first) == 4  # 2 blocks x (prepare + process)


def _children(tr, parent):
    return [s for s in tr.spans if s.parent_id == parent.span_id]


def test_process_proposal_spans_cover_the_block(tracer, monkeypatch):
    """A cold ProcessProposal of multi-blob PFBs on the device plane:
    decode_and_ante splits into its four ante.* stages with their counts,
    the device plane's host side into its five extend.* stages — the
    upload and launch before the ante, the wait, fetch and assembly
    after it — the block key hash has a span of its own beside extend,
    and the root's own time is a small remainder."""
    import jax

    from celestia_tpu.client import txsim
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import device_plane, eds_cache, inclusion
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.utils.secp256k1 import PrivateKey

    keys = [PrivateKey.from_seed(b"trace-ante-%d" % i) for i in range(2)]
    node = TestNode(
        funded_accounts=[(key, 10**15) for key in keys], auto_produce=False
    )
    rng = np.random.default_rng(5)
    signers = [Signer(node, key) for key in keys]
    txs = [
        signers[i % 2].pay_for_blob_tx([
            txsim.random_blob(rng, 2 * i, 400),
            txsim.random_blob(rng, 2 * i + 1, 100),
        ])(sequence=i // 2)
        for i in range(3)
    ]
    app = node.app

    def cold_process(prep):
        # a foreign block: every proposal-lifecycle cache misses
        eds_cache.clear()
        app._sig_cache.clear()
        app._decoded_cache.clear()
        inclusion._COMMITMENT_CACHE.clear()
        ok, reason = app.process_proposal(
            prep.block_txs, prep.square_size, prep.data_root
        )
        assert ok, reason

    # On the CPU backend the "device" is the host's own cores: the program
    # launched before the ante would run on them beside the host thread and
    # could preempt it between two spans.  Let it finish inside its launch
    # span, as a chip computes without taking the host's cores.
    begin_extend = device_plane.begin_extend

    def begin_and_drain(square):
        pending = begin_extend(square)
        jax.block_until_ready(pending.out)
        return pending

    monkeypatch.setattr(device_plane, "begin_extend", begin_and_drain)
    with device_plane.forced("on"):
        prep = app.prepare_proposal(txs)
        cold_process(prep)  # compiles; the traced call below is warm
        tracing.clear()
        cold_process(prep)
    tr = [t for t in tracing.block_traces() if t.name == "process_proposal"][-1]
    root = [s for s in tr.spans if s.span_id == tr.root_id][0]
    children = _children(tr, root)
    top = {s.name: s for s in children}
    assert {"decode_and_ante", "square_build", "eds_cache.key", "extend"} <= set(top)
    # the miss launches the extend before the ante and finishes it after
    extends = [s for s in children if s.name == "extend"]
    assert [s.args.get("stage") for s in extends] == ["launch", "finish"]
    assert all(s.args["leg"] == "process" for s in extends)
    assert extends[0].t1 <= top["decode_and_ante"].t0
    assert top["decode_and_ante"].t1 <= extends[1].t0
    assert extends[1].args["eds_cache"] == "miss"
    counters = app.telemetry.summary()["counters"]
    assert counters["extend_overlap_process"] == counters["eds_cache_miss_process"]
    assert top["eds_cache.key"].args["bytes"] == sum(len(t) for t in prep.block_txs)
    assert "cpu_ms" in top["decode_and_ante"].args

    ante = _children(tr, top["decode_and_ante"])
    by_name = {}
    for s in ante:
        by_name.setdefault(s.name, []).append(s)
    assert {"ante.decode", "ante.commitments", "ante.sig_verify", "ante.chain"} <= set(by_name)
    assert all("cpu_ms" in s.args for s in ante if s.cat != tracing.GC_CAT)
    unmarshal = [s for s in by_name["ante.decode"] if s.args["stage"] == "unmarshal"][0]
    assert unmarshal.args["txs"] == 3 and unmarshal.args["cache_hits"] == 0
    assert unmarshal.args["bytes"] == sum(len(t) for t in txs)
    commitments = by_name["ante.commitments"][0].args
    assert commitments["blobs"] == 6
    assert commitments["blob_bytes"] == 3 * (400 + 100)
    assert commitments["shares"] == 6
    assert commitments["tasks"] == 6  # six one-share blobs: a task each
    assert app.telemetry.summary()["counters"].get("commitment_warm_miss", 0) == 0
    assert by_name["ante.sig_verify"][0].args["verified"] == 3
    assert by_name["ante.sig_verify"][0].args["cache_hits"] == 0
    assert by_name["ante.chain"][0].args["txs"] == 3

    plane = {
        s.args["stage"]: s for s in tr.spans if s.name == "extend.device_plane"
    }
    assert set(plane) == {"launch", "finish"}, "the device plane must run the extend"
    assert plane["launch"].parent_id == extends[0].span_id
    assert plane["finish"].parent_id == extends[1].span_id
    stages = {s.name: s for s in _children(tr, plane["launch"])}
    assert {"extend.upload", "extend.launch"} <= set(stages)
    finish = {s.name: s for s in _children(tr, plane["finish"])}
    assert {"extend.wait", "extend.fetch_roots", "extend.assemble"} <= set(finish)
    stages.update(finish)
    k = prep.square_size
    assert k == 4
    assert stages["extend.upload"].args["bytes"] == k * k * 512
    assert stages["extend.fetch_roots"].args["bytes"] == 4 * k * 90

    self_ms = root.duration_ms - sum(s.duration_ms for s in children)
    assert self_ms < 0.1 * root.duration_ms, (self_ms, root.duration_ms)


def test_gc_collection_is_a_span(tracer):
    import gc

    with tracing.block_span("process_proposal", height=1):
        with tracing.span("work") as work:
            gc.collect()
    tr = tracing.block_traces()[0]
    pauses = [s for s in tr.spans if s.name == "gc"]
    assert pauses and pauses[-1].parent_id == work.span_id
    assert pauses[-1].args["generation"] == 2
    assert pauses[-1].args["collected"] >= 0
    assert pauses[-1].t1 >= pauses[-1].t0
    # timing, not structure
    assert tr.tree()["children"] == [{"name": "work", "children": []}]


def test_gc_hook_only_while_tracing():
    import gc

    def ours():
        return [cb for cb in gc.callbacks if getattr(cb, "__self__", None) is tracing.TRACER]

    tracing.disable()
    assert not tracing.gc_hook_installed() and ours() == []
    tracing.enable(4)
    try:
        assert tracing.gc_hook_installed() and len(ours()) == 1
        tracing.enable(4)  # enabling twice registers once
        assert len(ours()) == 1
    finally:
        tracing.disable()
        tracing.clear()
    assert not tracing.gc_hook_installed() and ours() == []
    gc.collect()
    assert tracing.block_traces() == []


def test_cpu_ms_only_where_asked(tracer):
    with tracing.block_span("process_proposal", height=1):
        with tracing.span("busy", cpu=True):
            sum(range(20000))
        with tracing.span("plain"):
            pass
    spans = {s.name: s for s in tracing.block_traces()[0].spans}
    assert spans["busy"].args["cpu_ms"] >= 0.0
    assert "cpu_ms" not in spans["plain"].args


def test_profiler_capture_holds_span_names(tracer, tmp_path):
    """Spans enter a jax.profiler.TraceAnnotation: a capture holds them
    as host events on the profiler's own clock."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.block_span("process_proposal", height=1):
            with tracing.span("ante.sig_verify"):
                jax.block_until_ready(jnp.arange(8) + 1)
            tracing.record_span("queue.only", 0.0, 0.001)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert paths
    host = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            host |= {e.name for line in plane.lines for e in line.events}
    assert {"process_proposal", "ante.sig_verify"} <= host
    assert "queue.only" not in host  # explicit-timestamp spans stay in the ring


def test_extend_levels_hlo_carries_stage_scopes():
    """The fused extend program's ops carry one name scope per stage, so a
    device profile can split its time by stage."""
    import jax

    from celestia_tpu.da import device_plane
    from celestia_tpu.ops.gf256 import active_codec

    fn = device_plane._extend_levels_fn(2, active_codec())
    text = fn.lower(
        jax.ShapeDtypeStruct((2, 2, 512), np.uint8)
    ).as_text(debug_info=True)
    for scope in ("rs", "nmt_leaves", "nmt_inner", "root_tree"):
        assert f"/{scope}/" in text, scope


# ---------------------------------------------------------------------------
# the RPC plane
# ---------------------------------------------------------------------------


def test_metrics_and_trace_dump_rpcs(tracer):
    """Metrics + TraceDump over a real gRPC server, via the RemoteNode
    helpers: the exposition parses line by line, and the dumped trace is
    a schema-valid Chrome document whose prepare tree matches the
    acceptance shape."""
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import eds_cache
    from celestia_tpu.node.server import NodeServer
    from celestia_tpu.state.tx import MsgSend

    eds_cache.clear()
    node, signer, msg = _make_node_and_send(b"trace-rpc")
    with NodeServer(node) as server:
        remote = RemoteNode(server.address, timeout_s=120.0)
        remote_signer = Signer(remote, signer.key)
        raw = remote_signer.sign_tx(
            [MsgSend(signer.address, b"\x33" * 20, 777)]
        ).marshal()
        res = remote.broadcast_tx(raw)
        assert res.code == 0, res.log
        # the served node has no producer loop in this test: produce
        # explicitly after broadcast
        node.produce_block()
        text = remote.metrics()
        _assert_exposition_valid(text)
        assert "celestia_tpu_prepare_proposal_seconds_bucket" in text
        assert "celestia_tpu_span_prepare_proposal_seconds_bucket" in text
        out = remote.trace_dump(last=4)
        remote.close()
    assert out["enabled"] is True
    assert any(b["name"] == "prepare_proposal" for b in out["blocks"])
    dump = out["trace"]
    assert tracing.validate_chrome_trace(dump) == []
    # rebuild the prepare tree from the dumped events alone: the RPC
    # consumer (Perfetto, tooling) sees parentage via args
    events = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
    prep = [e for e in events if e["name"] == "prepare_proposal"][-1]
    children = [
        e["name"] for e in events
        if e["args"].get("parent_id") == prep["args"]["span_id"]
    ]
    assert {"filter_txs", "square_build", "extend"} <= set(children)


def test_trace_dump_rpc_when_disabled():
    from celestia_tpu.client.remote import RemoteNode
    from celestia_tpu.node.server import NodeServer

    tracing.disable()
    tracing.clear()
    node, _signer, _msg = _make_node_and_send(b"trace-off")
    with NodeServer(node) as server:
        remote = RemoteNode(server.address, timeout_s=60.0)
        out = remote.trace_dump()
        remote.close()
    assert out["enabled"] is False
    assert out["blocks"] == []
