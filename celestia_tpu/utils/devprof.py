"""Device-side observability: the DEVICE half of the tracing plane.

The span tracer (utils/tracing.py) sees host wall-clock only: with JAX's
async dispatch the ``extend.jax`` span measures ENQUEUE time, not where
the ~8 ms of device work at k=128 actually goes.  This module closes the
gap with three mechanisms, all built on the same sanctioned telemetry
clock and the same bounded-structure idioms as the rest of the plane:

* **Per-dispatch device timing** (:func:`dispatch`).  A dispatch bracket
  stamps t0 (before the jitted call), t1 (the call returned — enqueue
  complete) and, after ``jax.block_until_ready``, t2 (device drained).
  The t1→t2 interval is recorded as a span on a synthetic per-chip
  **"device" Chrome-trace track** (``thread_name="device:<platform>:<id>"``,
  one track per chip) parented under the host span that issued the
  dispatch — Perfetto shows host spans, enqueue time and device
  occupancy on one timeline, and dispatch gaps become visible pixels.
  The interval is queue-wait PLUS execution (an upper bound on
  occupancy): splitting the two needs the XLA profiler, which is the
  optional :func:`start_profiler` capture below.
* **XLA cost/memory accounting** (:func:`note_compile`).  Once per
  (kernel, arg-shapes) — deduped through a bounded :class:`LruCache` —
  the jitted function is AOT-lowered and compiled, and the measured
  compile time plus ``cost_analysis()`` FLOPs / bytes-accessed land in
  the kernel table (``celestia_tpu_xla_*`` on the exposition).  The
  2108.02692 roofline numbers become mechanical telemetry.
* **Device-memory watermarks** (:func:`sample_memory`).  Each completed
  dispatch (and every time-series snapshot) samples
  ``device.memory_stats()``; ``bytes_in_use`` / ``peak_bytes_in_use``
  (+ the fraction of ``bytes_limit`` when the platform reports one)
  become gauges and device-span args.

**CPU degradation contract** (tests/test_devprof.py): every one of
these degrades to a telemetry *note*, never an exception —
``memory_stats()`` returning None (CPU), ``cost_analysis()``
absent/raising on the platform, the profiler flag set without a TPU.
A CPU backend still gets a device track (``device:cpu:0``): the XLA CPU
stream has the same async-dispatch blind spot.

Activation: device-track spans ride the ONE tracing switch
(``tracing.enabled()``) — a traced node gets the device track with no
extra flag.  Bench legs that want occupancy/cost stats without the
trace ring arm the module directly via :func:`collect`.  Disabled, the
hot path pays one function call returning a shared no-op.

The optional ``jax.profiler`` capture (``--device-profile DIR`` /
``CELESTIA_TPU_DEVICE_PROFILE``) wraps :func:`start_profiler` /
:func:`stop_profiler` around the node's lifetime and writes a
TensorBoard/XPlane trace next to (not instead of) this module's
Chrome-track accounting.

celint R3: this module is on the SANCTIONED_CHANNELS list — its clock
reads go through :func:`telemetry.clock` and the entropy bans still
apply inside it.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Tuple

from celestia_tpu.utils import tracing
from celestia_tpu.utils.lru import LruCache
from celestia_tpu.utils.telemetry import (
    Log2Histogram,
    clock,
    escape_label_value,
    sanitize_metric_name,
)

ENV_PROFILE = "CELESTIA_TPU_DEVICE_PROFILE"

# Synthetic Chrome tid base for the per-chip device tracks: far above
# any OS thread id so device tracks never collide with host threads in
# the merged timeline (tid = base + device ordinal).
DEVICE_TID_BASE = 1 << 40

# bounded caps on the accounting maps: a kernel table can only hold as
# many rows as there are distinct jitted programs, but a hostile/buggy
# caller must not grow it without bound
_MAX_KERNELS = 128
_MAX_NOTES = 64

_lock = threading.Lock()
_force = False  # bench-style collection armed without the tracer
_window_t0: float = clock()  # occupancy window start (reset())
# per-device busy seconds + dispatch counts; celint: guarded-by(_lock)
_busy_s: Dict[str, float] = {}
_dispatch_counts: Dict[str, int] = {}
# per-kernel cost/compile accounting; celint: guarded-by(_lock)
_kernels: Dict[str, dict] = {}
# degradation notes (CPU fallbacks, platform gaps): kind -> {count, last};
# celint: guarded-by(_lock)
_notes: Dict[str, dict] = {}
# per-dispatch-name duration histograms; celint: guarded-by(_lock)
_dispatch_hist: Dict[str, Log2Histogram] = {}
# per-leg H2D/D2H transfer accounting (bytes + ms + event counts);
# celint: guarded-by(_lock)
_transfers: Dict[str, dict] = {}
_MAX_TRANSFER_LEGS = 64
# last sampled memory watermark; celint: guarded-by(_lock)
_mem: Optional[dict] = None
# previous occupancy probe (ts, summed busy seconds) for the
# inter-sample gauge; celint: guarded-by(_lock)
_probe_prev: Optional[Tuple[float, float]] = None
# one compile note per (kernel, shapes): bounded, R2-compliant
_seen_compiles = LruCache("devprof_compiles", 256, register=False)
# outstanding background cost-compile threads; celint: guarded-by(_lock)
_compile_threads: List[threading.Thread] = []
_MAX_OUTSTANDING_COMPILES = 8
_profiler_dir: Optional[str] = None


def active() -> bool:
    """True when dispatch bracketing is armed: the tracer is on (the
    device track rides the one tracing switch) or a :func:`collect`
    window is open (bench stats without the trace ring)."""
    return _force or tracing.enabled()


def note(kind: str, exc: BaseException) -> None:
    """Record a degradation note (bounded): the CPU-only contract is
    that every platform gap lands HERE, never as an exception on the
    block path."""
    with _lock:
        rec = _notes.get(kind)
        if rec is None:
            if len(_notes) >= _MAX_NOTES:
                return
            rec = _notes[kind] = {"count": 0, "last": ""}
        rec["count"] += 1
        rec["last"] = repr(exc)[:200]


def reset() -> None:
    """Drop all accounting and restart the occupancy window (bench leg
    boundary / tests).  Outstanding background cost-compiles are joined
    FIRST so a late-landing kernel row can never leak into the next
    epoch's table."""
    global _window_t0, _mem, _probe_prev
    flush_compiles()
    with _lock:
        _busy_s.clear()
        _dispatch_counts.clear()
        _kernels.clear()
        _notes.clear()
        _dispatch_hist.clear()
        _transfers.clear()
        _mem = None
        _probe_prev = None
        _window_t0 = clock()
    _seen_compiles.clear()


def restart_window() -> None:
    """Restart ONLY the occupancy window (busy counters + t0), keeping
    the kernel/cost table and notes.  The bench leg uses it to exclude
    the one-time AOT compile from the dispatch-occupancy measurement."""
    global _window_t0
    with _lock:
        _busy_s.clear()
        _dispatch_counts.clear()
        _window_t0 = clock()


def occupancy_probe() -> Optional[float]:
    """Occupancy percent over the interval since the PREVIOUS probe
    call — the CONTINUOUS sampler's gauge.  ``device_profile()``'s
    window figure is the since-reset aggregate, which on a long-lived
    node decays toward zero regardless of current load; per-interval
    deltas are what an operator alert can act on.  None on the first
    probe or an empty interval (the time-series collector then simply
    omits the metric — skip-absent, like every platform gap)."""
    global _probe_prev
    now = clock()
    with _lock:
        busy = sum(_busy_s.values())
        prev = _probe_prev
        _probe_prev = (now, busy)
    if prev is None:
        return None
    dt = now - prev[0]
    if dt <= 0:
        return None
    return round(max(0.0, min(100.0, 100.0 * (busy - prev[1]) / dt)), 2)


@contextlib.contextmanager
def collect():
    """Arm dispatch/cost collection for a scoped window without the
    tracer (the bench ``extras.device_profile`` leg): stats are reset on
    entry and the occupancy window spans exactly the ``with`` body."""
    global _force
    reset()
    _force = True
    try:
        yield
    finally:
        _force = False


# ---------------------------------------------------------------------------
# dispatch bracketing (the device track)
# ---------------------------------------------------------------------------


class _NullDispatch:
    """Disabled-path dispatch: one shared instance, ``done`` is identity."""

    __slots__ = ()

    def done(self, out):
        return out


NULL_DISPATCH = _NullDispatch()


def _device_of(out):
    """(platform, ordinal) of the device holding ``out`` (first array
    leaf); falls back to the default backend.  Never raises."""
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(out):
            devs = getattr(leaf, "devices", None)
            if callable(devs):
                got = devs()
                if got:
                    d = next(iter(got))
                    return str(d.platform), int(d.id), d
            d = getattr(leaf, "device", None)
            if d is not None and not callable(d):
                return str(d.platform), int(d.id), d
        d = jax.devices()[0]
        return str(d.platform), int(d.id), d
    except Exception as e:
        note("device_of", e)
        return "unknown", 0, None


def _devices_of_sharded(out):
    """Every (platform, ordinal, dev) a sharded output spans, ordinal-
    sorted, or None when no leaf exposes a sharding (single-device
    arrays, host fallbacks).  Never raises — a platform that cannot
    answer degrades to the single-device accounting."""
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(out):
            sh = getattr(leaf, "sharding", None)
            ds = getattr(sh, "device_set", None)
            if ds and len(ds) > 1:
                return sorted(
                    ((str(d.platform), int(d.id), d) for d in ds),
                    key=lambda t: t[1],
                )
    except Exception as e:
        note("devices_of_sharded", e)
    return None


def _sample_memory_of(dev) -> Optional[dict]:
    """memory_stats() of one device folded to the watermark dict, or
    None (CPU backends return None / raise — both degrade to a note).
    Caller holds no lock; only the shared-state write takes it."""
    global _mem
    if dev is None:
        return None
    try:
        stats = dev.memory_stats()
    except Exception as e:
        note("memory_stats", e)
        return None
    if not isinstance(stats, dict):
        note("memory_stats", ValueError(f"memory_stats() -> {type(stats).__name__}"))
        return None
    out = {
        "bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0) or 0),
    }
    limit = stats.get("bytes_limit")
    if isinstance(limit, (int, float)) and limit > 0:
        out["bytes_limit"] = int(limit)
        # frac is CURRENT usage (alertable: it falls when pressure
        # clears); peak_frac is the monotone lifetime high-water mark
        # (informational: jax never lowers it)
        out["frac"] = round(out["bytes_in_use"] / float(limit), 4)
        out["peak_frac"] = round(out["peak_bytes_in_use"] / float(limit), 4)
    with _lock:
        _mem = dict(out)
    return out


def sample_memory() -> Optional[dict]:
    """One watermark sample of the default device (the time-series
    collector's entry): ``{"bytes_in_use", "peak_bytes_in_use"
    [, "bytes_limit", "peak_frac"]}`` or None on a platform without
    memory stats (noted, never raised)."""
    try:
        import jax

        dev = jax.devices()[0]
    except Exception as e:
        note("devices", e)
        return None
    return _sample_memory_of(dev)


class Dispatch:
    """One device dispatch bracket.  Construct BEFORE the jitted call
    (stamps enqueue start), call :meth:`done` with the call's result —
    it blocks until the device drains, records the device-track span and
    the occupancy stats, and returns the result unchanged.

    With ``multi=True`` (sharded dispatches — parallel/sharded.py) the
    t1→t2 interval is recorded on EVERY chip the output is sharded
    across: one device-track span and one busy contribution per chip.
    All chips execute the collective program concurrently, so charging
    the full interval to each is the same queue-wait-plus-execution
    upper bound the single-device bracket records."""

    __slots__ = ("name", "args", "_t0", "_parent", "_multi")

    def __init__(self, name: str, args: dict, multi: bool = False):
        self.name = name
        self.args = args
        self._multi = multi
        self._parent = tracing.current()
        self._t0 = clock()

    def done(self, out):
        import jax

        t1 = clock()  # enqueue returned; device may still be running
        try:
            jax.block_until_ready(out)
        except Exception as e:
            # a device lost mid-dispatch: the caller sees ITS error from
            # its own consumption of `out`; profiling must not preempt it
            note("block_until_ready", e)
            return out
        t2 = clock()
        devices = _devices_of_sharded(out) if self._multi else None
        if not devices:
            devices = [_device_of(out)]
        busy = max(0.0, t2 - t1)
        with _lock:
            for platform, ordinal, _dev in devices:
                key = f"{platform}:{ordinal}"
                _busy_s[key] = _busy_s.get(key, 0.0) + busy
            _dispatch_counts[self.name] = _dispatch_counts.get(self.name, 0) + 1
            hist = _dispatch_hist.get(self.name)
            if hist is None:
                hist = _dispatch_hist[self.name] = Log2Histogram()
        hist.observe(busy)
        # per-CHIP watermarks: each device track carries its OWN memory
        # numbers (an HBM imbalance across a sharded dispatch is exactly
        # what per-track spans exist to show); the module-level _mem
        # keeps the last sample, same as the single-device bracket
        mems = {
            ordinal: _sample_memory_of(dev)
            for _platform, ordinal, dev in devices
        }
        if tracing.enabled():
            for platform, ordinal, _dev in devices:
                key = f"{platform}:{ordinal}"
                span_args = dict(self.args)
                span_args["enqueue_ms"] = round((t1 - self._t0) * 1000.0, 3)
                span_args["device"] = key
                mem = mems.get(ordinal)
                if mem is not None:
                    span_args["mem_bytes_in_use"] = mem["bytes_in_use"]
                    span_args["mem_peak_bytes"] = mem["peak_bytes_in_use"]
                tracing.record_span(
                    f"device.{self.name}",
                    t1,
                    t2,
                    parent=self._parent,
                    cat="device",
                    tid=DEVICE_TID_BASE + ordinal,
                    thread_name=f"device:{key}",
                    **span_args,
                )
        return out


def dispatch(name: str, multi_device: bool = False, **args) -> Any:
    """Open a dispatch bracket (no-op shared instance when inactive).
    ``multi_device=True`` records the bracket on every chip a sharded
    output spans (one span per device track)."""
    if not active():
        return NULL_DISPATCH
    return Dispatch(name, args, multi=multi_device)


# ---------------------------------------------------------------------------
# H2D/D2H transfer accounting (the device-resident plane's ledger)
# ---------------------------------------------------------------------------


def record_transfer(
    leg: str, direction: str, nbytes: int, ms: float = 0.0
) -> None:
    """Charge one host<->device crossing to a named leg (``direction`` is
    ``"h2d"`` or ``"d2h"``).  Bytes are computed by the caller from array
    SHAPES — recording a transfer must never itself force one.  Inactive
    (no tracer, no :func:`collect` window), this is a no-op: the hot path
    pays one call + a bool."""
    if not active():
        return
    if direction not in ("h2d", "d2h"):
        raise ValueError(f"direction must be h2d/d2h, got {direction!r}")
    with _lock:
        rec = _transfers.get(leg)
        if rec is None:
            if len(_transfers) >= _MAX_TRANSFER_LEGS:
                return
            rec = _transfers[leg] = {
                "h2d_bytes": 0, "h2d_ms": 0.0, "h2d_events": 0,
                "d2h_bytes": 0, "d2h_ms": 0.0, "d2h_events": 0,
            }
        rec[f"{direction}_bytes"] += int(nbytes)
        rec[f"{direction}_ms"] += float(ms)
        rec[f"{direction}_events"] += 1


def fetch(leg: str, values):
    """``jax.device_get`` with transfer accounting: ONE batched D2H fetch
    of the whole pytree, charged to ``leg`` with its measured wall ms and
    the fetched byte count.  The sanctioned bulk-fetch primitive of the
    device-resident plane — per-array ``np.asarray`` pays a round trip
    each AND is invisible to the transfer ledger."""
    import jax

    if not active():
        return jax.device_get(values)
    t0 = clock()
    out = jax.device_get(values)
    ms = (clock() - t0) * 1000.0
    nbytes = 0
    try:
        for leaf in jax.tree_util.tree_leaves(out):
            nbytes += int(getattr(leaf, "nbytes", 0) or 0)
    except Exception as e:
        note("transfer_nbytes", e)
    record_transfer(leg, "d2h", nbytes, ms)
    return out


def transfer_accounting() -> Dict[str, dict]:
    """Per-leg transfer ledger snapshot:
    ``{leg: {h2d_bytes, h2d_ms, h2d_events, d2h_bytes, d2h_ms,
    d2h_events}}`` (bench ``extras.transfer_accounting`` + the
    device-resident smoke's only-sanctioned-D2H assertion)."""
    with _lock:
        return {
            leg: {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in rec.items()
            }
            for leg, rec in sorted(_transfers.items())
        }


# ---------------------------------------------------------------------------
# XLA cost / compile accounting
# ---------------------------------------------------------------------------


def _shape_key(args: Tuple[Any, ...]) -> tuple:
    return tuple(
        (tuple(getattr(a, "shape", ()) or ()), str(getattr(a, "dtype", "")))
        for a in args
    )


def _cost_fields(compiled) -> dict:
    """flops / bytes_accessed out of ``cost_analysis()`` across the
    jax-version shapes it has taken (dict, or list-of-dicts per
    partition); platform gaps fold to notes."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        note("cost_analysis", e)
        return out
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        note("cost_analysis", ValueError(f"cost_analysis() -> {type(ca).__name__}"))
        return out
    for field, keys in (
        ("flops", ("flops",)),
        ("bytes_accessed", ("bytes accessed", "bytes_accessed")),
    ):
        for k in keys:
            v = ca.get(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[field] = float(v)
                break
    return out


def _run_compile(name: str, fn, args: Tuple[Any, ...]) -> None:
    """The background cost-compile body (one daemon thread per first
    sighting): measure the AOT lower+compile, harvest cost/memory
    analysis, land the kernel row.  Every failure is a note."""
    try:
        t0 = clock()
        try:
            compiled = fn.lower(*args).compile()
        except Exception as e:
            note(f"compile.{name}", e)
            return
        compile_ms = (clock() - t0) * 1000.0
        rec = {"compile_ms": round(compile_ms, 3)}
        rec.update(_cost_fields(compiled))
        try:
            mem = compiled.memory_analysis()
            for attr in ("temp_size_in_bytes", "output_size_in_bytes"):
                v = getattr(mem, attr, None)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    rec[attr.replace("_size_in_bytes", "_bytes")] = int(v)
        except Exception as e:
            note("memory_analysis", e)
        with _lock:
            if name in _kernels or len(_kernels) < _MAX_KERNELS:
                _kernels[name] = rec
    finally:
        me = threading.current_thread()
        with _lock:
            if me in _compile_threads:
                _compile_threads.remove(me)


def note_compile(name: str, fn, args: Tuple[Any, ...]) -> None:
    """Record compile time + XLA cost analysis for a jitted kernel, once
    per (name, arg shapes/dtypes).  The AOT lower+compile runs on a
    BACKGROUND daemon thread — a traced validator's block path must
    never stall to measure itself (the jitted call already compiled the
    program; this build exists only for the cost/compile figures).  The
    measured wall time of that build IS the recorded compile figure.
    Outstanding builds are bounded (excess first-sightings are dropped
    with a note) and joinable via :func:`flush_compiles` (bench/tests/
    gates read the table deterministically).  Every platform gap
    (``lower`` unsupported, ``cost_analysis`` absent) degrades to a
    note; the kernel row still lands with whatever fields resolved."""
    if not active():
        return
    if not _seen_compiles.add_if_absent((name, _shape_key(args))):
        return
    t = threading.Thread(
        target=_run_compile, args=(name, fn, args),
        name=f"devprof-compile-{name}", daemon=True,
    )
    with _lock:
        # note() ALSO takes _lock — it must be called after release (it
        # was not, once: celint R6's founding self-deadlock, hit exactly
        # when the outstanding-compile cap fired under armed profiling)
        dropped = len(_compile_threads) >= _MAX_OUTSTANDING_COMPILES
        if not dropped:
            _compile_threads.append(t)
    if dropped:
        note(
            "compile_queue",
            RuntimeError(f"outstanding-compile cap hit; dropped {name}"),
        )
        return
    t.start()


def flush_compiles(timeout_s: float = 60.0) -> None:
    """Join every outstanding background cost-compile (bench legs and
    the smoke gates call this before reading the kernel table)."""
    deadline = clock() + timeout_s
    while True:
        with _lock:
            threads = list(_compile_threads)
        if not threads:
            return
        for t in threads:
            t.join(timeout=max(0.0, deadline - clock()))
        if clock() >= deadline:
            return


# ---------------------------------------------------------------------------
# aggregate views (bench extras, time series, exposition)
# ---------------------------------------------------------------------------


def device_profile() -> dict:
    """The one-document device profile: per-kernel FLOPs/bytes/compile
    ms, per-dispatch counts + busy ms, occupancy over the current window
    (busy / wall, summed across chips), the last memory watermark, the
    degradation notes, and the backend identity.  Safe on any platform —
    a CPU-only process reports its CPU "chip" and folds the gaps to
    notes (the bench host-only leg records exactly this)."""
    try:
        import jax

        platform = str(jax.default_backend())
        num_devices = int(jax.local_device_count())
    except Exception as e:
        note("backend", e)
        platform, num_devices = "unavailable", 0
    with _lock:
        busy = dict(_busy_s)
        counts = dict(_dispatch_counts)
        kernels = {k: dict(v) for k, v in _kernels.items()}
        notes = {k: dict(v) for k, v in _notes.items()}
        mem = dict(_mem) if _mem is not None else None
        t0 = _window_t0
    wall_s = max(1e-9, clock() - t0)
    busy_ms_total = sum(busy.values()) * 1000.0
    return {
        "platform": platform,
        "num_devices": num_devices,
        "kernels": kernels,
        "dispatches": counts,
        "device_busy_ms": {k: round(v * 1000.0, 3) for k, v in busy.items()},
        "device_busy_ms_total": round(busy_ms_total, 3),
        "window_s": round(wall_s, 3),
        # mean occupancy ACROSS chips: multi-device brackets charge the
        # interval to every chip they span, so the wall denominator must
        # scale with the chips that reported busy time — a single-wall
        # denominator would inflate by the chip count and pin a mesh
        # node at the 100% cap, killing the falling-occupancy regression
        # signal exactly where it matters
        "device_occupancy_pct": round(
            min(
                100.0,
                100.0
                * busy_ms_total
                / (wall_s * 1000.0 * max(1, len(busy))),
            ),
            2,
        ),
        "mem": mem if mem is not None else {"available": False},
        "notes": notes,
    }


def dispatch_summary() -> Dict[str, dict]:
    """Per-dispatch-name duration aggregates (count/p50/p95/p99/max)."""
    with _lock:
        hists = dict(_dispatch_hist)
    return {name: h.summary() for name, h in sorted(hists.items())}


def exposition_lines() -> List[str]:
    """Prometheus lines for the device plane (``celestia_tpu_xla_*`` +
    ``celestia_tpu_device_*``), appended to the node's Metrics
    exposition by node/server.py.  Every line passes the shared
    format-validity gate."""
    with _lock:
        kernels = {k: dict(v) for k, v in _kernels.items()}
        busy = dict(_busy_s)
        notes_total = sum(v["count"] for v in _notes.values())
        mem = dict(_mem) if _mem is not None else None
    lines: List[str] = []
    for name, rec in sorted(kernels.items()):
        label = escape_label_value(sanitize_metric_name(name))
        for field in ("flops", "bytes_accessed", "compile_ms"):
            v = rec.get(field)
            if isinstance(v, (int, float)):
                lines.append(
                    f'celestia_tpu_xla_{field}{{kernel="{label}"}} {v}'
                )
    for key, sec in sorted(busy.items()):
        label = escape_label_value(key)
        lines.append(
            f'celestia_tpu_device_busy_ms{{device="{label}"}} '
            f"{round(sec * 1000.0, 3)}"
        )
    if mem is not None:
        lines.append(
            f"celestia_tpu_device_mem_bytes_in_use {mem['bytes_in_use']}"
        )
        lines.append(
            f"celestia_tpu_device_mem_peak_bytes {mem['peak_bytes_in_use']}"
        )
        if "peak_frac" in mem:
            lines.append(
                f"celestia_tpu_device_mem_peak_frac {mem['peak_frac']}"
            )
    lines.append(f"celestia_tpu_devprof_notes_total {notes_total}")
    return lines


# ---------------------------------------------------------------------------
# optional jax.profiler capture (--device-profile)
# ---------------------------------------------------------------------------


def start_profiler(log_dir: str) -> bool:
    """Start a ``jax.profiler`` trace capture into ``log_dir`` (the
    TensorBoard/XPlane format — per-op device timelines the Chrome
    track cannot see).  Returns False and records a note when the
    platform cannot capture (the flag set without a TPU must never
    raise)."""
    global _profiler_dir
    if _profiler_dir is not None:
        return True  # already capturing; one session per process
    try:
        import jax

        jax.profiler.start_trace(log_dir)
    except Exception as e:
        note("profiler.start", e)
        return False
    _profiler_dir = str(log_dir)
    return True


def stop_profiler() -> Optional[str]:
    """Stop the capture; returns the log dir when one was running (and
    stopped cleanly), None otherwise."""
    global _profiler_dir
    if _profiler_dir is None:
        return None
    out, _profiler_dir = _profiler_dir, None
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as e:
        note("profiler.stop", e)
        return None
    return out


def profiler_dir() -> Optional[str]:
    return _profiler_dir
