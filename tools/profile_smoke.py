"""profile-smoke: the DEVICE observability plane's boot gate
(`make profile-smoke`).

Leg 1 (single process): one tiny-k testnode block with tracing AND the
device track armed — the extension is forced through the device plane
(the leg the chip runs, on whatever backend is present) — and
asserts:

* the merged Chrome trace is schema-valid and contains HOST spans and
  at least one per-chip DEVICE-track event for the same height (the
  `device.*` span inside the prepare block trace, on its synthetic
  `device:<platform>:<id>` track),
* the XLA cost table recorded the fused kernel (FLOPs/bytes/compile ms
  where the platform answers; notes where it cannot — never an error),
* a time-series ring over the node yields >= 2 snapshots whose dump is
  JSON-parseable with computed rates,
* a deliberately-tripped alert rule fires (a recorded degradation
  drives the stock `degradations` rule),
* the node's full Prometheus exposition (incl. the new
  celestia_tpu_xla_* / celestia_tpu_device_* / celestia_tpu_alert_*
  sections) parses line by line.

Leg 2 (one node subprocess): starts a traced validator (no
self-production — a synthetically HEIGHT-STALLED node) with the
plain-HTTP /metrics endpoint, an operator alert rule injected via
CELESTIA_TPU_ALERT_RULES, and a fast sampler cadence; then drives the
REAL CLI — `query timeseries` must return >= 2 snapshots with computed
rates, `query alerts` must show the tripped stall rule — and scrapes
GET /metrics over plain HTTP, asserting the exposition parses and
carries the firing alert gauge.

Exit 0 + one summary JSON line per leg on success; non-zero with the
reason on any failure.  Runs on the CPU backend (no device required —
proving exactly the degradation contract the device PRs rely on).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

# runnable as `python tools/profile_smoke.py` from the repo root
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SMOKE_RULE = {
    "name": "smoke_height_stall",
    "metric": "height",
    "kind": "stall",
    "for_s": 0.5,
}


def leg1() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from celestia_tpu.client.signer import Signer
    from celestia_tpu.da import eds_cache
    from celestia_tpu.node.server import NodeService
    from celestia_tpu.node.testnode import TestNode
    from celestia_tpu.state.tx import MsgSend
    from celestia_tpu.utils import devprof, faults, timeseries, tracing
    from celestia_tpu.utils.secp256k1 import PrivateKey
    from celestia_tpu.utils.telemetry import validate_exposition

    tracing.enable(4)
    tracing.clear()
    devprof.reset()
    eds_cache.clear()
    key = PrivateKey.from_seed(b"profile-smoke")
    node = TestNode(funded_accounts=[(key, 10**12)], auto_produce=False)
    signer = Signer(node, key)
    res = signer._broadcast(
        lambda: signer.sign_tx(
            [MsgSend(signer.address, b"\x22" * 20, 1000)]
        ).marshal()
    )
    if res.code != 0:
        print(f"profile-smoke: broadcast failed: {res.log}", file=sys.stderr)
        return 1
    node.produce_block()

    traces = tracing.block_traces()
    prep = [t for t in traces if t.name == "prepare_proposal"]
    if not prep:
        print("profile-smoke: no prepare trace", file=sys.stderr)
        return 1
    prep = prep[-1]
    host_spans = [s for s in prep.spans if s.cat != "device"]
    device_spans = [s for s in prep.spans if s.cat == "device"]
    if not host_spans:
        print("profile-smoke: prepare trace has no host spans", file=sys.stderr)
        return 1
    if not device_spans:
        print(
            "profile-smoke: no device-track span in the prepare trace "
            f"(spans: {sorted({s.name for s in prep.spans})})",
            file=sys.stderr,
        )
        return 1
    for s in device_spans:
        if s.tid < devprof.DEVICE_TID_BASE or not s.thread_name.startswith(
            "device:"
        ):
            print(
                f"profile-smoke: device span {s.name} not on a device "
                f"track (tid={s.tid}, thread={s.thread_name!r})",
                file=sys.stderr,
            )
            return 1

    # the merged host+device doc must stay a valid Chrome trace and the
    # device track must surface as a named Perfetto thread
    dump = tracing.trace_dump()
    problems = tracing.validate_chrome_trace(dump)
    if problems:
        print(f"profile-smoke: invalid trace JSON: {problems[:5]}", file=sys.stderr)
        return 1
    thread_names = {
        ev["args"]["name"]
        for ev in dump["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    }
    if not any(n.startswith("device:") for n in thread_names):
        print(
            f"profile-smoke: no device thread_name metadata ({thread_names})",
            file=sys.stderr,
        )
        return 1

    # XLA cost accounting recorded the fused kernel (the build runs on
    # a background thread — join it before reading the table)
    devprof.flush_compiles()
    prof = devprof.device_profile()
    if "extend_levels" not in prof["kernels"]:
        print(
            f"profile-smoke: no cost row for extend_levels "
            f"(kernels: {sorted(prof['kernels'])}, notes: {prof['notes']})",
            file=sys.stderr,
        )
        return 1

    # time series: >= 2 snapshots, parseable dump, computed rates
    series = timeseries.TimeSeries(16)
    series.record(timeseries.collect_node_sample(node))
    # deliberately degrade the node so the stock rule trips
    faults.record_degradation("profile_smoke", "deliberate alert trip")
    time.sleep(0.05)
    series.record(timeseries.collect_node_sample(node))
    snapshots = series.samples()
    if len(snapshots) < 2:
        print(f"profile-smoke: only {len(snapshots)} snapshots", file=sys.stderr)
        return 1
    rates = series.rates()
    json.loads(json.dumps({"snapshots": snapshots, "rates": rates}))
    if "height" not in rates:
        print(f"profile-smoke: no computed rates ({sorted(rates)})", file=sys.stderr)
        return 1

    engine = timeseries.AlertEngine(timeseries.default_rules())
    firing = engine.firing(series)
    if not any(a["name"] == "degradations" for a in firing):
        print(
            f"profile-smoke: tripped rule did not fire (firing: "
            f"{[a['name'] for a in firing]})",
            file=sys.stderr,
        )
        return 1

    # the full exposition (incl. xla/device/alert sections) must parse
    service = NodeService(node)
    service.timeseries = series
    bad = validate_exposition(service.metrics_text())
    if bad:
        print(
            f"profile-smoke: malformed exposition lines: {bad[:3]!r}",
            file=sys.stderr,
        )
        return 1

    print(
        json.dumps(
            {
                "profile_smoke": "ok",
                "height": node.height,
                "device_spans": len(device_spans),
                "device_tracks": sorted(
                    n for n in thread_names if n.startswith("device:")
                ),
                "kernels": sorted(prof["kernels"]),
                "snapshots": len(snapshots),
                "alerts_fired": [a["name"] for a in firing],
            }
        )
    )
    return 0


def _readline_deadline(proc, timeout_s: float = 180.0):
    """One stdout line from a subprocess, bounded (same contract as
    tools/trace_smoke.py — a hung validator fails the gate loudly)."""
    import threading

    out = []
    t = threading.Thread(
        target=lambda: out.append(proc.stdout.readline()), daemon=True
    )
    t.start()
    t.join(timeout_s)
    if not out or not out[0]:
        return None
    return out[0]


def leg2() -> int:
    from celestia_tpu.utils.telemetry import validate_exposition

    base = tempfile.mkdtemp(prefix="profile-smoke-")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "3",
        "CELESTIA_TPU_TRACE": "1",
        "CELESTIA_TPU_ALERT_RULES": json.dumps([SMOKE_RULE]),
    }
    home = os.path.join(base, "node")
    r = subprocess.run(
        [
            sys.executable, "-m", "celestia_tpu.cli",
            "--home", home, "init", "--chain-id", "profile-smoke-1",
        ],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    if r.returncode != 0:
        print(f"profile-smoke-node: init failed: {r.stderr}", file=sys.stderr)
        return 1
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "celestia_tpu.cli",
            "--home", home, "start", "--validator",
            "--grpc-address", "127.0.0.1:0",
            "--metrics-port", "0",
            "--timeseries-interval", "0.2",
            "--warm-squares", "",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=REPO,
        env={**env, "CELESTIA_TPU_NODE_ID": "profile-smoke-node"},
    )
    try:
        line = _readline_deadline(proc)
        if line is None or proc.poll() is not None:
            why = "died" if proc.poll() is not None else "hung"
            print(f"profile-smoke-node: validator {why} at startup",
                  file=sys.stderr)
            return 1
        started = json.loads(line)
        addr, http_addr = started["grpc"], started.get("metrics_http")
        if not http_addr:
            print("profile-smoke-node: no metrics_http in startup line",
                  file=sys.stderr)
            return 1
        # a validator with no driver produces no blocks: the injected
        # stall rule needs its for_s of flat samples
        time.sleep(1.2)

        def cli(*args):
            return subprocess.run(
                [sys.executable, "-m", "celestia_tpu.cli", *args],
                capture_output=True, text=True, timeout=120,
                cwd=REPO, env=env,
            )

        # the REAL CLI surface: query timeseries (called twice via the
        # alerts query too, so >= 2 on-demand samples are guaranteed
        # even if the sampler thread lost every race)
        ts = cli("query", "--node", addr, "timeseries")
        if ts.returncode != 0:
            print(f"profile-smoke-node: query timeseries failed: {ts.stderr}",
                  file=sys.stderr)
            return 1
        ts_doc = json.loads(ts.stdout)
        if len(ts_doc["snapshots"]) < 2:
            print(
                f"profile-smoke-node: {len(ts_doc['snapshots'])} snapshots "
                "(need >= 2)",
                file=sys.stderr,
            )
            return 1
        if "height" not in ts_doc["rates"]:
            print(f"profile-smoke-node: no computed rates: {ts_doc['rates']}",
                  file=sys.stderr)
            return 1
        al = cli("query", "--node", addr, "alerts", "--firing-only")
        if al.returncode != 0:
            print(f"profile-smoke-node: query alerts failed: {al.stderr}",
                  file=sys.stderr)
            return 1
        al_doc = json.loads(al.stdout)
        fired = {a["name"] for a in al_doc["alerts"]}
        if SMOKE_RULE["name"] not in fired:
            print(
                f"profile-smoke-node: stall rule not firing (fired: "
                f"{sorted(fired)})",
                file=sys.stderr,
            )
            return 1
        # the plain-HTTP scrape: parse-valid and carrying the alert gauge
        body = urllib.request.urlopen(
            f"http://{http_addr}/metrics", timeout=30
        ).read().decode()
        bad = validate_exposition(body)
        if bad:
            print(
                f"profile-smoke-node: malformed HTTP exposition: {bad[:3]!r}",
                file=sys.stderr,
            )
            return 1
        want = 'celestia_tpu_alert_firing{rule="%s"} 1' % SMOKE_RULE["name"]
        if want not in body:
            print(f"profile-smoke-node: {want!r} missing from the scrape",
                  file=sys.stderr)
            return 1
        print(
            json.dumps(
                {
                    "profile_smoke_node": "ok",
                    "grpc": addr,
                    "metrics_http": http_addr,
                    "snapshots": len(ts_doc["snapshots"]),
                    "alerts_fired": sorted(fired),
                    "scrape_bytes": len(body),
                }
            )
        )
        return 0
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def main(argv) -> int:
    legs = argv[1:] or ["--leg1", "--leg2"]
    if "--leg1" in legs:
        from celestia_tpu.da import device_plane

        # force the device plane (the leg the chip runs): the native
        # fused pipeline would otherwise satisfy the tiny square
        # host-side and no device dispatch would happen on a CPU backend
        with device_plane.forced("on"):
            rc = leg1()
        if rc != 0:
            return rc
    if "--leg2" in legs:
        rc = leg2()
        if rc != 0:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
