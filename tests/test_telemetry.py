"""Unified telemetry (ISSUE 7): metrics registry, span tracing, flight
recorder — and the cross-cutting invariants they pin:

  * Prometheus text exposition format (pinned here — the serving
    /metrics endpoint serves it under Accept: text/plain);
  * one serving request = one connected trace (shared request id across
    queue/prefill/decode spans, visible in the Perfetto export);
  * profiler.dump() append-safety + chrome-trace schema (monotonic ts);
  * every pl.pallas_call under mxnet_tpu/ops/ declares a cost_estimate
    (the PR 2/4/5 bytes-report invariant, now a static check);
  * flight-recorder ring bounds, dump format, and the postmortem
    renderer.
"""
import ast
import json
import os
import re
import pathlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu import profiler
from mxnet_tpu.telemetry import metrics as tmetrics


@pytest.fixture(autouse=True)
def _clean_rings():
    telemetry.tracing.clear()
    telemetry.flight().clear()
    yield
    telemetry.tracing.clear()
    telemetry.flight().clear()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_roundtrip():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    # idempotent creation returns the same instance
    assert reg.counter("reqs_total") is c
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")


def test_histogram_quantiles_without_samples():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.001, 0.01, 0.1, 1.0))
    assert h.quantile(0.5) is None and h.mean is None
    for v in [0.005] * 50 + [0.05] * 45 + [0.5] * 5:
        h.observe(v)
    assert h.count == 100
    assert abs(h.sum - (50 * 0.005 + 45 * 0.05 + 5 * 0.5)) < 1e-9
    # p50 interpolates inside the (0.001, 0.01] bucket, p99 in (0.1, 1]
    assert 0.001 < h.quantile(0.50) <= 0.01
    assert 0.01 < h.quantile(0.95) <= 0.1
    assert 0.1 < h.quantile(0.99) <= 1.0
    snap = reg.snapshot()["metrics"]["lat"]
    assert snap["count"] == 100 and snap["p50"] == h.quantile(0.5)
    assert snap["buckets"]["+Inf"] == 0


def test_prometheus_exposition_format_pinned():
    """The text format contract: HELP/TYPE pairs, label set on every
    sample, cumulative le buckets + _sum/_count, trailing newline, and
    every sample line matches the Prometheus line grammar."""
    reg = telemetry.MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc(3)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1))
    h.observe(0.05)
    text = reg.prometheus_text()
    assert text.endswith("\n")
    assert "# HELP reqs_total requests" in text
    assert "# TYPE reqs_total counter" in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat_seconds histogram" in text
    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*\{[^}]*\} "
                        r"(NaN|[+-]?(Inf|[0-9.e+-]+))$")
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert lines, text
    for ln in lines:
        assert sample.match(ln), ln
    # labels: host/replica on every sample
    for ln in lines:
        assert 'host="' in ln and 'replica="' in ln, ln
    # cumulative buckets end at +Inf == _count
    bucket_lines = [ln for ln in lines if "_bucket" in ln]
    assert any('le="+Inf"' in ln for ln in bucket_lines)
    inf_val = [int(ln.rsplit(" ", 1)[1]) for ln in bucket_lines
               if 'le="+Inf"' in ln][0]
    count_val = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                 if ln.startswith("lat_seconds_count")][0]
    assert inf_val == count_val == 1


def test_telemetry_kill_switch(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    reg = telemetry.MetricsRegistry()
    c = reg.counter("x_total")
    c.inc(10)
    assert c.value == 0
    h = reg.histogram("h")
    h.observe(1.0)
    assert h.count == 0
    with telemetry.span("dead"):
        pass
    assert telemetry.spans() == []
    telemetry.flight().record("event", "dead")
    assert telemetry.flight().events() == []


def test_host_label_env(monkeypatch):
    monkeypatch.setenv("MXNET_HOST_ID", "3")
    reg = telemetry.MetricsRegistry()
    assert reg.labels()["host"] == "3"
    reg.counter("a_total").inc()
    assert 'host="3"' in reg.prometheus_text()


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_span_nesting_inherits_trace():
    with telemetry.span("outer", trace="t-1"):
        assert telemetry.current_trace() == "t-1"
        with telemetry.span("inner"):
            pass
    assert telemetry.current_trace() is None
    got = telemetry.spans(trace="t-1")
    assert [s["name"] for s in got] == ["inner", "outer"]
    assert all(s["trace"] == "t-1" for s in got)


def test_span_records_to_profiler_when_running():
    profiler._state["events"] = []
    profiler._state["flushed"] = []
    profiler.set_state("run")
    try:
        with telemetry.span("traced.region", category="serving"):
            pass
    finally:
        profiler.set_state("stop")
    names = [e["name"] for e in profiler._state["events"]]
    assert "traced.region" in names


def test_perfetto_export_one_row_per_trace(tmp_path):
    with telemetry.span("a", trace=7):
        pass
    with telemetry.span("b", trace=7):
        pass
    with telemetry.span("c", trace=9):
        pass
    path = str(tmp_path / "trace.json")
    doc = telemetry.export_perfetto(path)
    with open(path) as f:
        assert json.load(f) == doc
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tids = {e["args"]["trace"]: e["tid"] for e in evs}
    by7 = [e for e in evs if e["args"]["trace"] == 7]
    assert len(by7) == 2 and len({e["tid"] for e in by7}) == 1
    assert tids[7] != tids[9]
    # row names come from thread_name metadata events
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {"trace 7", "trace 9"} <= {m["args"]["name"] for m in meta}
    # ts sorted
    ts = [e["ts"] for e in doc["traceEvents"] if "ts" in e]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_dump(tmp_path, monkeypatch):
    fr = telemetry.FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("event", "e%d" % i, i=i)
    evs = fr.events()
    assert len(evs) == 8
    assert evs[0]["name"] == "e12" and evs[-1]["name"] == "e19"
    # no dir configured -> no file, no error
    monkeypatch.delenv("MXNET_FLIGHT_RECORDER_DIR", raising=False)
    assert fr.dump("test") is None
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    path = fr.dump("unit test!")
    assert path and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit test!"
    assert len(doc["events"]) == 8
    assert "metrics" in doc and "pid" in doc
    # a second dump gets a distinct file
    path2 = fr.dump("again")
    assert path2 != path and os.path.exists(path)


def test_flagged_counter_lands_in_flight_ring():
    telemetry.flight().clear()
    reg = telemetry.MetricsRegistry()
    c = reg.counter("bad_steps_total", flight=True)
    c.inc(step=12)
    evs = [e for e in telemetry.flight().events()
           if e["kind"] == "metric" and e["name"] == "bad_steps_total"]
    assert evs and evs[0]["step"] == 12 and evs[0]["value"] == 1


def test_preemption_watcher_dumps_flight(tmp_path, monkeypatch):
    from mxnet_tpu.parallel.resilient import PreemptionWatcher
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    with telemetry.span("train.device_step", category="train", step=5):
        pass
    w = PreemptionWatcher(grace_secs=60)
    w.trigger()          # simulated SIGTERM, no OS signal needed
    w.cancel_deadline()
    files = list(tmp_path.glob("flight-*.sigterm.json"))
    assert len(files) == 1
    doc = json.loads(files[0].read_text())
    span_names = [e["name"] for e in doc["events"]
                  if e["kind"] == "span"]
    assert "train.device_step" in span_names     # last spans pre-fault
    faults = [e for e in doc["events"] if e["kind"] == "fault"]
    assert any(e["name"] == "train.preemption_signal" for e in faults)


def test_postmortem_renders_timeline(tmp_path, monkeypatch):
    import importlib.util
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    fr = telemetry.FlightRecorder(capacity=16)
    fr.record("span", "train.device_step", trace=None, dur_us=1200,
              step=3)
    fr.record("fault", "chaos.sigterm_at", step=3)
    fr.dump("sigterm")
    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "postmortem.py"))
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    text = pm.render(pm.load_dumps([str(tmp_path)]))
    assert "train.device_step" in text
    assert "chaos.sigterm_at" in text
    assert "sigterm" in text            # the dump reason appears
    assert "FAULT" in text              # faults are called out


# ---------------------------------------------------------------------------
# serving: one request = one connected trace; Prometheus /metrics
# ---------------------------------------------------------------------------


def _tiny_server(**kw):
    import jax
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_len=32)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return serving.serve((params, cfg), max_batch=2, block_size=8, **kw)


def test_one_request_single_connected_trace(tmp_path):
    srv = _tiny_server()
    try:
        req = srv.submit([1, 2, 3], max_new_tokens=4)
        req.result(timeout=60)
        # since ISSUE 13 the trace key is the request's W3C-compatible
        # trace id (rides failover hops), not the process-local req.id
        rid = req.trace
    finally:
        srv.close()
    names = [s["name"] for s in telemetry.spans(trace=rid)]
    assert "serving.submit" in names
    assert "serving.queue" in names
    assert "serving.prefill" in names
    assert names.count("serving.token") == 4      # one per served token
    assert "serving.decode" not in names    # the batch-level step's alone
    # the Perfetto export renders them as ONE row (a single tid)
    doc = telemetry.export_perfetto(str(tmp_path / "serving.json"))
    evs = [e for e in doc["traceEvents"]
           if e["ph"] == "X" and e["args"].get("trace") == rid]
    assert len({e["tid"] for e in evs}) == 1
    assert {"serving.submit", "serving.queue", "serving.prefill",
            "serving.token"} <= {e["name"] for e in evs}


def test_http_metrics_content_negotiation():
    import urllib.request
    srv = _tiny_server()
    try:
        host, port = srv.serve_http(port=0, block=False)
        srv.generate([1, 2], max_new_tokens=2, timeout=60)
        base = "http://%s:%d/metrics" % (host, port)
        # default: the JSON snapshot (unchanged contract)
        with urllib.request.urlopen(base) as r:
            snap = json.loads(r.read())
        assert snap["requests"]["completed"] == 1
        # Accept: text/plain -> Prometheus text exposition
        rq = urllib.request.Request(base,
                                    headers={"Accept": "text/plain"})
        with urllib.request.urlopen(rq) as r:
            ctype = r.headers["Content-Type"]
            text = r.read().decode()
        assert ctype.startswith("text/plain")
        assert "# TYPE serving_requests_completed_total counter" in text
        assert re.search(r"serving_requests_completed_total\{[^}]*\} 1",
                         text)
        # PR 4 paged-serving observables are gauges in the exposition
        for gauge in ("serving_queue_depth", "serving_blocks_in_use",
                      "serving_blocks_high_water",
                      "serving_prefill_queue_depth"):
            assert "# TYPE %s gauge" % gauge in text, gauge
        assert "serving_decode_step_seconds_bucket" in text
    finally:
        srv.close()


def test_serving_metrics_snapshot_shape_unchanged():
    """The migration contract: snapshot() keeps its dict shape."""
    srv = _tiny_server()
    try:
        srv.generate([1, 2, 3], max_new_tokens=3, timeout=60)
        snap = srv.snapshot()
    finally:
        srv.close()
    assert snap["requests"]["completed"] == 1
    assert snap["requests"]["failed"] == 0
    assert snap["throughput"]["tokens_generated"] >= 2
    assert snap["latency_ms"]["total_mean"] > 0
    assert snap["latency_ms"]["queue_mean"] >= 0
    assert snap["batch"]["mean_occupancy"] <= 1.0
    assert snap["cache"]["blocks_in_use"] == 0
    assert snap["scheduler"]["queued"] == 0
    # new since the migration: percentiles ride along
    assert snap["latency_ms"]["decode_step_p50"] is not None


# ---------------------------------------------------------------------------
# profiler dump: append-safe, schema
# ---------------------------------------------------------------------------


def test_profiler_dump_append_safe(tmp_path):
    profiler._state["events"] = []
    profiler._state["flushed"] = []
    profiler._state["dumped_to"] = set()
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    a = mx.nd.ones((4, 4))
    mx.nd.relu(a).wait_to_read()
    profiler.set_state("stop")
    n1 = len(json.load(open(profiler.dump()))["traceEvents"])
    assert n1 > 0
    # re-dump with no new events: the file must NOT grow (the old bug:
    # every dump re-emitted the full buffer)
    n2 = len(json.load(open(profiler.dump()))["traceEvents"])
    assert n2 == n1
    # new events append to the same file...
    profiler.set_state("run")
    mx.nd.dot(a, a).wait_to_read()
    profiler.set_state("stop")
    n3 = len(json.load(open(profiler.dump()))["traceEvents"])
    assert n3 > n1
    # ...and a dump to a FRESH file carries only not-yet-flushed events
    profiler.set_config(filename=str(tmp_path / "p2.json"))
    fresh = json.load(open(profiler.dump()))["traceEvents"]
    assert fresh == []
    # the aggregate table still sees everything (flushed included)
    table = profiler.dumps()
    assert "relu" in table and "dot" in table


def test_profiler_dump_schema_monotonic_ts(tmp_path):
    profiler._state["events"] = []
    profiler._state["flushed"] = []
    profiler._state["dumped_to"] = set()
    profiler.set_config(filename=str(tmp_path / "s.json"))
    profiler.set_state("run")
    a = mx.nd.ones((8, 8))
    for _ in range(3):
        a = mx.nd.relu(a)
    a.wait_to_read()
    with telemetry.span("schema.region"):
        pass
    profiler.set_state("stop")
    with open(profiler.dump()) as f:
        doc = json.load(f)           # parses
    evs = doc["traceEvents"]
    assert evs
    for e in evs:
        assert isinstance(e["name"], str)
        assert e["ph"] == "X"
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert isinstance(e["dur"], int) and e["dur"] >= 0
        assert "pid" in e and "tid" in e
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "events must carry monotonic ts"


# ---------------------------------------------------------------------------
# static invariant: every pallas_call under ops/ declares a cost estimate
# ---------------------------------------------------------------------------


def _mentions_cost(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            f = sub.func
            name = f.attr if isinstance(f, ast.Attribute) \
                else getattr(f, "id", "")
            if "cost" in name.lower():
                return True
        if isinstance(sub, ast.Name) and "cost" in sub.id.lower():
            return True
    return False


def test_every_pallas_call_declares_cost_estimate():
    """PR 2/4/5 invariant, now pinned statically: on TPU a Pallas kernel
    is an opaque custom call, so without a declared CostEstimate the XLA
    cost model (benchmarks/*_report.py's A/B instrument) counts it as
    zero bytes/flops — silently corrupting every bytes report."""
    import mxnet_tpu.ops
    ops_dir = pathlib.Path(mxnet_tpu.ops.__file__).parent
    found, missing = 0, []
    for py in sorted(ops_dir.glob("*.py")):
        tree = ast.parse(py.read_text(), filename=str(py))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) \
                else getattr(f, "id", "")
            if name != "pallas_call":
                continue
            found += 1
            ok = any(kw.arg == "cost_estimate" for kw in node.keywords)
            ok = ok or any(kw.arg is None and _mentions_cost(kw.value)
                           for kw in node.keywords)
            if not ok:
                missing.append("%s:%d" % (py.name, node.lineno))
    assert found >= 7, "pallas_call scan broke (found %d)" % found
    assert not missing, ("pallas_call without a declared cost_estimate "
                         "(bytes reports would count it as zero): %s"
                         % ", ".join(missing))


# ---------------------------------------------------------------------------
# static invariant: docs/OBSERVABILITY.md can't drift from the registry
# ---------------------------------------------------------------------------


def _doc_instrument_names():
    """Backticked instrument-looking tokens in docs/OBSERVABILITY.md,
    outside fenced code blocks: lowercase snake_case, with
    `<placeholder>` tokens (`<site>`, `<tenant>`, `<objective>`,
    `<window>`, `<kind>`, ...) mapped onto the %s metric-name templates
    (telemetry/introspect.py, serving/metrics.py, telemetry/slo.py),
    one optional `{a,b,...}` alternation expanded, `*` kept as a
    wildcard."""
    repo = pathlib.Path(mx.__file__).resolve().parent.parent
    doc = (repo / "docs" / "OBSERVABILITY.md").read_text()
    doc = re.sub(r"```.*?```", "", doc, flags=re.S)
    names = set()
    for span in re.findall(r"`([^`]+)`", doc):
        t = re.sub(r"<[a-z_]+>", "%s", span)
        if "_" not in t or not re.match(
                r"^[a-z][a-z0-9_%*]*(?:\{[a-z0-9_,]*\}[a-z0-9_]*)?$", t):
            continue
        m = re.match(r"^([a-z0-9_%*]*)\{([a-z0-9_,]*)\}([a-z0-9_]*)$", t)
        if m:
            names.update(m.group(1) + alt + m.group(3)
                         for alt in m.group(2).split(","))
        else:
            names.add(t)
    return names


def _code_name_population():
    """Everything a doc-referenced instrument may resolve to: string
    literals and attribute names under mxnet_tpu/ + tools/ + bench.py,
    plus each literal's dot->underscore form (what `CompileSite.sane`
    renders a site name to, so `serving_decode` finds "serving.decode")."""
    repo = pathlib.Path(mx.__file__).resolve().parent.parent
    files = (list((repo / "mxnet_tpu").rglob("*.py"))
             + list((repo / "tools").glob("*.py"))
             + [repo / "bench.py"])
    population = set()
    for py in files:
        try:
            tree = ast.parse(py.read_text(), filename=str(py))
        except (OSError, SyntaxError):                 # pragma: no cover
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value,
                                                             str):
                population.add(node.value)
                if "." in node.value:
                    population.add(node.value.replace(".", "_"))
            elif isinstance(node, ast.Attribute):
                population.add(node.attr)
    return population


def test_observability_doc_names_only_existing_instruments():
    """Every instrument name docs/OBSERVABILITY.md references must exist
    in code (as a metric-name literal, a %s template, or — for sites and
    accessors — an attribute), so the page cannot drift from the
    registry. The count floor pins the extraction itself: if a doc
    rewrite silently stops matching, this fails before the doc rots."""
    doc_names = _doc_instrument_names()
    assert len(doc_names) >= 45, ("doc scan broke (found %d names)"
                                  % len(doc_names))
    population = _code_name_population()
    missing = []
    for name in sorted(doc_names):
        if "*" in name:
            pat = re.compile("^" + re.escape(name)
                             .replace(r"\*", "[a-z0-9_]*") + "$")
            if not any(pat.match(p) for p in population):
                missing.append(name + " (wildcard: nothing matches)")
        elif name not in population:
            missing.append(name)
    assert not missing, ("docs/OBSERVABILITY.md names instruments that "
                         "don't exist in code: %s" % ", ".join(missing))
