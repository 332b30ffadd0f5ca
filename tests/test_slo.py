"""Fleet-wide request tracing + SLO/goodput accounting (ISSUE 13).

Load-bearing claims:
* one request = ONE connected trace — W3C `traceparent` in/out at the
  HTTP door, the trace id rides Request through admission, prefill
  chunks, decode steps, AND failover hops (the stitched row is pinned
  with a mid-generation replica drain, `serving.failover_hop`
  annotated, Perfetto renders a single named row);
* malformed/foreign traceparent headers degrade to a fresh trace id —
  fuzzed values can never 500 the frontend;
* the request lifecycle ledger streams schema-pinned JSONL, sampled
  deterministically per trace id;
* the SLO engine derives attainment/burn/budget from the existing
  histograms, and the goodput token ledger satisfies
  submitted == goodput + slow + shed + expired + failed at every
  instant, /statusz agreeing with the Prometheus registry;
* the bounded span ring counts overwrites of unexported spans
  (`spans_dropped_total`) instead of dropping silently;
* tools/fleet_top.py renders single-server and degraded-fleet frames.
"""
import json
import re
import threading
import time
import urllib.request

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.telemetry import slo as tslo
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.serving.scheduler import Request, make_resume
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)


@pytest.fixture(autouse=True)
def _clean_rings():
    telemetry.tracing.clear()
    telemetry.flight().clear()
    yield
    telemetry.tracing.clear()
    telemetry.flight().clear()


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _serve(tiny_lm, **kw):
    params, cfg = tiny_lm
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    return serving.serve((params, cfg), **kw)


# ---------------------------------------------------------------------------
# W3C traceparent: parse/format + the never-500 fuzz regression
# ---------------------------------------------------------------------------


def test_traceparent_parse_and_format():
    tid = "0af7651916cd43dd8448eb211c80319c"
    assert telemetry.parse_traceparent(
        "00-%s-b7ad6b7169203331-01" % tid) == tid
    # uppercase + whitespace normalize
    assert telemetry.parse_traceparent(
        "  00-%s-B7AD6B7169203331-01  " % tid.upper()) == tid
    hdr = telemetry.format_traceparent(tid)
    assert telemetry.parse_traceparent(hdr) == tid
    # a non-hex in-process id folds into a deterministic well-formed one
    h1 = telemetry.format_traceparent("req-17")
    h2 = telemetry.format_traceparent("req-17")
    t1, t2 = (telemetry.parse_traceparent(h) for h in (h1, h2))
    assert t1 == t2 and re.match(r"^[0-9a-f]{32}$", t1)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00", "00-short-b7ad6b7169203331-01",
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",          # all-zero trace
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",
    "00-0af7651916cd43dd8448eb211c80319X-b7ad6b7169203331-01",
    "zz-!!-##-@@", "00-0af7-01", 12345, b"\x00\xff",
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-extra",
])
def test_traceparent_malformed_degrades_to_none(bad):
    assert telemetry.parse_traceparent(bad) is None


def test_http_fuzzed_traceparent_never_500(tiny_lm):
    """Satellite (ISSUE 13): garbage traceparent headers must degrade
    to a fresh trace id — 200 with a well-formed response traceparent,
    never a 500."""
    srv = _serve(tiny_lm)
    try:
        host, port = srv.serve_http(port=0, block=False)
        url = "http://%s:%d/v1/generate" % (host, port)
        fuzz = ["garbage", "00", "ff-" + "a" * 32 + "-" + "b" * 16
                + "-01", "00-" + "0" * 32 + "-" + "0" * 16 + "-01",
                "\x01\x02\x03", "a" * 4096,
                "00-zzzz-yyyy-01", "-", "::", " "]
        seen = set()
        for i, tp in enumerate(fuzz):
            body = json.dumps({"tokens": [1 + i, 2, 3],
                               "max_new_tokens": 2}).encode()
            rq = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json",
                         "traceparent": tp})
            with urllib.request.urlopen(rq, timeout=120) as r:
                assert r.status == 200
                out = json.loads(r.read())
                hdr = r.headers.get("traceparent")
            assert out["tokens"], out
            # fresh, well-formed trace despite the garbage inbound
            parsed = telemetry.parse_traceparent(hdr)
            assert parsed is not None and parsed == out["trace"]
            seen.add(out["trace"])
        assert len(seen) == len(fuzz), "fresh ids must not collide"
        # and a WELL-FORMED inbound traceparent is honored verbatim
        tid = "0af7651916cd43dd8448eb211c80319c"
        rq = urllib.request.Request(
            url, data=json.dumps({"tokens": [5, 6],
                                  "max_new_tokens": 2}).encode(),
            headers={"traceparent":
                     "00-%s-b7ad6b7169203331-01" % tid})
        with urllib.request.urlopen(rq, timeout=120) as r:
            out = json.loads(r.read())
        assert out["trace"] == tid
        assert [s for s in telemetry.spans(trace=tid)
                if s["name"] == "serving.token"]
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the stitched failover trace: one request, one row, across replicas
# ---------------------------------------------------------------------------


def park_after_decodes(rep, n_calls):
    real = rep.engine.decode_pass
    parked, hold = threading.Event(), threading.Event()
    state = {"n": 0}

    def parking(*args, **kw):
        out = real(*args, **kw)
        state["n"] += 1
        if state["n"] == n_calls:
            parked.set()
            hold.wait()
        return out

    rep.engine.decode_pass = parking
    return parked, hold


def test_failover_trace_stitched_single_row(tiny_lm, tmp_path):
    """Satellite (ISSUE 13): kill a replica mid-decode; every span of
    the request — victim prefill/decodes AND the rescue replica's
    replay — shares ONE trace id with a `serving.failover_hop`
    annotation, and the Perfetto export renders it as one named row."""
    srv = _serve(tiny_lm, replicas=2)
    hold = None
    try:
        victim = srv.replicas[0]
        parked, hold = park_after_decodes(victim, 2)
        req = victim.submit([3, 5, 7, 9, 11, 13], max_new_tokens=6)
        tid = req.trace
        assert parked.wait(timeout=60)
        victim._last_beat -= 999.0
        srv.health()                     # sweep: drain + failover
        got = req.result(timeout=120)
        assert got, "failover produced no tokens"
        hold.set()
        spans = telemetry.spans(trace=tid)
        names = [s["name"] for s in spans]
        # the victim's life AND the replay's life on one trace
        assert "serving.submit" in names
        assert "serving.prefill" in names
        assert names.count("serving.prefill") >= 2, (
            "the replay's prefill must join the original trace: %r"
            % names)
        assert names.count("serving.token") >= 3
        hops = [s for s in spans if s["name"] == "serving.failover_hop"]
        assert len(hops) == 1
        attrs = hops[0]["attrs"]
        assert attrs["request"] == req.id
        assert attrs["carried_tokens"] >= 1
        assert attrs["hop"] == 1
        assert attrs["target"] == 1      # rescued by replica 1
        # Perfetto: ONE named row for the whole stitched life
        doc = telemetry.export_perfetto(str(tmp_path / "stitch.json"))
        evs = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["args"].get("trace") == tid]
        assert len({e["tid"] for e in evs}) == 1
        row_tid = evs[0]["tid"]
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"
                and e["tid"] == row_tid]
        assert meta and meta[0]["args"]["name"] == "trace %s" % tid
        assert "serving.failover_hop" in {e["name"] for e in evs}
        # the CLIENT's TTFT was observed exactly once, on the victim —
        # the replay must not record a second, fresh-clock TTFT (that
        # would make SLO numbers optimistic exactly under failover)
        assert srv.replicas[1].metrics._h_ttft.count == 0
        assert victim.metrics._h_ttft.count == 1
    finally:
        if hold is not None:
            hold.set()
        srv.close()


def test_make_resume_carries_trace(tiny_lm):
    orig = Request([1, 2, 3], max_new_tokens=8)
    resume, carried = make_resume(orig, [1, 2, 3, 9, 10], max_len=64)
    assert carried == 2
    assert resume.trace == orig.trace
    assert resume.resumed_tokens == 2
    assert resume.failovers == 1


# ---------------------------------------------------------------------------
# request lifecycle ledger: schema, ordering, deterministic sampling
# ---------------------------------------------------------------------------


def test_request_log_schema_and_ordering(tiny_lm, tmp_path,
                                         monkeypatch):
    path = str(tmp_path / "requests.jsonl")
    monkeypatch.setenv("MXNET_REQUEST_LOG", path)
    monkeypatch.delenv("MXNET_REQUEST_LOG_SAMPLE", raising=False)
    srv = _serve(tiny_lm)
    try:
        reqs = [srv.submit([1 + i, 2, 3], max_new_tokens=3,
                           tenant="acme" if i % 2 else None)
                for i in range(3)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        srv.close()
    with open(path) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    assert recs, "nothing logged"
    for rec in recs:
        for key in tslo.REQUEST_LOG_REQUIRED:
            assert key in rec, (key, rec)
        assert rec["event"] in tslo.REQUEST_LOG_EVENTS, rec
    for req in reqs:
        mine = [r for r in recs if r["trace"] == req.trace]
        events = [r["event"] for r in mine]
        for needed in ("queued", "admitted", "first_token", "decode",
                       "finish"):
            assert needed in events, (req.id, events)
        # lifecycle ordering by timestamp
        t_of = {r["event"]: r["ts"] for r in mine}
        assert t_of["queued"] <= t_of["first_token"] <= t_of["finish"]
        fin = [r for r in mine if r["event"] == "finish"][0]
        assert fin["outcome"] == "completed"
        assert fin["generated"] == 3
        decodes = [r for r in mine if r["event"] == "decode"]
        assert all(r["itl_ms"] >= 0 for r in decodes)
    assert any(r["tenant"] == "acme" for r in recs)


def test_request_log_sampling_deterministic(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_REQUEST_LOG",
                       str(tmp_path / "s.jsonl"))
    log = tslo.RequestLog()
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "0")
    assert not log.sampled("abc123")
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "1")
    assert log.sampled("abc123")
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "0.5")
    # deterministic: the same trace id always gets the same verdict
    traces = ["t-%d" % i for i in range(200)]
    first = [log.sampled(t) for t in traces]
    assert first == [log.sampled(t) for t in traces]
    kept = sum(first)
    assert 60 <= kept <= 140, "crc sampling wildly unbalanced"
    # a sample=0 run writes nothing even with the path set
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "0")

    class R:
        id, trace, tenant = 1, "t-0", "default"

    assert log.event("queued", R()) is None


# ---------------------------------------------------------------------------
# SLO engine: env parsing, burn math, histogram interpolation
# ---------------------------------------------------------------------------


def test_parse_slo_env(monkeypatch):
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "250:0.99,acme=100")
    monkeypatch.setenv("MXNET_SLO_ITL_MS", "50")
    monkeypatch.setenv("MXNET_SLO_AVAILABILITY", "0.999,acme=0.9999")
    objs = telemetry.parse_slo_env()
    by = {(o.kind, o.tenant): o for o in objs}
    assert len(objs) == 5
    assert by[("ttft", None)].threshold_s == 0.25
    assert by[("ttft", None)].target == 0.99
    assert by[("ttft", "acme")].threshold_s == 0.1
    assert by[("ttft", "acme")].target == 0.95          # kind default
    assert by[("itl", None)].target == 0.99
    assert by[("availability", "acme")].target == 0.9999
    assert by[("ttft", "acme")].key == "ttft_tenant_acme"
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "not-a-number")
    with pytest.raises(ValueError, match="MXNET_SLO_TTFT_MS"):
        telemetry.parse_slo_env()
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "250:1.5")
    with pytest.raises(ValueError):
        telemetry.parse_slo_env()


def test_parse_windows(monkeypatch):
    monkeypatch.delenv("MXNET_SLO_WINDOWS", raising=False)
    assert telemetry.parse_windows() == tslo.DEFAULT_WINDOWS
    monkeypatch.setenv("MXNET_SLO_WINDOWS", "30,600")
    assert telemetry.parse_windows() == (30, 600)
    monkeypatch.setenv("MXNET_SLO_WINDOWS", "0,-5")
    with pytest.raises(ValueError, match="MXNET_SLO_WINDOWS"):
        telemetry.parse_windows()


def test_histogram_count_below_interpolates():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 0.2, 0.4))
    for v in [0.05] * 10 + [0.15] * 10 + [0.3] * 10:
        h.observe(v)
    assert h.count_below(0.1) == 10
    assert h.count_below(0.2) == 20
    # mid-bucket: 10 + half of the (0.2, 0.4] bucket
    assert abs(h.count_below(0.3) - 25.0) < 1e-9
    assert h.count_below(0.4) == 30
    assert h.count_below(99.0) == 30     # +Inf observations excluded
    h.observe(100.0)
    assert h.count_below(99.0) == 30


def test_burn_rate_multi_window():
    """Burn = windowed bad fraction / error budget, computed from
    snapshot deltas — pinned against hand-computed numbers."""
    reg = telemetry.MetricsRegistry()
    counts = {"good": 0.0, "total": 0.0}
    obj = telemetry.Objective("ttft", threshold_s=0.25, target=0.9)
    tracker = telemetry.SLOTracker(
        reg, lambda o: (counts["good"], counts["total"]),
        objectives=[obj], windows=(60, 600))
    t0 = 1000.0
    tracker.update(now=t0)               # baseline: 0/0
    counts.update(good=90.0, total=100.0)
    tracker.update(now=t0 + 30)          # 10 bad / 100 in 30s
    # 60s window: bad_frac 0.1 over budget 0.1 -> burn 1.0
    burn60 = reg.gauge(tslo._BURN % ("ttft", 60)).value
    assert abs(burn60 - 1.0) < 1e-6
    counts.update(good=180.0, total=200.0)
    tracker.update(now=t0 + 60)
    # fresh window sample at t0+30 as base: 90 good / 100 total
    burn60 = reg.gauge(tslo._BURN % ("ttft", 60)).value
    assert abs(burn60 - 1.0) < 1e-6
    # attainment + budget remaining from lifetime counts
    assert abs(reg.gauge(tslo._ATTAIN % "ttft").value - 0.9) < 1e-9
    # lifetime bad 20 of total 200 * budget 0.1 = 20 -> remaining 0.0
    assert abs(reg.gauge(tslo._BUDGET % "ttft").value - 0.0) < 1e-9
    # a clean stretch drives windowed burn back to 0 while lifetime
    # budget stays spent
    counts.update(good=300.0, total=320.0)
    tracker.update(now=t0 + 90)
    counts.update(good=400.0, total=420.0)
    tracker.update(now=t0 + 120)
    pay = tracker.payload(now=t0 + 121)
    w60 = pay[0]["burn"]["60s"]
    assert w60["rate"] == 0.0 and w60["total"] >= 100


def test_merge_slo_sums_not_averages():
    a = [{"objective": "ttft", "tenant": None, "threshold_ms": 250.0,
          "target": 0.9, "good": 90, "total": 100,
          "burn": {"60s": {"good": 90, "total": 100, "span_s": 60}}}]
    b = [{"objective": "ttft", "tenant": None, "threshold_ms": 250.0,
          "target": 0.9, "good": 0, "total": 0,
          "burn": {"60s": {"good": 0, "total": 0, "span_s": 0}}}]
    merged = telemetry.merge_slo([a, b])
    assert len(merged) == 1
    m = merged[0]
    assert m["attainment"] == 0.9
    # an idle replica does not dilute the burning one
    assert abs(m["burn"]["60s"]["rate"] - 1.0) < 1e-6


# ---------------------------------------------------------------------------
# the goodput token ledger + /statusz consistency
# ---------------------------------------------------------------------------


def _token_identity(tok):
    assert tok["submitted"] == (tok["goodput"] + tok["slow"]
                                + tok["shed"] + tok["expired"]
                                + tok["failed"]), tok


def test_statusz_identity_and_registry_consistency(tiny_lm,
                                                   monkeypatch):
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "250:0.95")
    monkeypatch.setenv("MXNET_SLO_AVAILABILITY", "0.999")
    srv = _serve(tiny_lm)
    try:
        for i in range(4):
            srv.generate([1 + i, 2, 3], max_new_tokens=3, timeout=120)
        srv.submit([9, 8, 7], max_new_tokens=4,
                   tenant="acme").result(timeout=120)
        host, port = srv.serve_http(port=0, block=False)
        with urllib.request.urlopen(
                "http://%s:%d/statusz" % (host, port)) as r:
            stz = json.loads(r.read())
        # the four-term ISSUE 13 identity (+ slow for SLO violations)
        _token_identity(stz["tokens"])
        assert stz["tokens"]["goodput"] + stz["tokens"]["slow"] \
            == 4 * 3 + 4
        for name, t in stz["tenants"].items():
            _token_identity(t["tokens"])
        assert stz["tenants"]["acme"]["tokens"]["submitted"] == 4
        assert stz["tenants"]["acme"]["requests"]["completed"] == 1
        # /statusz agrees with the Prometheus exposition byte-for-byte
        text = srv.prometheus_text()
        for kind, n in stz["tokens"].items():
            if kind in ("replayed", "generated"):
                continue
            m = re.search(
                r"serving_%s_tokens_total\{[^}]*\} (\d+)" % kind, text)
            assert m and int(m.group(1)) == n, (kind, n)
        m = re.search(
            r"serving_tenant_acme_submitted_tokens_total\{[^}]*\} (\d+)",
            text)
        assert m and int(m.group(1)) == 4
        # the SLO block rides /statusz and the exposition
        kinds = {(o["objective"], o["tenant"]) for o in stz["slo"]}
        assert ("ttft", None) in kinds and ("availability", None) in kinds
        assert "slo_ttft_attainment{" in text
        assert "slo_availability_burn_rate_300s{" in text
        assert "slo_ttft_budget_remaining{" in text
    finally:
        srv.close()


def test_ledger_classifies_shed_expired_failed(tiny_lm):
    """Unit-level terminal classification: every error class lands on
    its own token bucket and the identity holds throughout."""
    from mxnet_tpu.serving.metrics import ServingMetrics
    from mxnet_tpu.serving.scheduler import (BrownoutShed,
                                             DeadlineExceeded)
    met = ServingMetrics()

    def finish(err=None, tokens=None, max_new=5, tenant=None):
        req = Request([1, 2, 3], max_new_tokens=max_new, tenant=tenant)
        if err is not None:
            req._finish(error=err)
        else:
            req._finish(tokens=tokens or [1, 2, 3, 4, 5])
        met.request_finished(req)
        return req

    finish()                                           # goodput 2
    finish(err=BrownoutShed("x"))                      # shed 5
    finish(err=DeadlineExceeded("x"))                  # expired 5
    finish(err=mx.MXNetError("engine died"))           # failed 5
    tok = met.tokens_ledger()
    assert tok["goodput"] == 2 and tok["shed"] == 5
    assert tok["expired"] == 5 and tok["failed"] == 5
    _token_identity(tok)
    # failover salvage: replayed counts extra work, the resume's
    # delivery credits the carried tokens to goodput
    orig = Request([1, 2], max_new_tokens=6)
    resume, carried = make_resume(orig, [1, 2, 9, 9, 9], max_len=64)
    met.request_failover(orig, carried)
    resume._finish(tokens=[1, 2, 9, 9, 9, 8, 8, 8])
    met.request_finished(resume)
    tok = met.tokens_ledger()
    assert tok["replayed"] == 3
    assert tok["goodput"] == 2 + (3 + 3)   # carried + fresh decode
    _token_identity(tok)


def test_resume_goodput_judged_by_client_ttft(monkeypatch):
    """A resume whose ORIGINAL first token violated the TTFT objective
    must classify its delivery as slow even when the replay itself was
    fast — the client experienced the original latency."""
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "100")
    from mxnet_tpu.serving.metrics import ServingMetrics
    met = ServingMetrics()
    orig = Request([1, 2], max_new_tokens=6)
    orig.t_first_token = orig.t_submit + 0.4      # 400ms > 100ms
    orig.t_client_first_token = orig.t_first_token
    orig.t_last_token = orig.t_first_token
    resume, carried = make_resume(orig, [1, 2, 9], max_len=64)
    assert resume.t_client_submit == orig.t_client_submit
    assert resume.t_client_first_token == orig.t_client_first_token
    resume._finish(tokens=[1, 2, 9, 8, 8])
    met.request_finished(resume)
    tok = met.tokens_ledger()
    assert tok["slow"] == 3 and tok["goodput"] == 0, tok


def test_tenant_sanitize_collision_and_cap():
    """Raw names that sanitize identically share ONE ledger entry (no
    fleet-aggregate double count), and tenant cardinality is capped —
    client-supplied names can't grow the registry without bound."""
    from mxnet_tpu.serving.metrics import ServingMetrics
    met = ServingMetrics()
    assert met._tenant("a-b") is met._tenant("a.b")
    assert len(met._tenants_view()) == 1
    for i in range(2 * met._TENANT_CAP):
        met._tenant("t%d" % i)
    view = met._tenants_view()
    assert len(view) <= met._TENANT_CAP + 1
    assert "overflow" in view
    assert met._tenant("yet-another") is view["overflow"]


def test_router_statusz_aggregates_fleet(tiny_lm):
    srv = _serve(tiny_lm, replicas=2)
    try:
        for i in range(4):
            srv.generate([2 + i, 3, 4], max_new_tokens=2, timeout=120)
        stz = srv.statusz()
        assert len(stz["replicas"]) == 2
        fleet = stz["fleet"]
        _token_identity(fleet["tokens"])
        per = [b["tokens"]["submitted"] for b in stz["replicas"]]
        assert fleet["tokens"]["submitted"] == sum(per) == 8
        assert fleet["replicas_total"] == 2
        _token_identity(fleet["tenants"]["default"]["tokens"])
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# span ring: drops are counted, occupancy is a gauge
# ---------------------------------------------------------------------------


def test_span_ring_drop_accounting(monkeypatch):
    from collections import deque
    monkeypatch.setattr(tracing, "_spans", deque(maxlen=4))
    monkeypatch.setattr(tracing, "_exported_upto", 0)
    reg = telemetry.default_registry()
    ctr = reg.counter("spans_dropped_total")
    base = ctr.value
    for i in range(4):
        telemetry.record_span("fill%d" % i, 0, 1)
    assert ctr.value == base                 # ring not yet overwriting
    assert reg.gauge("span_ring_occupancy").value == 1.0
    telemetry.record_span("overflow", 0, 1)
    assert ctr.value == base + 1             # unexported span evicted
    # an export blesses the current contents: overwriting THEM is fine,
    # overwriting anything recorded after the export is a drop again
    telemetry.export_perfetto()
    for i in range(4):
        telemetry.record_span("post%d" % i, 0, 1)
    assert ctr.value == base + 1
    telemetry.record_span("post-overflow", 0, 1)
    assert ctr.value == base + 2


# ---------------------------------------------------------------------------
# fleet_top: the stdlib console renders both server shapes
# ---------------------------------------------------------------------------


def _fleet_top():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "fleet_top", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "fleet_top.py"))
    ft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ft)
    return ft


def test_fleet_top_renders_live_server(tiny_lm):
    ft = _fleet_top()
    srv = _serve(tiny_lm)
    try:
        host, port = srv.serve_http(port=0, block=False)
        srv.generate([1, 2, 3], max_new_tokens=2, timeout=120)
        frame = ft.render_once("http://%s:%d" % (host, port))
    finally:
        srv.close()
    assert "server: OK" in frame
    assert "tokens: submitted" in frame
    assert "goodput" in frame


def test_fleet_top_renders_degraded_fleet_from_canned_bodies():
    """The exact shape the chaos drill's fleet emits — one healthy, one
    drained, one circuit-open — must render without errors."""
    ft = _fleet_top()
    health = {"ok": True, "degraded": True, "replicas_total": 3,
              "replicas_healthy": 1, "replicas_circuit_open": 1,
              "replicas": [
                  {"replica": 0, "ok": True, "drained": False,
                   "circuit_open": False, "last_beat_age_s": 0.1,
                   "respawns": 0},
                  {"replica": 1, "ok": False, "drained": True,
                   "circuit_open": False, "dead": False,
                   "last_beat_age_s": 9.0, "respawns": 1},
                  {"replica": 2, "ok": False, "drained": True,
                   "circuit_open": True, "dead": True,
                   "last_beat_age_s": 99.0, "respawns": 3}]}
    statusz = {"replicas": [
        {"replica": i, "tokens": {}, "tenants": {},
         "goodput_tok_per_sec": 10.0 * i, "slo": []}
        for i in range(3)],
        "fleet": {"tokens": {"submitted": 70, "goodput": 50, "slow": 5,
                             "shed": 5, "expired": 5, "failed": 5,
                             "replayed": 3},
                  "tenants": {"acme": {"tokens": {"goodput": 50}}},
                  "slo": [{"objective": "ttft", "tenant": None,
                           "threshold_ms": 250.0, "target": 0.95,
                           "attainment": 0.97,
                           "budget_remaining": 0.4,
                           "burn": {"60s": {"rate": 0.5},
                                    "3600s": {"rate": 0.1}}}]}}
    snap = {"replicas": [
        {"scheduler": {"queued": i, "prefilling": 0},
         "cache": {"blocks_in_use": 2, "blocks_total": 31},
         "requests": {"failovers": 1, "engine_failures": 0},
         "throughput": {"tokens_per_sec": 100.0}} for i in range(3)]}
    frame = ft.render(health, statusz, snap, url="http://x:1")
    assert "CIRCUIT" in frame and "drained" in frame
    assert "acme" in frame
    assert "burn" in frame
    assert "tokens: submitted 70" in frame
    # every section degrades alone: a dead door still renders
    assert "UNREACHABLE" in ft.render(None, None, None)


# ---------------------------------------------------------------------------
# kill switch: no SLO/ledger mutation when telemetry is off
# ---------------------------------------------------------------------------


def test_slo_and_ledger_respect_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    monkeypatch.setenv("MXNET_REQUEST_LOG",
                       str(tmp_path / "dead.jsonl"))
    req = Request([1, 2, 3], max_new_tokens=2)
    telemetry.request_event("queued", req)
    assert not (tmp_path / "dead.jsonl").exists()
    from mxnet_tpu.serving.metrics import ServingMetrics
    met = ServingMetrics()
    req._finish(tokens=[1, 2, 3, 4])
    met.request_finished(req)
    assert met.tokens_ledger()["submitted"] == 0
