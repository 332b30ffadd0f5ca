#!/usr/bin/env python
"""Bench regression sentinel: judge a fresh bench.py run against the
committed trajectory (ISSUE 9).

The committed trajectory is BASELINE.json (reference published numbers,
when any) plus the per-round driver captures BENCH_r*.json — each holds
the bench run's exit code and the JSON result lines recoverable from its
stdout tail. A round with rc != 0 contributed nothing; a line with
`value: null` + `error` is an OUTAGE marker (nothing was measured, as in
r4/r5), recorded as such and never treated as a zero measurement.

For every fresh line the sentinel finds the matching historical series
(metric + device class + whatever discriminators — batch, seq_len,
remat, fused flags, tp, replicas — both sides declare; an absent or
null discriminator matches anything, so the outage re-emit's bare
headline still finds the batch-256 history), derives a per-metric noise
band from the relative spread of the series' CURRENT regime — points
within 30% of the LAST committed value, the same ref the delta is
judged against; a landed 5x improvement must not widen the band and
mask every later regression — floored at --min-band (default 10%), and
emits one machine-readable verdict line:

    improved      delta beyond the band in the metric's good direction
    within-noise  |delta| inside the band
    regressed     delta beyond the band in the bad direction
    outage        fresh value is null (error carried on the line)
    new           no committed history to judge against
    config-error  the fresh line is a crashed config (metric *_error)

plus a final `sentinel_summary` line. Exit code: 1 when anything
regressed or a config crashed, --fail-on-outage promotes outages to
exit 2, else 0. Secondary fields (`compile_s`, `exec_hbm_bytes` — the
compile watchdog's accounting) are judged warn-only with generous bands
when both sides carry them: a compile-time or footprint blowup is
reported, but only the measured value decides the exit code.

Deliberately dependency-free (stdlib json only): the sentinel must run
during exactly the kind of outage where importing jax can hang.

Usage:
    python tools/bench_sentinel.py FRESH [--min-band 0.10] [...]
        FRESH = bench stdout capture (JSON lines), a BENCH_ALL.json-style
        list, a BENCH_r*.json-style driver capture, or `-` for stdin.
    python tools/bench_sentinel.py --replay N
        Re-judge committed round N against rounds < N (the fixture mode:
        `--replay 5` reproduces the known r5 outage/trajectory verdicts).
"""
import argparse
import glob
import json
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: discriminators that split one metric into distinct tracked configs
#: (mirrors bench._merge_results' identity key; the sentinel stays
#: import-free so it also works while jax is wedged)
_DISCRIMINATORS = ("batch", "seq_len", "layout", "remat",
                   "fused_bn_epilogue", "fused_rnn", "hidden",
                   "num_features", "tp", "replicas", "quantized_dtype",
                   "prefix_cache")

#: units where smaller is better; anything rate-like (…/s) is
#: larger-is-better, unknown units default to larger-is-better
_SMALLER_IS_BETTER = ("ms", "s", "us", "seconds")

#: metric prefixes judged WARN-ONLY (ISSUE 11): the serving-chaos drill
#: numbers (availability %, failover added latency, respawn-to-first-
#: token) are resilience health signals riding a fault-injection
#: harness — their run-to-run wobble must be reported, but only real
#: performance measurements decide the exit code. The disaggregation
#: A/B (ISSUE 17) rides the same carve-out: its decode-ITL-under-storm
#: legs are a thread-scheduler-sensitive contention drill, and the
#: committed verdict is the in-leg baseline-vs-roles delta, not the
#: absolute numbers. The live-rollout drill (ISSUE 18) likewise: its
#: hard gate is zero requests lost (enforced by check_line, not the
#: sentinel); the durations are contention-sensitive wall clock.
#: Speculative decoding (ISSUE 19) too: its hard gates are the bench's
#: own accepted-per-pass > 1.0 assert and check_line's k+1 ceiling;
#: the wall-clock A/B inverts under CPU interpret, so absolutes are
#: warnings, never failures.
#: Quantized serving (ISSUE 20) likewise: its hard gates are the
#: bench's own token-match + logit-budget refusals and check_line's
#: budget/layout rules; CPU interpret stages int8 blocks through f32
#: copies, so quant wall-clock off-TPU is a warning, never a failure
_WARN_ONLY_PREFIXES = ("serving_chaos_", "smoke_serving_chaos_",
                       "serving_disagg_", "smoke_serving_disagg_",
                       "serving_rollout_", "smoke_serving_rollout_",
                       "serving_spec_", "smoke_serving_spec_",
                       "serving_quant_", "smoke_serving_quant_")


def _device_class(line):
    """'TPU v5 lite', 'tpu', 'v5e' … -> 'tpu'; everything else keeps its
    lowercase platform name, so cpu smoke lines never masquerade as chip
    history for the same metric."""
    dev = str(line.get("device") or "").lower()
    if "tpu" in dev or re.match(r"v\d", dev):
        return "tpu"
    return dev or "unknown"


def _discriminators(line):
    return {k: line[k] for k in _DISCRIMINATORS
            if line.get(k) is not None}


def _compatible(a, b):
    """Two lines describe the same tracked config if no discriminator
    PRESENT ON BOTH disagrees (an absent/null one matches anything)."""
    for k in _DISCRIMINATORS:
        va, vb = a.get(k), b.get(k)
        if va is not None and vb is not None and va != vb:
            return False
    return True


def _is_outage(line):
    return line.get("value") is None and bool(line.get("error"))


def parse_round_capture(blob):
    """Result lines out of one BENCH_r*.json driver capture: every
    json-parseable line in the stdout tail (the tail is truncated at the
    head, so the first line may be a torn fragment — skipped), plus the
    `parsed` final line when the tail lost it."""
    lines = []
    for raw in str(blob.get("tail") or "").splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            r = json.loads(raw)
        except ValueError:
            continue
        if isinstance(r, dict) and r.get("metric"):
            lines.append(r)
    parsed = blob.get("parsed")
    if isinstance(parsed, dict) and parsed.get("metric") and \
            not any(r == parsed for r in lines):
        lines.append(parsed)
    return lines


def load_trajectory(repo, max_round=None):
    """[(round_n, [lines])] from the committed BENCH_r*.json, oldest
    first. rc != 0 rounds stay in the list with no lines — a whole-round
    outage is part of the trajectory, not a gap in it."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        n = int(m.group(1))
        if max_round is not None and n >= max_round:
            continue
        try:
            with open(path) as f:
                blob = json.load(f)
        except (OSError, ValueError):
            continue
        lines = parse_round_capture(blob) if blob.get("rc") == 0 else []
        rounds.append((n, lines))
    rounds.sort()
    return rounds


def load_baseline(repo):
    """BASELINE.json's published reference numbers (metric -> value),
    attached to verdicts as context. Empty when nothing is published."""
    try:
        with open(os.path.join(repo, "BASELINE.json")) as f:
            pub = json.load(f).get("published") or {}
    except (OSError, ValueError):
        return {}
    out = {}
    for k, v in pub.items():
        if isinstance(v, dict):
            v = v.get("value")
        if isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def load_fresh(source):
    """Fresh result lines from `source`: '-' (stdin), a JSON-lines
    capture of bench stdout, a BENCH_ALL.json-style list/{'results': …},
    or a BENCH_r*.json-style driver capture."""
    text = sys.stdin.read() if source == "-" else open(source).read()
    try:
        blob = json.loads(text)
    except ValueError:
        blob = None
    if isinstance(blob, dict) and "tail" in blob:
        return parse_round_capture(blob)
    if isinstance(blob, dict) and isinstance(blob.get("results"), list):
        return [r for r in blob["results"] if isinstance(r, dict)]
    if isinstance(blob, list):
        return [r for r in blob if isinstance(r, dict)]
    lines = []
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            r = json.loads(raw)
        except ValueError:
            continue
        if isinstance(r, dict) and r.get("metric"):
            lines.append(r)
    return lines


def _series(trajectory, fresh_line):
    """The matching historical observations, oldest first:
    [(round, line)] with outage lines included (they carry information —
    'this metric was unmeasurable in round 4')."""
    metric = fresh_line.get("metric")
    dev = _device_class(fresh_line)
    out = []
    for n, lines in trajectory:
        for r in lines:
            if r.get("metric") != metric or _device_class(r) != dev:
                continue
            if _compatible(fresh_line, r):
                out.append((n, r))
    return out


#: a point this far (relative) from the series median is a different
#: REGIME (a landed optimization, a config rewrite), not noise
_REGIME = 0.30


def _band(values, min_band):
    """Per-metric noise band (relative): the spread of the points in the
    series' current regime — within _REGIME of the LAST committed value,
    the same ref the delta is judged against — floored. Anchoring at the
    ref (not the series median) matters twice over: after a committed 5x
    improvement the raw hi-lo spread would be ~400%, and a median anchor
    would keep selecting the ABANDONED regime (the median lags the
    improvement), letting its wobble set the band while the fresh delta
    is judged against the new level. Only round-to-round wobble of the
    level actually being defended may widen the band. With < 2 regime
    points the spread is unknowable — the floor rules."""
    if len(values) < 2:
        return min_band
    ref = values[-1]
    if ref <= 0:
        return min_band
    regime = [v for v in values if abs(v / ref - 1.0) <= _REGIME]
    if len(regime) < 2:
        return min_band
    return max((max(regime) - min(regime)) / ref, min_band)


def _direction(line):
    unit = str(line.get("unit") or "")
    if unit.endswith("/s"):
        return 1
    if unit in _SMALLER_IS_BETTER:
        return -1
    return 1


def _judge_secondary(verdict, fresh, ref):
    """Warn-only secondary-field comparison (compile wall time is noisy
    on shared hosts; footprint is not; the prefix-cache hit rate and
    the SLO goodput/attainment pair are health signals, not the
    measurement) — none of these decide the exit code, the measured
    value does. `bad` is the direction that warrants a warning: +1 =
    growth is bad (time, bytes), -1 = a drop is bad (hit rate,
    goodput, attainment)."""
    for field, band, bad in (("compile_s", 0.50, 1),
                             ("exec_hbm_bytes", 0.15, 1),
                             ("prefix_hit_rate", 0.15, -1),
                             ("prefix_hit_tokens", 0.25, -1),
                             ("failover_added_latency_p95_ms", 0.50, 1),
                             ("respawn_to_first_token_ms", 0.50, 1),
                             # ISSUE 13: SLO health signals — a goodput
                             # or attainment drop warns, the measured
                             # tok/s decides the exit code
                             ("goodput_tok_per_sec", 0.25, -1),
                             ("slo_ttft_attainment", 0.10, -1),
                             # ISSUE 14: training-observability health
                             # signals — a growing data-wait fraction,
                             # step-time tail, or collective footprint
                             # warns; the measured value decides
                             ("data_wait_fraction", 0.25, 1),
                             ("step_p95_ms", 0.50, 1),
                             ("comms_bytes_per_step", 0.15, 1),
                             # ISSUE 15: remediation health signals — a
                             # growing fault->recovery time or more
                             # re-executed work per restart warns; the
                             # measured publish latency decides
                             ("mttr_s", 0.50, 1),
                             ("steps_lost_per_remediation", 0.50, 1),
                             # ISSUE 16: warm-start health signals — a
                             # growing warm respawn TTFT or a slower
                             # breach->capacity span means the AOT
                             # cache stopped absorbing the XLA cost;
                             # warn-only like the rest of the chaos leg
                             ("respawn_to_first_token_warm_ms", 0.50, 1),
                             ("burn_to_scale_up_s", 0.50, 1)):
        fv, rv = fresh.get(field), ref.get(field)
        if not isinstance(fv, (int, float)) or not isinstance(
                rv, (int, float)) or rv <= 0:
            continue
        delta = (fv - rv) / rv
        verdict[field] = fv
        verdict[field + "_ref"] = rv
        verdict[field + "_delta_pct"] = round(delta * 100, 1)
        if bad * delta > band:
            verdict.setdefault("warnings", []).append(
                "%s %s %.0f%% vs the last committed round (warn band "
                "%.0f%%)" % (field,
                             "grew" if delta > 0 else "dropped",
                             abs(delta) * 100, band * 100))


def judge(fresh_lines, trajectory, baselines, min_band):
    """One verdict dict per fresh line (see module docstring for the
    verdict vocabulary)."""
    verdicts = []
    for line in fresh_lines:
        metric = str(line.get("metric") or "")
        v = {"metric": metric, "device": _device_class(line),
             "unit": line.get("unit"), "value": line.get("value")}
        v.update({k: line[k] for k in _DISCRIMINATORS
                  if line.get(k) is not None})
        if metric in baselines:
            v["baseline"] = baselines[metric]
        if metric.endswith("_error"):
            v["verdict"] = "config-error"
            v["error"] = line.get("error")
            verdicts.append(v)
            continue
        # judgeable history needs a POSITIVE numeric value: a committed
        # 0 can't anchor a relative delta (and a rate/time of 0 is a
        # degenerate measurement, not a level to defend)
        hist = _series(trajectory, line)
        healthy = [(n, r) for n, r in hist if not _is_outage(r)
                   and isinstance(r.get("value"), (int, float))
                   and r["value"] > 0]
        if _is_outage(line):
            v["verdict"] = "outage"
            v["error"] = line.get("error")
            if healthy:
                n, r = healthy[-1]
                v["last_committed"] = {"round": n, "value": r["value"]}
            verdicts.append(v)
            continue
        if not healthy or not isinstance(line.get("value"), (int, float)):
            v["verdict"] = "new"
            v["n_history"] = len(healthy)
            verdicts.append(v)
            continue
        values = [r["value"] for _, r in healthy]
        ref_round, ref = healthy[-1]
        band = _band(values, min_band)
        delta = (line["value"] - ref["value"]) / ref["value"]
        good = delta * _direction(line)
        v.update(ref=ref["value"], ref_round=ref_round,
                 n_history=len(values),
                 delta_pct=round(delta * 100, 1),
                 band_pct=round(band * 100, 1))
        if good > band:
            v["verdict"] = "improved"
        elif good < -band:
            v["verdict"] = "regressed"
        else:
            v["verdict"] = "within-noise"
        if metric.startswith(_WARN_ONLY_PREFIXES):
            v["warn_only"] = True
            if v["verdict"] == "regressed":
                v.setdefault("warnings", []).append(
                    "%s regressed but is a warn-only chaos-drill "
                    "metric; not failing the session" % metric)
        _judge_secondary(v, line, ref)
        verdicts.append(v)
    return verdicts


def summarize(verdicts, fail_on_outage):
    counts = {}
    for v in verdicts:
        counts[v["verdict"]] = counts.get(v["verdict"], 0) + 1
    # warn-only metrics (chaos-drill health signals) never decide the
    # exit code — their regressions ride along as warnings
    hard_regressed = [v for v in verdicts
                      if v["verdict"] in ("regressed", "config-error")
                      and not v.get("warn_only")]
    exit_code = 0
    if hard_regressed:
        exit_code = 1
    elif fail_on_outage and counts.get("outage"):
        exit_code = 2
    return {"sentinel_summary": {
        "counts": counts, "judged": len(verdicts), "exit_code": exit_code,
        "regressed": [v["metric"] for v in hard_regressed],
    }}, exit_code


def run(fresh_lines, repo=_REPO, min_band=0.10, fail_on_outage=False,
        max_round=None, out=None):
    """Judge + print the verdict block. Returns the exit code (the
    importable seam the tests go through)."""
    out = out or sys.stdout
    trajectory = load_trajectory(repo, max_round=max_round)
    verdicts = judge(fresh_lines, trajectory, load_baseline(repo),
                     min_band)
    summary, exit_code = summarize(verdicts, fail_on_outage)
    for v in verdicts:
        out.write(json.dumps(v) + "\n")
    out.write(json.dumps(summary) + "\n")
    return exit_code


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="judge a fresh bench run against the committed "
                    "BENCH_r*.json trajectory")
    ap.add_argument("fresh", nargs="?",
                    help="fresh bench output (JSON lines, BENCH_ALL.json "
                         "list, or BENCH_r*.json capture; '-' = stdin)")
    ap.add_argument("--replay", type=int, metavar="N",
                    help="judge committed round N against rounds < N "
                         "(fixture mode; ignores FRESH)")
    ap.add_argument("--repo", default=_REPO,
                    help="repo root holding the committed trajectory")
    ap.add_argument("--min-band", type=float, default=0.10,
                    help="noise-band floor as a fraction (default 0.10)")
    ap.add_argument("--fail-on-outage", action="store_true",
                    help="exit 2 when the fresh run has outage lines "
                         "(default: report only)")
    args = ap.parse_args(argv)

    if args.replay is not None:
        path = os.path.join(args.repo, "BENCH_r%02d.json" % args.replay)
        with open(path) as f:
            blob = json.load(f)
        if blob.get("rc") != 0:
            print(json.dumps({"sentinel_summary": {
                "counts": {"outage": 1}, "judged": 0,
                "exit_code": 2 if args.fail_on_outage else 0,
                "note": "round %d was a whole-run outage (rc=%s)"
                        % (args.replay, blob.get("rc")),
                "regressed": []}}))
            return 2 if args.fail_on_outage else 0
        fresh = parse_round_capture(blob)
        max_round = args.replay
    elif args.fresh:
        fresh = load_fresh(args.fresh)
        max_round = None
    else:
        ap.error("need FRESH or --replay N")
    if not fresh:
        print(json.dumps({"sentinel_summary": {
            "counts": {}, "judged": 0, "exit_code": 1,
            "note": "no parseable result lines in the fresh input",
            "regressed": []}}))
        return 1
    return run(fresh, repo=args.repo, min_band=args.min_band,
               fail_on_outage=args.fail_on_outage, max_round=max_round)


if __name__ == "__main__":
    sys.exit(main())
