"""The serving front doors: in-process `serve()` and a stdlib HTTP shim.

`serve(model, ...)` accepts any of:
  * a `(params, TransformerConfig)` pair — paged-KV continuous batching
  * an adapter instance (TransformerLM / BlockLM / ExportedLM)
  * a path to a `.mxtpu` artifact from `predict.export_model`
  * an initialized Gluon Block (give `vocab` and `max_len`)

and returns a started `LMServer`: a background thread runs the
continuous-batching loop (admit → prefill → launch the next decode step →
collect the last one → evict: one step stays in flight, so the device
runs while the host does its part of a pass), callers submit token
prompts and block on per-request futures. The HTTP frontend
(`LMServer.serve_http` / tools/serve.py) is a thin stdlib
ThreadingHTTPServer over the same object — one handler thread per
connection, all of them funneling into the single serving thread, so the
compiled-step single-writer invariant holds no matter how many clients
connect.
"""
from __future__ import annotations

import json
import os
import threading
import time

from ..base import MXNetError
from .. import telemetry
from ..utils import chaos
from ..models import afmoe, falcon_h1, latent_moe, nemotron_h
from .engine import (Engine, TransformerLM, BlockLM, ExportedLM,
                     PoolsLost)
from .latent_lm import LatentMoELM
from .afmoe_lm import AfmoeLM
from .falcon_h1_lm import FalconH1LM
from .nemotron_h_lm import NemotronHLM
from .scheduler import (Scheduler, Request, QueueFull, BrownoutShed,
                        DeadlineExceeded, DeadlineUnmeetable, make_resume)
from .metrics import ServingMetrics


def _queue_span(req):
    """Record the request's submit -> admission wait as a span on its
    trace row (req.t_submit/t_admit are perf_counter seconds; span
    timestamps are the same clock in microseconds)."""
    telemetry.record_span("serving.queue", int(req.t_submit * 1e6),
                          int((req.t_admit - req.t_submit) * 1e6),
                          trace=req.trace, category="serving",
                          to_profiler=False, request=req.id)


def _resolve_model(model, vocab=None, max_len=None, time_major=False):
    if isinstance(model, (TransformerLM, LatentMoELM, FalconH1LM, BlockLM,
                          ExportedLM)):
        return model
    if isinstance(model, str):
        return ExportedLM(model)
    if isinstance(model, tuple) and len(model) == 2:
        params, cfg = model
        if isinstance(cfg, latent_moe.LatentMoEConfig):
            return LatentMoELM(params, cfg)
        if isinstance(cfg, afmoe.AfmoeConfig):
            return AfmoeLM(params, cfg)
        if isinstance(cfg, falcon_h1.FalconH1Config):
            return FalconH1LM(params, cfg)
        if isinstance(cfg, nemotron_h.NemotronHConfig):
            return NemotronHLM(params, cfg)
        return TransformerLM(params, cfg)
    if hasattr(model, "collect_params"):          # Gluon Block
        if vocab is None or max_len is None:
            raise MXNetError("serving a Gluon Block needs vocab= and "
                             "max_len=")
        return BlockLM(model, vocab, max_len, time_major=time_major)
    raise MXNetError("don't know how to serve %r — pass (params, cfg), an "
                     "adapter, a Gluon Block, or a .mxtpu path"
                     % type(model))


class _HTTPFrontend:
    """The stdlib HTTP front door, shared by the single-engine
    `LMServer` and the multi-replica `ReplicatedLMServer` (router.py).
    A front provides submit/snapshot/prometheus_text/health/close plus
    two backpressure knobs: `saturated_status` (the HTTP code a full
    queue maps to — 429 on one server, 503 behind the router) and
    `retry_after_s` (emitted as a Retry-After header when set)."""

    saturated_status = 429
    retry_after_s = None
    submit_retries = 3
    submit_backoff = 0.05
    _httpd = None

    def _final_reject(self):
        """Count one request bounced by backpressure after retries."""

    def serve_http(self, host="127.0.0.1", port=8080, block=True):
        """Start the stdlib HTTP frontend. Endpoints:
        POST /v1/generate  {"tokens": [...], "max_new_tokens": N,
                            "eos_id": id?}  -> {"tokens": [...], ...}
        GET  /v1/metrics   -> the metrics snapshot
        GET  /healthz      -> {"ok": true}
        Returns the bound (host, port); with block=False the HTTP server
        runs on a daemon thread (tests bind port 0)."""
        from http.server import ThreadingHTTPServer
        self._httpd = ThreadingHTTPServer((host, port),
                                          _make_handler(self))
        addr = self._httpd.server_address
        if block:
            try:
                self._httpd.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                self.close()
        else:
            threading.Thread(target=self._httpd.serve_forever,
                             daemon=True).start()
        return addr


def _make_handler(outer):
    """BaseHTTPRequestHandler class bound to one `_HTTPFrontend`. All
    handler threads funnel into the front's submit path; the serving
    thread(s) stay the single writers of their engines."""
    from http.server import BaseHTTPRequestHandler
    from .router import NoHealthyReplicas

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):   # keep stdout clean
            pass

        def _reply(self, code, payload, headers=None):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                h = outer.health()
                self._reply(200 if h["ok"] else 503, h)
            elif self.path == "/statusz":
                # ISSUE 13: the SLO / goodput view — per-tenant token
                # ledgers, attainment, error budget, multi-window burn
                self._reply(200, outer.statusz())
            elif self.path in ("/v1/metrics", "/metrics"):
                accept = self.headers.get("Accept", "")
                if "text/plain" in accept:
                    # Prometheus scrape: text exposition 0.0.4
                    body = outer.prometheus_text().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._reply(200, outer.snapshot())
            else:
                self._reply(404, {"error": "unknown path %s" % self.path})

        def do_POST(self):
            if self.path in ("/v1/rollout", "/rollout"):
                # live-rollout operator overrides (ISSUE 18,
                # tools/rollout.py): promote / rollback / reject /
                # status against the attached RolloutController; 404
                # on a door with no rollout support (single LMServer)
                dispatch = getattr(outer, "rollout_command", None)
                if dispatch is None:
                    self._reply(404, {"error": "no rollout support on "
                                               "this server"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                    out = dispatch(body.get("cmd"),
                                   step=body.get("step"),
                                   reason=body.get("reason"))
                    self._reply(200, out)
                except (KeyError, ValueError, TypeError,
                        MXNetError) as e:
                    self._reply(400, {"error": "bad request: %s" % e})
                return
            if self.path not in ("/v1/generate", "/generate"):
                self._reply(404, {"error": "unknown path %s" % self.path})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                from ..utils import retry
                # W3C trace context (ISSUE 13): a well-formed inbound
                # `traceparent` joins the caller's trace; ANYTHING
                # malformed or foreign degrades to a fresh trace id —
                # a client's garbage header must never 500 the door
                trace = telemetry.parse_traceparent(
                    self.headers.get("traceparent"))
                # a briefly-full queue drains in a few decode steps:
                # absorb the burst with bounded backoff before bouncing.
                # count_reject=False: only the FINAL failure below
                # counts as a rejection in the metrics
                priority = body.get("priority")
                deadline_ms = body.get("deadline_ms")
                req = retry(
                    lambda: outer.submit(
                        body["tokens"],
                        max_new_tokens=int(
                            body.get("max_new_tokens", 32)),
                        eos_id=body.get("eos_id"),
                        count_reject=False,
                        tenant=body.get("tenant"),
                        priority=(int(priority) if priority is not None
                                  else None),
                        deadline_ms=(float(deadline_ms)
                                     if deadline_ms is not None
                                     else None),
                        trace=trace),
                    attempts=outer.submit_retries,
                    backoff=outer.submit_backoff,
                    retry_on=QueueFull)
            except DeadlineUnmeetable as e:
                # admission-time shed: the observed service rate cannot
                # meet this request's deadline — 503 with the COMPUTED
                # Retry-After (when the backlog will have drained enough
                # for the same deadline to be feasible)
                self._reply(503, {"error": str(e)},
                            headers={"Retry-After":
                                     "%d" % max(1, int(e.retry_after_s))})
                return
            except QueueFull as e:
                outer._final_reject()
                headers = None
                if outer.retry_after_s is not None:
                    headers = {"Retry-After":
                               "%d" % max(1, int(outer.retry_after_s))}
                self._reply(outer.saturated_status, {"error": str(e)},
                            headers=headers)
                return
            except NoHealthyReplicas as e:
                # fleet outage, NOT a client error: 503 so load
                # balancers fail over / clients retry (a 400 would
                # read as permanent and mask the outage)
                self._reply(503, {"error": str(e)})
                return
            except (KeyError, ValueError, TypeError, MXNetError) as e:
                # submit-side failures are the CLIENT's fault
                # (malformed body, empty/oversized prompt)
                self._reply(400, {"error": "bad request: %s" % e})
                return
            try:
                generated = req.result(
                    timeout=float(body.get("timeout", 300)))
            except DeadlineExceeded as e:
                # the deadline passed in queue: dropped before prefill —
                # a Gateway Timeout, not a server error
                self._reply(504, {"error": str(e)})
                return
            except BrownoutShed as e:
                self._reply(503, {"error": str(e)},
                            headers={"Retry-After": "1"})
                return
            except MXNetError as e:
                self._reply(500, {"error": str(e)})
                return
            self._reply(200, {
                "tokens": generated,
                "prompt_len": len(req.prompt),
                "latency_ms": 1e3 * (req.t_done - req.t_submit),
                "trace": req.trace,
            }, headers={"traceparent":
                        telemetry.format_traceparent(req.trace)})

    return Handler


class LMServer(_HTTPFrontend):
    """Continuous-batching server over one Engine. Start with
    `serve(...)`; stop with `close()` (or use as a context manager).
    `replica_id=` labels this server's metrics registry (the router
    gives each replica its index); `tp=`/`devices=` pass through to the
    Engine's tensor-parallel placement (serving/tp.py)."""

    #: resume hops one request may spend before its fault is surfaced
    #: (a crash-looping fleet must not bounce a request forever)
    max_failovers = 2

    def __init__(self, model, max_batch=8, max_len=None, block_size=16,
                 num_blocks=None, max_queue=64, queue_timeout=None,
                 keep_logits=False, vocab=None, time_major=False,
                 idle_wait=0.005, paged=None, prefill_chunk=None,
                 token_budget=None, tp=None, devices=None,
                 replica_id=None, prefix_cache=None, tenant_budget=None,
                 tenant_budgets=None, default_priority=0,
                 default_deadline_ms=None, brownout=None,
                 aot_cache=None, role=None, draft=None, spec=None,
                 spec_k=None, kv_quant=None, weight_quant=None):
        adapter = _resolve_model(model, vocab=vocab, max_len=max_len,
                                 time_major=time_major)
        self.engine = Engine(adapter, max_batch=max_batch, max_len=max_len,
                             block_size=block_size, num_blocks=num_blocks,
                             keep_logits=keep_logits, paged=paged,
                             prefill_chunk=prefill_chunk, tp=tp,
                             devices=devices, prefix_cache=prefix_cache,
                             aot_cache=aot_cache, draft=draft, spec=spec,
                             spec_k=spec_k, kv_quant=kv_quant,
                             weight_quant=weight_quant)
        self.scheduler = Scheduler(max_batch=max_batch, max_queue=max_queue,
                                   queue_timeout=queue_timeout,
                                   token_budget=token_budget,
                                   tenant_budget=tenant_budget,
                                   tenant_budgets=tenant_budgets,
                                   brownout=brownout)
        self.default_priority = int(default_priority)
        if default_deadline_ms is None:
            env = os.environ.get("MXNET_SERVING_DEADLINE_MS")
            default_deadline_ms = float(env) if env else None
        self.default_deadline_ms = default_deadline_ms
        self.metrics = ServingMetrics(replica=replica_id)
        self.replica_id = replica_id
        # disaggregated serving (ISSUE 17): `role` is an advisory label
        # ("prefill"/"decode"/None) the router stamps for placement and
        # observability — it never changes this server's compute or
        # logits. `on_prefill_done` is the router's migration hook,
        # installed on prefill-role replicas: called on the serving
        # thread when a prompt finishes prefilling, it moves steady-
        # state decode to a decode replica via the replay transport.
        self.role = str(role) if role is not None else None
        self.on_prefill_done = None
        self._idle_wait = idle_wait
        self._work = threading.Event()
        self._closed = False
        # survival-layer state (ISSUE 11): `on_death` is the router's
        # rescue hook — called on the DYING serving thread with the
        # queued requests and in-flight resume states so they can be
        # re-homed instead of failed; `_died` distinguishes a crashed
        # loop (respawnable) from an administrative close
        self.on_death = None
        self._died = False
        self._chaos_stolen = None     # (block ids, release-at iteration)
        # serializes in-flight capture between the death path (dying
        # serving thread) and the router's wedge rescue (sweep thread):
        # whoever detaches a sequence first owns its failover — the
        # other side sees request=None and captures nothing
        self._failover_lock = threading.Lock()
        # liveness observables for /healthz: the loop thread beats every
        # iteration; decode progress stamps separately
        self._last_beat = time.perf_counter()
        self._last_step_t = None
        # the decode step launched and not yet collected (`_iterate`):
        # the serving thread's alone
        self._flight = None
        self._wedge_dumped = False
        # HTTP submit-on-QueueFull retry budget (utils.retry): a briefly
        # full queue absorbs a burst instead of bouncing clients to 429
        self.submit_retries = 3
        self.submit_backoff = 0.05
        self._thread = threading.Thread(target=self._loop,
                                        name="mxtpu-serving", daemon=True)
        self._httpd = None
        self._thread.start()

    # -- client API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens=32, eos_id=None,
               count_reject=True, tenant=None, priority=None,
               deadline_ms=None, trace=None):
        """Enqueue one request; returns it (a future: .result(timeout)).
        Raises QueueFull immediately when backpressure kicks in.
        `count_reject=False` suppresses the rejected-metric increment —
        for retry wrappers that only count the FINAL failure (a request
        that eventually lands is not a rejection). `tenant`/`priority`
        feed the scheduler's multi-tenant admission (default tenant,
        server default priority when omitted — fully backward
        compatible). `deadline_ms` (default `default_deadline_ms` /
        MXNET_SERVING_DEADLINE_MS) is the client's total latency budget:
        a request the OBSERVED service rate already can't meet is shed
        right here (DeadlineUnmeetable, with the computed Retry-After)
        instead of burning queue slots and prefill tokens on a
        guaranteed 504. `trace` (ISSUE 13) is the caller's trace id —
        the HTTP frontend passes a parsed W3C `traceparent` through it;
        unset mints a fresh id. Every span of the request's life keys
        on it, across replicas and failover hops."""
        if self._closed:
            # a replica behind the router reports closure as
            # backpressure so the door tries the next replica (a crash
            # racing a routed submit must not surface as a hard error
            # while healthy replicas exist); a standalone server keeps
            # the hard contract
            if self.replica_id is not None:
                raise QueueFull("replica %s is closed"
                                % self.replica_id)
            raise MXNetError("server is closed")
        if len(prompt) > self.engine.max_len:
            raise MXNetError(
                "prompt length %d exceeds the server's max_len %d"
                % (len(prompt), self.engine.max_len))
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        req = Request(prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                      tenant=tenant,
                      priority=(priority if priority is not None
                                else self.default_priority),
                      deadline_ms=deadline_ms, trace=trace)
        if deadline_ms is not None:
            # the gate runs AFTER the Request exists so an admission
            # shed has an id/trace/tenant to account and log against
            # (the request is discarded on the raise — it was never
            # submitted, so its terminal accounting happens here)
            self._check_deadline_meetable(req)
        try:
            self.scheduler.submit(req)
        except QueueFull:
            if count_reject:
                self.metrics.request_rejected()
            raise
        if self._closed:
            # the loop died between the check above and the enqueue: if
            # the death-path drain already took the request it will be
            # re-homed (proceed and count it submitted here, matching
            # the drained-replica ledger convention); if it is still on
            # the dead queue, pull it back and report backpressure so
            # the caller retries elsewhere — never strand it
            with self.scheduler._lock:
                try:
                    self.scheduler._queue.remove(req)
                    pulled = True
                except ValueError:
                    pulled = False
            if pulled:
                if self.replica_id is not None:
                    raise QueueFull("replica %s closed mid-submit"
                                    % self.replica_id)
                raise MXNetError("server is closed")
        self.metrics.request_submitted(req)
        # the trace row's start marker: every later span (queue, prefill
        # chunks, decode steps) shares this request's trace id
        telemetry.record_span("serving.submit", int(req.t_submit * 1e6),
                              0, trace=req.trace, category="serving",
                              to_profiler=False, request=req.id,
                              prompt_len=len(req.prompt),
                              max_new_tokens=req.max_new_tokens)
        self._work.set()
        return req

    def _load_split(self):
        """Committed backlog split into (prefill tokens, decode tokens)
        — the two drain at very different rates, and the deadline gate
        must price each at its own observed rate. Advisory reads, same
        caveats as `load_tokens`."""
        sched = self.scheduler
        with sched._lock:
            queued = list(sched._queue)
        pre = sum(len(r.prompt) for r in queued)
        dec = sum(r.max_new_tokens for r in queued)
        for s in list(sched.running):
            dec += max(1, s.max_total - len(s.tokens))
        for s in list(sched.prefilling):
            pre += max(0, s.prompt_len - s.prefilled)
            dec += max(1, s.max_total - s.prompt_len)
        return pre, dec

    def _check_deadline_meetable(self, req):
        """Admission-time deadline gate: estimated completion time is
        the committed DECODE backlog over the observed decode token
        rate PLUS the prefill backlog over the observed prefill rate
        (prefill drains orders of magnitude faster — pricing prompt
        tokens at the decode rate would falsely shed servable
        long-prompt requests). When the estimate already exceeds the
        deadline, shed NOW with a Retry-After computed from how long
        the backlog needs to drain below feasibility — honest
        backpressure beats a queue full of corpses. Still an estimate:
        it only has to be right about hopeless cases, and a false
        accept is dropped at scheduling time."""
        deadline_ms = req.deadline_ms
        rate = self.metrics.observed_token_rate()
        if rate is None or rate <= 0:
            return                      # nothing measured yet: admit
        pre_b, dec_b = self._load_split()
        pre_b += len(req.prompt)
        dec_b += req.max_new_tokens
        prate = self.metrics.observed_prefill_rate()
        est_s = dec_b / rate + (pre_b / prate if prate else 0.0)
        if est_s <= deadline_ms / 1e3:
            return
        self.metrics.request_deadline_shed(req)
        retry_after = max(1.0, est_s - deadline_ms / 1e3)
        raise DeadlineUnmeetable(
            "deadline %.0f ms unmeetable: %d decode + %d prefill "
            "backlog tokens at the observed %.0f tok/s decode rate "
            "need ~%.0f ms; retry in %.0fs"
            % (deadline_ms, dec_b, pre_b, rate, est_s * 1e3,
               retry_after),
            retry_after_s=retry_after)

    def generate(self, prompt, max_new_tokens=32, eos_id=None,
                 timeout=None):
        """Synchronous helper: submit and wait; returns generated tokens
        (prompt excluded)."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_id=eos_id).result(timeout)

    def snapshot(self):
        return self.metrics.snapshot(self.engine, self.scheduler)

    def prometheus_text(self):
        """Prometheus exposition of the server's metrics registry (the
        `/metrics` body under `Accept: text/plain`)."""
        return self.metrics.prometheus_text(self.engine, self.scheduler)

    def statusz(self):
        """The /statusz JSON body (ISSUE 13): the goodput token ledger,
        per-tenant breakdown, and SLO attainment/burn for this server."""
        body = self.metrics.statusz(self.engine, self.scheduler)
        if self.role is not None:
            # stamped only on disaggregated fleets — role-less bodies
            # stay byte-for-byte as before
            body["role"] = self.role
        return body

    def health(self, max_beat_age=5.0):
        """Loop-liveness summary for /healthz: `ok` requires the serving
        thread alive AND beating recently (a wedged loop is as dead as a
        crashed one). `last_step_age_s` is decode-progress age — None
        until the first decode step, and allowed to grow while idle.

        Wedge detection doubles as a flight-recorder trigger: the FIRST
        health check that observes a wedged-but-not-closed loop dumps
        the black box (the post-mortem of what the loop was doing when
        it stopped beating)."""
        now = time.perf_counter()
        alive = self._thread.is_alive() and not self._closed
        beat_age = now - self._last_beat
        ok = bool(alive and beat_age < max_beat_age)
        if not ok and not self._closed and not self._wedge_dumped:
            self._wedge_dumped = True
            telemetry.flight().record(
                "fault", "serving.healthz_wedge",
                loop_alive=bool(alive), beat_age_s=round(beat_age, 3))
            telemetry.flight().dump("healthz_wedge")
        return {
            "ok": ok,
            "loop_alive": bool(alive),
            "last_beat_age_s": round(beat_age, 3),
            "last_step_age_s": (round(now - self._last_step_t, 3)
                                if self._last_step_t is not None else None),
            "engine_failures": self.metrics.engine_failures,
        }

    def close(self, drain=True, timeout=30.0):
        """Stop the loop; with drain=True finish in-flight work first.
        A clean close (drained, loop exited on its own terms) runs the
        engine's block-pool leak audit — `Engine.close()` raises listing
        leaked block ids, so a serving-side leak fails loudly at the
        point of retirement instead of starving a future pool."""
        if drain:
            deadline = time.perf_counter() + timeout
            while self.scheduler.has_work() and \
                    time.perf_counter() < deadline:
                time.sleep(0.01)
        clean = (drain and not self._died
                 and not self.scheduler.has_work())
        self._closed = True
        self._work.set()
        self._thread.join(timeout=timeout)
        # strand-proofing: work can slip past both the drain wait and
        # `_closed` — a submit that passed the closed check enqueues
        # after the loop exited, and a request MID-ADMISSION (popped
        # from the queue by `admit()`, still inside its prefill, not
        # yet visible in `running`) hides from `has_work()` and from a
        # router `_rehome` scan, then lands in `running` just as the
        # loop sees `_closed` and exits (a scale_down retiring the
        # replica races routed traffic exactly this way). Sweep the
        # corpse: rescue through the router's death hook, or fail
        # promptly — never let a request ride silently to its timeout.
        leftovers = self.drain_queue()
        states = []
        with self._failover_lock:
            for s in (self.scheduler.running
                      + self.scheduler.prefilling):
                req = s.request
                if req is None or req._event.is_set():
                    continue
                states.append((req, list(s.tokens), s.prompt_len))
                s.request = None
                s.done = True
        if leftovers or states:
            # the stranded seqs' blocks go back to the pool ahead of
            # the engine's leak audit; reusable=False — an exited loop
            # cannot certify its KV
            for seq in (self.scheduler.running
                        + self.scheduler.prefilling):
                try:
                    self.engine.release(seq, reusable=False)
                except Exception:
                    pass
            self.scheduler.running = []
            self.scheduler.prefilling = []
            rescued = False
            if self.on_death is not None:
                try:
                    self.on_death(self, leftovers, states)
                    rescued = True
                except Exception:
                    pass
            if not rescued:
                err = MXNetError("server closed with the request "
                                 "still in flight")
                for req, _tokens, _plen in states:
                    req._finish(error=err)
                    self.metrics.request_finished(req)
                for req in leftovers:
                    req._finish(error=err)
                    self.metrics.request_finished(req)
        self._release_chaos_blocks()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd = None
        self.engine.close(audit=clean and self._thread.is_alive() is False)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- the serving loop ----------------------------------------------------

    def _loop(self):
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — a dead loop must not
            # strand clients in result(): rescue (via the router's
            # on_death hook) or fail everything in flight
            telemetry.flight().record("fault", "serving.loop_died",
                                      error="%s: %s"
                                      % (type(e).__name__, e))
            telemetry.flight().dump("serving_loop_died")
            self._died = True
            self._closed = True
            self._drop_flight("fault")
            err = MXNetError("serving loop died: %s: %s"
                             % (type(e).__name__, e))
            # capture AND DETACH the in-flight survivors (under the
            # failover lock — a concurrent wedge-rescue sweep racing
            # this handler must not capture the same request twice)
            # before releasing their blocks: prompt + generated-so-far
            # is all a failover replay needs, the KV is reconstructible
            # from tokens
            states = []
            with self._failover_lock:
                for s in (self.scheduler.running
                          + self.scheduler.prefilling):
                    req = s.request
                    if req is None or req._event.is_set():
                        continue
                    states.append((req, list(s.tokens), s.prompt_len))
                    s.request = None
                    s.done = True
            # the dead replica's blocks go back to the pool NOW (leak
            # audit: in-use returns to zero once the batch closes out);
            # reusable=False — a dying loop cannot certify its KV
            for seq in (self.scheduler.running
                        + self.scheduler.prefilling):
                try:
                    self.engine.release(seq, reusable=False)
                except Exception:
                    pass
            self.scheduler.running = []
            self.scheduler.prefilling = []
            self._release_chaos_blocks()
            with self.scheduler._lock:
                queued = list(self.scheduler._queue)
                self.scheduler._queue.clear()
            rescued = False
            if self.on_death is not None:
                try:
                    self.on_death(self, queued, states)
                    rescued = True
                except Exception:   # rescue failed: fall back to failing
                    pass
            if not rescued:
                for req, tokens, _plen in states:
                    req._finish(error=err)
                    self.metrics.request_finished(req)
                for req in queued:
                    req._finish(error=err)
                    # close the ledger: submitted == completed + failed
                    # must survive a crash, or the snapshot reports
                    # phantom in-flight load forever
                    self.metrics.request_finished(req)
            raise

    def _loop_inner(self):
        rid = self.replica_id if self.replica_id is not None else 0
        it = 0
        while not self._closed:
            it += 1
            self._last_beat = time.perf_counter()
            # one tree of spans per iteration that did work (PERF.md:
            # they label the device's idle gaps between decode steps)
            with telemetry.span("serving.loop", category="serving",
                                it=it) as loop_span:
                self._iterate(rid, it, loop_span)
        # closed with work in flight (no drain, or its time ran out):
        # `close` rescues or fails the requests from their tokens so far
        self._drop_flight("close")

    def _iterate(self, rid, it, loop_span):
        """One pass of the serving loop: admit, prefill, launch the next
        decode step, collect the one the last pass launched, that step's
        bookkeeping (`Engine.decode_pass`: one step stays in flight from
        pass to pass wherever the engine can launch from a result it has
        not read), then the first tokens of the prefills this pass
        launched (`Engine.collect_firsts`: the launched step took them on
        the device); or, with nothing to do, a wait (and then no span is
        recorded)."""
        eng, sched, met = self.engine, self.scheduler, self.metrics
        # chaos seams (no-ops unless armed; utils/chaos.py): a kill
        # raises HERE — outside the engine-fault isolation — so the
        # loop dies like a real bug; a wedge sleeps so the beat goes
        # stale; exhaustion steals the free list for a few rounds
        chaos.maybe_kill_serving_loop(rid, it)
        chaos.maybe_wedge_serving_loop(rid, it)
        # rollout chaos (ISSUE 18): a standing per-iteration sleep
        # on ONE replica — the healthy-but-slow canary the rollout
        # judge must roll back on SLO burn instead of promoting
        chaos.rollout_slow_canary(rid, it)
        self._chaos_pool_pressure(rid, it)
        with telemetry.span("serving.admit",
                            category="serving") as admit_span:
            admitted, expired = sched.admit(eng)
            if not (admitted or expired or sched.prefilling
                    or sched.running):
                admit_span.cancel()     # nothing to do: this pass waits
                loop_span.cancel()
            for req in expired:
                if isinstance(req.error, DeadlineExceeded):
                    met.request_deadline_shed()
                elif isinstance(req.error, BrownoutShed):
                    met.request_brownout_shed()
                met.request_expired(req)
                met.request_finished(req)
            if eng.paged:
                # chunked prefill: allocate now, stream the prompt
                # through fixed-shape chunks co-scheduled with decode
                self._admit_paged(admitted)
                self._prefill_chunks()
            else:
                self._admit_dense(admitted)
            loop_span.attrs["batch"] = len(sched.running)
            admit_span.attrs.update(batch=len(sched.running),
                                    admitted=len(admitted),
                                    expired=len(expired))
        if sched.running or self._flight is not None:
            try:
                if chaos.decode_poison(rid, it):
                    raise MXNetError("chaos: decode step poisoned")
                if eng.spec:
                    # spec-poison seam: NaN-fill THIS iteration's
                    # draft logits — the engine must degrade the
                    # batch to the non-speculative path, token-
                    # identical to the undisturbed oracle
                    eng.chaos_spec_poison = chaos.spec_poison(rid, it)
                # launch the next step, THEN collect the one the last
                # pass left in flight: the device runs while the host
                # appends, accounts, admits and builds again
                collected, self._flight = eng.decode_pass(
                    sched.running, after=self._flight)
            except Exception as e:
                self._decode_fault(e)
                return
            for step in collected:
                self._account(step, evict=step is collected[-1])
            # the first tokens of this pass's prefills, behind the step
            # that was launched from them on the device: each read blocks
            # until its prefill has run, with that step queued behind it
            firsts = []
            try:
                for seq in eng.collect_firsts():
                    firsts.append(seq)
                    if seq.request is not None:
                        self._first_token(seq, seq.request,
                                          seq.t_last_token - seq.t_begin)
            except Exception as e:
                self._decode_fault(e)
                return
            # nothing read, but a sequence may have ended in its prefill
            # or been detached; or one ended with its first token
            if not collected or any(seq.done for seq in firsts):
                self._evict()
        elif sched.prefilling:
            pass      # chunk work ran this iteration; no decode to
                      # pace against, so loop straight into the next
                      # chunk round (sleeping here would throttle
                      # TTFT on an otherwise-idle server)
        elif not sched.pending():
            self._work.clear()
            self._work.wait(self._idle_wait * 20)
        else:
            time.sleep(self._idle_wait)

    def _decode_fault(self, e):
        """A decode fault poisons the STEPS in flight, not the history
        (unless a program had consumed the KV pools: `PoolsLost`, which
        a fault at the read of a first token in flight always is): every
        token already appended came from a step, or a prefill, that was
        collected, and what was launched and not collected is dropped
        with the fault (the greedy replay chooses those tokens again).
        Re-home the batch onto this server's own queue as failover
        replays (prompt + generated so far re-prefills, decode continues
        token-identically) instead of failing user-visible work; a
        request that keeps hitting faults exhausts max_failovers and
        surfaces the error."""
        self.metrics.engine_failure()
        err = MXNetError("engine decode failed: %s: %s"
                         % (type(e).__name__, e))
        if isinstance(e, PoolsLost):
            self._replay_all(err)     # the prefilling ones too
            return
        self._drop_flight("fault")
        self._resume_locally(self.scheduler.running, err)
        self.scheduler.running = []

    def _account(self, step, evict):
        """A collected decode step's bookkeeping: the metrics, each
        emitted token's record and, after the pass's last collect, the
        eviction of what has ended. The step's time is the step
        interval: from its launch, or from the collect before it where
        that came later (a step launched ahead waits for the one before
        it), to its own collect."""
        eng, met = self.engine, self.metrics
        advanced = step.advanced
        with telemetry.span("serving.account", category="serving",
                            to_flight=False, batch=len(advanced)):
            since = max(step.t_launch, self._last_step_t or 0.0)
            self._last_step_t = step.t_read
            met.decode_collected(step.ahead, step.drains, step.walk,
                                 eng.moe)
            if advanced:  # count only sequences that really stepped
                # a speculative step emits a BURST per sequence, so
                # tokens = post-len minus pre-len, not 1 per step
                emitted = sum(len(gaps) for *_, gaps in advanced)
                met.decode_step(len(advanced), eng.max_batch,
                                step.t_read - since,
                                cache_util=eng.cache_utilization(),
                                paged=eng.paged, tokens=emitted,
                                live_max=max(row[1] for row in advanced))
                if eng.last_spec is not None:
                    met.spec_pass(**eng.last_spec)
                    eng.last_spec = None
                # per-request inter-token latency (ISSUE 13): the ITL
                # SLO and the lifecycle ledger see every gap, as the
                # engine put it on the token's record
                met.step_tokens_generated(advanced)
            if evict:
                self._evict()

    def _evict(self):
        for req in (s.request for s in self.scheduler.evict(self.engine)
                    if s.request is not None):
            self.metrics.request_finished(req)

    def _drop_flight(self, reason):
        """Forget the step in flight, uncollected (a fault, a replay, the
        loop's end), and with it the first tokens in flight that it was
        launched from: none of their tokens was appended, so every
        sequence's tokens are still exactly those of collected steps and
        prefills, and a replay from them chooses the dropped ones again.
        What the step wrote lies in its own sequences' blocks; whoever
        gets them next is queued behind it on the device."""
        self.engine.drop_firsts()
        if self._flight is not None:
            self._flight = None
            self.metrics.decode_drained(reason)

    def _first_sync_reason(self, more=False):
        """Why a prefill's first token is read inside the pass, before
        anything else is done with the sequence, or None where it stays
        in flight (`Engine.first_sync_reason`): the engine's reason; that
        the sequence is handed to another replica with that token
        (`on_prefill_done`); or that `more` prompts are admitted behind
        it in this pass. One first token is in flight at a time, so that
        a `serving.prefill` span holds its own program on the device and
        no other prompt's (what reads a prefill's device time finds it by
        that span); the pass's last prompt is the one carried. The metrics
        count prefills by it."""
        if self.on_prefill_done is not None:
            return "hand_off"
        return self.engine.first_sync_reason or ("more_admitted" if more
                                                 else None)

    def _admit_dense(self, admitted):
        """PR 1 admission: each admitted request's WHOLE prefill is
        launched before the decode step (the gather path's one-shot
        prefill), and its first token read there and then only where
        something needs it at once (`_first_sync_reason`: every prompt of
        the pass but the last, too): otherwise the sequence joins the
        running set with that token in flight, the pass's decode step
        takes it on the device, and `_iterate` reads it behind that
        step's launch."""
        eng, sched, met = self.engine, self.scheduler, self.metrics
        for i, req in enumerate(admitted):
            sync = self._first_sync_reason(more=i + 1 < len(admitted))
            t0 = time.perf_counter()
            try:
                # the engine's prefill span inherits the request's trace
                # id via the thread-local (the Sequence only learns its
                # request after start() returns)
                prev = telemetry.set_trace(req.trace)
                try:
                    seq = eng.start(req.prompt, req.max_new_tokens,
                                    eos_id=req.eos_id, hold=sync is None)
                finally:
                    telemetry.set_trace(prev)
            except Exception as e:  # engine fault: fail THIS request,
                met.engine_failure()  # the loop (and the rest of the
                err = MXNetError(     # batch) live on
                    "engine prefill failed: %s: %s"
                    % (type(e).__name__, e))
                if isinstance(e, PoolsLost):
                    self._replay_all(err, req)
                    continue
                req._finish(error=err)
                met.request_finished(req)
                continue
            if seq is None:       # transient block shortage: requeue
                # this one AND everything admitted behind it, in order
                with sched._lock:
                    for r in reversed(admitted[i:]):
                        sched._queue.appendleft(r)
                break
            seq.request = req
            req.state = "running"
            _queue_span(req)
            met.request_admitted(req)
            if seq.first is None:
                self._first_token(seq, req, time.perf_counter() - t0, sync)
                # disaggregated serving: same hand-off seam as the
                # chunked path — the dense one-shot prefill just
                # completed and the first token is appended
                if not seq.done and self.on_prefill_done is not None \
                        and not req._event.is_set() \
                        and self._migrate_out(seq, req):
                    continue
            sched.running.append(seq)

    def _first_token(self, seq, req, prefill_s, sync=None):
        """A request's prefill has ended with its first token here (read
        behind the launch of the step that took it on the device, or
        inside the pass for the reason `sync`, which the metrics count):
        the metrics' stamps, and the token's record on the request's
        timeline. It spans the
        prefill from where the engine took the sequence in (`first`; the
        prefills it counts are its own and whatever ran between its
        chunks) or, for a failover's replay, from the victim's last
        token, which is the gap the client saw and `serving_itl_seconds`
        observed; it ends where the host held the prefill's result, and
        `stamp_lag_us` says how much later `t_first_token` was stamped."""
        since = req.t_last_token
        self.metrics.request_prefilled(
            req, prefill_s, seq.t_last_token, seq.attn, self.engine.moe,
            sync)
        attrs = {"stamp_lag_us": int(
            (req.t_first_token - seq.t_last_token) * 1e6)}
        if since is None:
            since = seq.t_begin
            attrs["first"] = 1
        self.engine.record_tokens([(seq, len(seq.tokens) - 1)],
                                  seq.t_last_token, since=since, **attrs)

    def _admit_paged(self, admitted):
        """Paged admission: allocate cache blocks only; the prompt
        streams through `_prefill_chunks` across loop iterations."""
        eng, sched, met = self.engine, self.scheduler, self.metrics
        for i, req in enumerate(admitted):
            try:
                seq = eng.begin(req.prompt, req.max_new_tokens,
                                eos_id=req.eos_id)
            except Exception as e:
                met.engine_failure()
                err = MXNetError("engine prefill failed: %s: %s"
                                 % (type(e).__name__, e))
                if isinstance(e, PoolsLost):    # the copy-on-write or
                    self._replay_all(err, req)  # the scale reset
                    continue
                req._finish(error=err)
                met.request_finished(req)
                continue
            if seq is None:       # transient block shortage: requeue
                with sched._lock:
                    for r in reversed(admitted[i:]):
                        sched._queue.appendleft(r)
                break
            seq.request = req
            req.state = "running"
            _queue_span(req)
            met.request_admitted(req)
            if req.migrated and getattr(seq, "cache_hit_tokens", 0):
                # the migration hop's savings ledger, priced at THIS
                # engine's KV layout: every prompt token the prefix
                # cache already held is KV the hop did not re-transport
                # (re-prefill) — accounted per hop, on the target
                met.request_migration_savings(
                    req, seq.cache_hit_tokens,
                    seq.cache_hit_tokens * eng.kv_bytes_per_token())
            sched.prefilling.append(seq)

    def _prefill_chunks(self):
        """Advance every mid-prefill sequence by ONE chunk (FIFO),
        bounded by the scheduler's token budget net of the decode batch
        — then the decode step runs: a long prompt prefilling in chunks
        can never starve in-flight decode sequences, and a tight budget
        spreads a multi-chunk prompt over several iterations. At least
        one chunk always runs when nothing is decoding (progress)."""
        eng, sched, met = self.engine, self.scheduler, self.metrics
        budget = sched.token_budget
        # the decode batch's claim on this iteration, at the same price
        # admission charges: k+1 scored tokens per speculating sequence.
        # Pricing both sides identically is what keeps chunks and
        # speculative decode from starving each other under one budget.
        spent = eng.decode_tokens_per_step() * len(sched.running)
        for seq in list(sched.prefilling):
            if seq.done:
                # detached by a router failover while this loop was
                # wedged: the request lives elsewhere now — release the
                # blocks (mid-prefill KV may be partial) and move on
                sched.prefilling.remove(seq)
                try:
                    eng.release(seq, reusable=False)
                except Exception:
                    pass
                continue
            cost = eng.prefill_tokens_per_step(seq.prompt_len)
            if budget is not None and spent + cost > budget \
                    and spent > 0:
                break             # rest keep their place for next round
            t0 = time.perf_counter()
            try:
                done = eng.prefill_step(seq)
            except Exception as e:  # chunk fault: fail THIS request,
                met.engine_failure()  # free its blocks, keep serving
                if isinstance(e, PoolsLost):
                    self._replay_all(MXNetError(
                        "engine prefill failed: %s: %s"
                        % (type(e).__name__, e)))
                    return
                sched.prefilling.remove(seq)
                try:
                    eng.release(seq, reusable=False)
                except Exception:
                    pass
                if seq.request is not None:
                    seq.request._finish(error=MXNetError(
                        "engine prefill failed: %s: %s"
                        % (type(e).__name__, e)))
                    met.request_finished(seq.request)
                continue
            seq.prefill_s += time.perf_counter() - t0
            spent += cost
            if seq.request is not None:
                met.request_chunk(seq.request, seq.prefilled)
            if done:
                sched.prefilling.remove(seq)
                req = seq.request
                if req is not None:
                    self._first_token(seq, req, seq.prefill_s,
                                      self._first_sync_reason())
                # disaggregated serving: a prefill-role replica hands
                # the finished prompt to a decode replica here — after
                # the first token (TTFT observed on THIS replica, which
                # really produced it), before any steady-state decode.
                # A sequence whose generation is already complete
                # (seq.done: eos / budget hit on the first token)
                # finishes locally; a failed placement falls through to
                # local decode (co-scheduled fallback, no behavior
                # change).
                if req is not None and not seq.done \
                        and self.on_prefill_done is not None \
                        and not req._event.is_set() \
                        and self._migrate_out(seq, req):
                    met.prefill_chunk(len(sched.prefilling))
                    continue
                sched.running.append(seq)
            met.prefill_chunk(len(sched.prefilling))

    # -- migration (disaggregated serving, ISSUE 17) -------------------------

    def _migrate_out(self, seq, req):
        """Hand one just-prefilled sequence to the router's migration
        hook (`on_prefill_done`). Returns True when the request now
        lives elsewhere (a migration resume was placed on a decode
        replica, or nothing remained and the hook finished it) — the
        local sequence is then released, its fully-prefilled KV
        registered in THIS replica's prefix cache so a same-prefix
        prompt never re-prefills here. Returns False when the source
        should keep decoding it locally (no healthy decode replica, or
        every one saturated): co-scheduled fallback, byte-for-byte the
        role-less behavior.

        Exactly-once: the sequence is DETACHED under the failover lock
        BEFORE the hook can place a replay anywhere — once a resume
        exists, this loop can only ever release, never finish. A failed
        placement re-attaches; the sequence was in neither scheduler
        list during the window (the caller popped it from `prefilling`
        and hasn't appended to `running`), so no rescue sweep can have
        captured it meanwhile."""
        hook = self.on_prefill_done
        with self._failover_lock:
            if seq.request is None or req._event.is_set():
                return False
            seq.request = None
            seq.done = True
        tokens = list(seq.tokens)
        try:
            placed = bool(hook(self, req, tokens))
        except Exception:
            placed = False
        if not placed:
            with self._failover_lock:
                seq.request = req
                seq.done = False
            return False
        # the prompt is fully prefilled and its first token appended:
        # the KV is certified, so reusable=True keeps the prompt
        # resident in the source's prefix cache for the next same-prefix
        # arrival while the blocks go back to the pool
        try:
            self.engine.release(seq, reusable=True)
        except Exception:
            pass
        return True

    # -- failover ------------------------------------------------------------

    def _resume_locally(self, seqs, err):
        """Decode-fault recovery: release every poisoned sequence's
        blocks and re-queue each request on THIS server as a failover
        replay (prompt + tokens generated so far; the generated history
        predates the faulted step, so it is trustworthy and the greedy
        continuation is token-identical). A request that has exhausted
        its failover budget surfaces the engine error instead."""
        for seq in list(seqs):
            req = seq.request
            tokens = list(seq.tokens)
            try:
                self.engine.release(seq, reusable=False)
            except Exception:
                pass
            if req is not None:
                self._replay(req, tokens, err)

    def _replay(self, req, tokens, err):
        """Re-queue one request here as a failover replay of `tokens`,
        or surface `err` when its failover budget is spent or the queue
        is full."""
        if req._event.is_set():
            return
        if req.failovers >= self.max_failovers:
            req._finish(error=err)
            self.metrics.request_finished(req)
            return
        try:
            resume, carried = spawn_resume(req, tokens, self)
        except QueueFull:
            req._finish(error=err)
            self.metrics.request_finished(req)
            return
        if resume is None:      # generation was already complete
            self.metrics.request_finished(req)
        else:
            self.metrics.request_failover(req, carried)

    def _replay_all(self, err, req=None):
        """The engine lost its KV pools (`PoolsLost`: a step failed after
        it had consumed them, and the engine made them anew, empty). No
        sequence's history is on the device any more, so a fault that
        would have cost one step or one request costs every sequence
        its cache: replay everything running and prefilling, and `req`,
        the request being admitted, which has no sequence yet."""
        sched = self.scheduler
        self._drop_flight("fault")
        seqs = sched.running + sched.prefilling
        sched.running, sched.prefilling = [], []
        self._resume_locally(seqs, err)
        if req is not None:
            self._replay(req, list(req.prompt), err)

    # -- chaos seams ---------------------------------------------------------

    def _chaos_pool_pressure(self, rid, it):
        """Armed serve_exhaust: steal the whole free list for a few loop
        iterations (admission sees transient exhaustion and queues), then
        hand the blocks back."""
        if self._chaos_stolen is not None:
            ids, release_at = self._chaos_stolen
            if it >= release_at:
                if ids:
                    self.engine.cache.pool.free(ids)
                self._chaos_stolen = None
            return
        hold = chaos.pool_exhaustion(rid, it)
        if hold and self.engine.cache is not None:
            pool = self.engine.cache.pool
            ids = pool.try_alloc(pool.available) or []
            self._chaos_stolen = (ids, it + hold)

    def _release_chaos_blocks(self):
        if self._chaos_stolen is None:
            return
        ids, _ = self._chaos_stolen
        self._chaos_stolen = None
        try:
            if ids:
                self.engine.cache.pool.free(ids)
        except Exception:
            pass

    # -- router hooks --------------------------------------------------------

    def _final_reject(self):
        self.metrics.request_rejected()

    def load_tokens(self):
        """Routing score for the front door: tokens this replica is
        still committed to — queued requests' prompt+generation budgets
        plus every in-flight sequence's remaining tokens. One backlog
        walk (`_load_split`) feeds both this score and the deadline
        gate, so the two can never silently diverge. Advisory (the
        serving thread mutates the running set concurrently); list
        copies keep the reads safe."""
        pre, dec = self._load_split()
        return pre + dec

    def drain_queue(self):
        """Pull every queued (not yet admitted) request off this
        replica's scheduler — the router calls this when the replica
        wedges, then re-routes the orphans to healthy replicas."""
        with self.scheduler._lock:
            orphans = list(self.scheduler._queue)
            self.scheduler._queue.clear()
        return orphans

    def adopt(self, req):
        """Enqueue a Request object created elsewhere (a drained
        replica's orphan). Raises QueueFull under backpressure."""
        if self._closed:
            raise QueueFull("replica is closed")
        self.scheduler.submit(req)
        self._work.set()
        return req


def spawn_resume(orig, tokens, target):
    """Place one failover replay for `orig` onto `target` (an LMServer):
    the resume request's prompt is `tokens` — the original prompt plus
    everything generated before the fault — replayed as a prefill
    (hitting the target's prefix cache when the prefix is resident),
    after which decode continues. The stitch callback completes `orig`
    from the resume's result, so the client's future resolves with ONE
    seamless token stream, greedy-token-identical to an undisturbed run.

    Returns `(resume, carried)`; `resume` is None when the generation
    was already complete (orig finished directly, nothing placed).
    Raises QueueFull when the target can't absorb it. Ledger/metric
    accounting stays with the caller."""
    resume, carried = make_resume(orig, tokens, target.engine.max_len)
    if resume is None:
        orig._finish(tokens=list(tokens))
        return None, carried

    def stitch(r):
        if r.error is None:
            orig._finish(tokens=list(r.tokens))
        else:
            orig._finish(error=r.error)

    resume._on_finish = stitch
    target.adopt(resume)
    # the hop annotation on the request's (single, stitched) trace row:
    # Perfetto shows where the request moved and how much it salvaged
    now_us = time.perf_counter_ns() // 1000
    telemetry.record_span("serving.failover_hop", now_us, 0,
                          trace=orig.trace, category="serving",
                          to_profiler=False, request=orig.id,
                          resume=resume.id, carried_tokens=carried,
                          hop=resume.failovers,
                          target=target.replica_id)
    return resume, carried


def spawn_migrate(orig, tokens, target):
    """Place one PLANNED prefill->decode migration hop for `orig` onto
    `target` (a decode-role LMServer): same replay transport as
    `spawn_resume` — the target re-prefills prompt + generated-so-far
    (skipping every KV block its prefix cache already holds) and decode
    continues greedy-token-identically — but the hop is disaggregated
    serving's steady-state move, not a fault: the resume spends no
    failover budget and admission treats it as already-admitted work
    (never brownout-shed or clamped). Deadline, tenant, priority, the
    client's latency anchors, and the W3C trace all ride along, so the
    request stays ONE connected trace row and is SLO-classified exactly
    once, by client truth, at its terminal state on the target.

    Returns `(resume, carried)`; `resume` is None when the generation
    was already complete (orig finished directly, nothing placed).
    Raises QueueFull when the target can't absorb it. Ledger/metric
    accounting stays with the caller."""
    resume, carried = make_resume(orig, tokens, target.engine.max_len,
                                  migrate=True)
    if resume is None:
        orig._finish(tokens=list(tokens))
        return None, carried

    def stitch(r):
        if r.error is None:
            orig._finish(tokens=list(r.tokens))
        else:
            orig._finish(error=r.error)

    resume._on_finish = stitch
    target.adopt(resume)
    now_us = time.perf_counter_ns() // 1000
    telemetry.record_span("serving.migration_hop", now_us, 0,
                          trace=orig.trace, category="serving",
                          to_profiler=False, request=orig.id,
                          resume=resume.id, carried_tokens=carried,
                          target=target.replica_id)
    return resume, carried


def serve(model, replicas=None, autoscale=None, roles=None,
          rollout=None, **kwargs):
    """Build and start a serving front door over `model` (see module
    docstring for accepted forms). With `replicas=N > 1` (or
    `MXNET_SERVING_REPLICAS=N`) this is a `ReplicatedLMServer`: N engine
    replicas — each with its own scheduler, cache pool, serving thread,
    and metrics registry — behind one submit/HTTP front with
    least-loaded routing (router.py). Otherwise a single `LMServer`.
    `autoscale=True` (or MXNET_SERVING_AUTOSCALE=1) arms SLO-driven
    elastic scaling (serving/autoscale.py) — that always builds the
    replicated door, even at replicas=1, so the fleet can grow.
    `roles="prefill:N,decode:M"` (or MXNET_SERVING_ROLES) builds a
    disaggregated fleet: prefill replicas absorb prompt processing and
    migrate finished prompts to decode replicas over the replay
    transport; replica count is the sum of the role counts (the
    `replicas` arg is ignored when roles are set).
    `rollout=<checkpoint dir>` (or MXNET_SERVING_ROLLOUT_DIR) attaches
    a live-rollout watcher (serving/rollout.py): newly published
    checkpoint steps canary, judge, and promote with zero downtime —
    this too always builds the replicated door, even at replicas=1,
    so a canary replica has somewhere to stand. Keyword args pass
    through to each LMServer."""
    from .autoscale import autoscale_enabled
    from .router import (ReplicatedLMServer, serving_replicas,
                         serving_roles)
    from .rollout import rollout_dir
    role_map = serving_roles(roles)
    scale = autoscale_enabled() if autoscale is None else autoscale
    rdir = rollout_dir() if rollout is None else (rollout or None)
    if role_map:
        srv = ReplicatedLMServer(model, roles=role_map,
                                 autoscale=scale, **kwargs)
    else:
        n = serving_replicas() if replicas is None else int(replicas)
        if n > 1 or scale or rdir:
            srv = ReplicatedLMServer(model, replicas=n,
                                     autoscale=scale, **kwargs)
        else:
            return LMServer(model, **kwargs)
    if rdir:
        srv.attach_rollout(rdir, start=True)
    return srv
