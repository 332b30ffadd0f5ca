"""Compile watchdog + executable memory accounting (ISSUE 9).

Every jit entry point the framework owns — `TrainStep`'s fused step, the
serving engine's prefill-chunk/decode executables, the full-forward
serving adapters, `predict.export_*` — registers through ONE seam:
`instrument(jax.jit(fn), site=...)`. The wrapper owns the executable
cache (signature -> `lower().compile()` AOT executable), so every
compilation is an explicit, observable event instead of a silent stall
inside jax's dispatch:

  * **Signature-diff attribution**: each compile is diffed against the
    site's cached signatures — which argument's shape / dtype / sharding
    / static flag changed, rendered as a human-readable reason
    ("tables: shape (1, 1) -> (1, 2) (axis 1)"). Sites are
    PROCESS-GLOBAL while executable caches are per-instance, so an
    engine restart that recompiles an already-seen signature is
    attributed as a `duplicate` (the cold-executable-cache gap the
    ROADMAP item-5 AOT cache exists to close), and a tp restart with
    unchanged shapes is attributed to the sharding diff.
  * **Recording**: a `compile` span (wall-time into the `compile_seconds`
    histogram), a flight-recorder event, a global `compile_total` and a
    per-site `compile_<site>_total` counter — all on the default
    registry, all no-ops under `MXNET_TELEMETRY=0` (signature tracking
    and the engine's recompile counters stay functional: they are
    behavior, not telemetry).
  * **Memory & cost accounting**: after each compile the executable's
    `memory_analysis()` / `cost_analysis()` (None where a backend does
    not provide them) land in per-site gauges —
    `exec_<site>_{argument,output,temp,code,hbm}_bytes` and
    `exec_<site>_flops` — exported through the Prometheus exposition
    and every flight dump.
  * **Collective-comms ledger** (ISSUE 14): the compiled HLO is walked
    once per compile and every collective instruction (all-reduce,
    reduce-scatter, all-gather, all-to-all, collective-permute) is
    attributed to its site as per-kind byte/op gauges —
    `comms_<site>_<kind>_bytes` / `comms_<site>_<kind>_ops` — plus a
    derived `comms_<site>_fraction` (collective payload over the
    executable's total `bytes accessed`). Bytes are the per-device
    LOGICAL payload of each instruction, max(operand, result) — a
    reduce-scatter counts its full input, an all-gather its full
    output, so the ZeRO-1 train step's reduce-scatter/all-gather both
    read ≈ param bytes (the analytic pin in
    tests/test_train_observability.py) — not the ring-wire traffic
    (which is topology-dependent: 2(N−1)/N× for a ring all-reduce).
  * **Budgets**: `MXNET_COMPILE_BUDGET=<n>[:warn|:raise]` turns the
    (n+1)-th compile at any one site into a warning or a raise — a
    recompile storm fails loudly instead of silently eating throughput.
    `MXNET_HBM_BUDGET_GB=<gb>[:raise|:warn]` is a pre-flight check: an
    executable whose compiled footprint (arguments + outputs + temps +
    generated code) exceeds the budget is refused BEFORE dispatch
    (default) or warned about, instead of dying as an opaque device OOM
    mid-serve.

`watchdog().events()` is the in-process record (what tests and
`bench.py`'s `compile_s` / `exec_hbm_bytes` fields read);
`tools/postmortem.py` renders the flight-recorder copies.
"""
from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
import time
import warnings
from collections import deque

from ..base import MXNetError
from .metrics import enabled, default_registry, _sane


class CompileBudgetExceeded(MXNetError):
    """MXNET_COMPILE_BUDGET=<n>:raise tripped: one site compiled more
    than <n> distinct programs — a recompile storm (an unstable shape
    bucket, a sharding flapping between configs) that would otherwise
    just eat throughput silently."""


class HbmBudgetExceeded(MXNetError):
    """MXNET_HBM_BUDGET_GB pre-flight refusal: the compiled executable's
    footprint exceeds the declared budget; refusing before dispatch
    beats an opaque device OOM mid-request."""


# -- metric-name templates (docs/OBSERVABILITY.md lists these; the static
# -- doc-drift check resolves `<site>` placeholders against them) ----------
COMPILE_SECONDS = "compile_seconds"
COMPILE_TOTAL = "compile_total"
COMPILE_DUPLICATE_TOTAL = "compile_duplicate_total"
COMPILE_OVERRUNS_TOTAL = "compile_budget_overruns_total"
SITE_COMPILE_TOTAL = "compile_%s_total"
COMPILE_CACHE_HITS = "compile_cache_hits"
COMPILE_CACHE_MISSES = "compile_cache_misses"
COMPILE_CACHE_STORES = "compile_cache_stores"
COMPILE_CACHE_LOAD_SECONDS = "compile_cache_load_seconds"
COMPILE_CACHE_CORRUPT_TOTAL = "compile_cache_corrupt_total"
EXEC_ARG_BYTES = "exec_%s_argument_bytes"
EXEC_OUT_BYTES = "exec_%s_output_bytes"
EXEC_TEMP_BYTES = "exec_%s_temp_bytes"
EXEC_CODE_BYTES = "exec_%s_code_bytes"
EXEC_HBM_BYTES = "exec_%s_hbm_bytes"
EXEC_FLOPS = "exec_%s_flops"
COMMS_BYTES = "comms_%s_%s_bytes"
COMMS_OPS = "comms_%s_%s_ops"
COMMS_FRACTION = "comms_%s_fraction"

#: compile-seconds histogram buckets: traces take ms, XLA compiles of a
#: fused train step take seconds to minutes
_COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0, 30.0, 60.0, 120.0, 300.0, 600.0)

#: warm-load buckets: deserializing a cached executable is disk + PJRT
#: load work — milliseconds to a few seconds, never an XLA compile
_CACHE_LOAD_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                       0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _parse_budget(env_var, default_policy, convert):
    """`<value>[:warn|:raise]` -> (converted value, policy) or
    (None, None). Any malformed part raises MXNetError NAMING the env
    var — this parse runs deep inside a compile, where a bare
    int()/float() ValueError would say nothing about its origin."""
    raw = os.environ.get(env_var)
    if not raw:
        return None, None
    value, _, policy = raw.partition(":")
    policy = policy or default_policy
    if policy not in ("warn", "raise"):
        raise MXNetError("%s policy must be warn or raise, got %r"
                         % (env_var, policy))
    try:
        value = convert(value)
    except ValueError:
        raise MXNetError("%s must be <number>[:warn|:raise], got %r"
                         % (env_var, raw))
    return value, policy


def compile_budget():
    """MXNET_COMPILE_BUDGET=<n>[:warn|:raise] — max distinct compilations
    per site; overruns warn by default. Returns (n, policy) or
    (None, None). Read at each compile, so it can be tightened live."""
    return _parse_budget("MXNET_COMPILE_BUDGET", "warn", int)


def hbm_budget_bytes():
    """MXNET_HBM_BUDGET_GB=<gb>[:raise|:warn] — pre-flight executable
    footprint ceiling; overruns refuse dispatch by default. Returns
    (bytes, policy) or (None, None)."""
    value, policy = _parse_budget("MXNET_HBM_BUDGET_GB", "raise", float)
    if value is None:
        return None, None
    return value * (1024.0 ** 3), policy


# ---------------------------------------------------------------------------
# signatures: what distinguishes one compiled program from another
# ---------------------------------------------------------------------------


try:
    from jax.sharding import NamedSharding as _NamedSharding
except Exception:                                        # pragma: no cover
    _NamedSharding = ()


@functools.lru_cache(maxsize=512)
def _sharding_desc_cached(s):
    """Stable string for a placement. NamedShardings render by mesh axis
    sizes + spec (two engines over equal-shaped meshes of different Mesh
    objects must produce EQUAL signatures, or every restart would read
    as a sharding diff) — the cache key is the sharding OBJECT, but the
    rendered value is identity-free, so unequal objects with the same
    placement still collide to one signature on a cache miss. signature()
    runs on EVERY dispatch; without the memo this rendering dominates
    the per-call cost."""
    if isinstance(s, _NamedSharding):
        axes = ",".join("%s=%d" % kv for kv in s.mesh.shape.items())
        # normalize the spec: P(None, 'tp', None) and P(None, 'tp')
        # are the same placement, but jit outputs trim trailing
        # Nones while device_put placements keep them — a raw repr
        # would misread every round-trip as a sharding change
        spec = tuple(s.spec)
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return "NamedSharding({%s}, %s)" % (axes, spec)
    return type(s).__name__


def _sharding_desc(v):
    s = getattr(v, "sharding", None)
    if s is None or not getattr(v, "_committed", True):
        # numpy/python inputs and UNCOMMITTED device arrays produce the
        # same executable (jax's own cache treats them alike) — both
        # must read "host", or an engine feeding numpy decode batches
        # would recompile programs its jnp prefill args already built
        return "host"
    try:
        return _sharding_desc_cached(s)
    except Exception:                                    # pragma: no cover
        return type(s).__name__       # unhashable exotic sharding


@functools.lru_cache(maxsize=64)
def _dtype_str(dt):
    return str(dt)


def _leaf_sig(v):
    shape = getattr(v, "shape", None)
    if shape is None:
        # a python static (bool flag, enum string): its VALUE is part of
        # the program identity, unlike a dynamic array argument's
        return ("static", type(v).__name__, repr(v))
    try:
        dtype = _dtype_str(getattr(v, "dtype", "?"))
    except TypeError:                                    # pragma: no cover
        dtype = str(v.dtype)
    return (tuple(shape), dtype, _sharding_desc(v))


def signature(args):
    """Per-top-level-argument signature tuple for a positional call."""
    import jax
    return tuple(tuple(_leaf_sig(l) for l in jax.tree.leaves(a))
                 for a in args)


def _axes_changed(a, b):
    if len(a) != len(b):
        return "rank %d -> %d" % (len(a), len(b))
    axes = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
    return "axis " + ",".join(str(i) for i in axes) if axes else ""


def _leaf_diff(old_leaf, new_leaf):
    """One leaf's human-readable change."""
    if old_leaf[0] == "static" or new_leaf[0] == "static":
        return "static %s -> %s" % (old_leaf[-1], new_leaf[-1])
    parts = []
    if old_leaf[0] != new_leaf[0]:
        extra = _axes_changed(old_leaf[0], new_leaf[0])
        parts.append("shape %s -> %s%s"
                     % (old_leaf[0], new_leaf[0],
                        " (%s)" % extra if extra else ""))
    if old_leaf[1] != new_leaf[1]:
        parts.append("dtype %s -> %s" % (old_leaf[1], new_leaf[1]))
    if old_leaf[2] != new_leaf[2]:
        parts.append("sharding %s -> %s" % (old_leaf[2], new_leaf[2]))
    return ", ".join(parts) or "changed"


def _arg_diff(old_arg, new_arg):
    if len(old_arg) != len(new_arg):
        return "structure %d -> %d leaves" % (len(old_arg), len(new_arg))
    diffs = [i for i, (o, n) in enumerate(zip(old_arg, new_arg)) if o != n]
    if not diffs:
        return "unchanged"
    text = _leaf_diff(old_arg[diffs[0]], new_arg[diffs[0]])
    if len(old_arg) > 1:
        text = "leaf %d: %s" % (diffs[0], text)
    if len(diffs) > 1:
        text += " (+%d more leaves)" % (len(diffs) - 1)
    return text


def diff_reason(argnames, cached_sigs, new_sig):
    """Attribute a new signature to the smallest diff against the site's
    cached signatures: which ARGUMENT changed, and how. Returns the
    human-readable reason string the compile event carries."""
    candidates = [s for s in cached_sigs if len(s) == len(new_sig)]
    if not candidates:
        if cached_sigs:
            return ("argument structure changed (%d args -> %d args)"
                    % (len(next(iter(cached_sigs))), len(new_sig)))
        return "first compilation at this site"
    # nearest neighbor: fewest differing arguments
    def ndiff(s):
        return sum(1 for o, n in zip(s, new_sig) if o != n)
    best = min(candidates, key=ndiff)
    parts = []
    for i, (o, n) in enumerate(zip(best, new_sig)):
        if o == n:
            continue
        name = (argnames[i] if argnames and i < len(argnames)
                else "arg%d" % i)
        parts.append("%s: %s" % (name, _arg_diff(o, n)))
    return "; ".join(parts) if parts else "identical signature"


# ---------------------------------------------------------------------------
# the watchdog
# ---------------------------------------------------------------------------


class CompileSite:
    """One named compile seam. Signature history is PROCESS-wide (so a
    restarted engine diffs against its predecessor's signatures);
    executable caches live on the InstrumentedJit instances."""

    def __init__(self, name):
        self.name = name
        self.sane = _sane(name.replace(".", "_"))
        self.signatures = {}          # sig -> first-seen event seq
        self.compiles = 0             # process-wide compiles at this site
        self.duplicates = 0           # same-sig recompiles (cold caches)
        self.cache_hits = 0           # executables warm-loaded from disk
        self.comms = None             # latest executable's comms ledger


def _analyses(compiled):
    """(memory dict, flops, bytes accessed) from a compiled executable.
    Telemetry at a boundary that must keep running: an analysis a backend
    does not provide reads as None, never as an exception."""
    memory = None
    try:
        ma = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(ma, "generated_code_size_in_bytes",
                                      0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        # aliased (donated) buffers overlap the argument set; don't
        # double-count them in the footprint
        memory["hbm_bytes"] = (memory["argument_bytes"]
                               + memory["output_bytes"]
                               - memory["alias_bytes"]
                               + memory["temp_bytes"]
                               + memory["code_bytes"])
    except Exception:
        memory = None
    flops = None
    bytes_accessed = None
    try:
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0)) or None
        bytes_accessed = float(cost.get("bytes accessed", 0.0)) or None
    except Exception:
        flops = None
    return memory, flops, bytes_accessed


# ---------------------------------------------------------------------------
# collective-comms ledger: bytes per collective kind, read off the HLO
# ---------------------------------------------------------------------------

#: the collective opcodes the ledger attributes (gauge-name kinds are the
#: underscored forms: all_reduce, reduce_scatter, ...)
COLLECTIVE_KINDS = ("all_reduce", "reduce_scatter", "all_gather",
                    "all_to_all", "collective_permute")

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2,
                "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
#: one collective instruction: `%name = RESULT opcode(OPERANDS...`,
#: where RESULT is a shape or a tuple of shapes. `-start` matches the
#: async forms; the paired `-done` (which would double-count) does not.
_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)((?:-start)?)\(([^)]*)")


#: any instruction: `%name = RESULT opcode(` — the installed XLA prints
#: operands by name only (`reduce-scatter(%param.2)`), so an operand's
#: shape is its defining instruction's result
_DEFINITION_RE = re.compile(r"%([\w.\-]+)\s+=\s+(\([^)]*\)|\S+)\s")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(text):
    """Summed byte size of every `dtype[dims]` shape token in `text`."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def comms_from_hlo(hlo_text):
    """{kind: {"bytes": b, "ops": n}} over the collective instructions
    of one (per-device SPMD) HLO module. Bytes are the instruction's
    logical payload — max(summed operand shapes, summed result shapes)
    — so a reduce-scatter counts its full input and an all-gather its
    full output: exactly the hand-computable ZeRO-1 sizes (≈ param
    bytes each), independent of which side the partitioner sharded.

    Known limit: this is a STATIC walk — a collective inside a
    while/scan body (e.g. ring attention's per-ring-step ppermute)
    counts once, not once per iteration, so the ledger is a lower
    bound for loop-heavy programs (trip counts are not recoverable
    from HLO text in general; docs/OBSERVABILITY.md discloses this)."""
    kinds = {}
    defined = dict(_DEFINITION_RE.findall(hlo_text))
    for result, opcode, started, operands in \
            _COLLECTIVE_RE.findall(hlo_text):
        in_bytes = _shape_bytes(operands) or sum(
            _shape_bytes(defined.get(name, ""))
            for name in _OPERAND_NAME_RE.findall(operands))
        out_bytes = _shape_bytes(result)
        if started:
            # async form: the result tuple is (aliased input, real
            # output[, contexts]) — max(in, raw out) would double-count
            # the alias, while the bare operand undercounts an
            # all-gather-start (whose operand is the 1/N shard). The
            # real output side is result minus the aliased input.
            payload = max(in_bytes, out_bytes - in_bytes)
        else:
            payload = max(in_bytes, out_bytes)
        k = kinds.setdefault(opcode.replace("-", "_"),
                             {"bytes": 0, "ops": 0})
        k["bytes"] += payload
        k["ops"] += 1
    return kinds


def comms_ledger(compiled, bytes_accessed=None):
    """The per-executable collective ledger dict the watchdog records:
    {"kinds": {...}, "total_bytes", "bytes_accessed", "fraction"}.
    Returns None when the executable exposes no HLO text (an exported
    artifact observed `owned=False` never reaches here)."""
    try:
        txt = compiled.as_text()
    except Exception:
        return None
    if not txt:
        return None
    kinds = comms_from_hlo(txt)
    total = sum(k["bytes"] for k in kinds.values())
    fraction = None
    if bytes_accessed:
        # comms fraction of the step: collective payload over the
        # executable's total traffic ("bytes accessed", same per-device
        # cost model) — the at-a-glance "is this step collective-bound"
        # gauge. Payload is max(in, out) <= in + out, so it can't
        # exceed the traffic that contains it.
        fraction = total / float(bytes_accessed)
    return {"kinds": kinds, "total_bytes": total,
            "bytes_accessed": bytes_accessed, "fraction": fraction}


class Watchdog:
    """Process-wide compile observatory: named sites, a bounded event
    ring, and the metric/span/flight recording every compile flows
    through."""

    def __init__(self, registry=None):
        self._lock = threading.RLock()
        self._sites = {}
        self._events = deque(maxlen=512)
        self._seq = 0
        self._registry = registry
        self.total_seconds = 0.0

    def registry(self):
        return self._registry or default_registry()

    def site(self, name):
        with self._lock:
            s = self._sites.get(name)
            if s is None:
                s = self._sites[name] = CompileSite(name)
            return s

    def sites(self):
        with self._lock:
            return dict(self._sites)

    # -- budget gate (checked BEFORE paying a compile) ----------------------
    def check_budget(self, site):
        budget, policy = compile_budget()
        if budget is None or site.compiles + site.duplicates < budget:
            return
        msg = ("compile budget overrun at site %r: %d compilations "
               "already recorded, MXNET_COMPILE_BUDGET=%d (%s) — a "
               "recompile storm; see watchdog().events() for the "
               "signature diffs" % (site.name,
                                    site.compiles + site.duplicates,
                                    budget, policy))
        if enabled():
            self.registry().counter(
                COMPILE_OVERRUNS_TOTAL, flight=True,
                help="compile-budget overruns (MXNET_COMPILE_BUDGET)"
            ).inc(site=site.name)
        if policy == "raise":
            raise CompileBudgetExceeded(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    # -- recording ----------------------------------------------------------
    def record(self, site, sig, reason, seconds, phase=None, memory=None,
               flops=None, duplicate=False, start_us=None, comms=None):
        """Record one compile event (the seam `InstrumentedJit` and
        `compile_region` report through). Returns the event dict."""
        with self._lock:
            self._seq += 1
            seq = self._seq
            if duplicate:
                site.duplicates += 1
            else:
                site.compiles += 1
                if sig is not None:
                    site.signatures.setdefault(sig, seq)
            if comms is not None:
                site.comms = comms
            self.total_seconds += seconds
            ev = {"seq": seq, "site": site.name, "reason": reason,
                  "seconds": seconds, "phase": phase,
                  "duplicate": bool(duplicate), "t": time.time()}
            if memory:
                ev["memory"] = dict(memory)
                ev["hbm_bytes"] = memory.get("hbm_bytes")
            if flops:
                ev["flops"] = flops
            if comms is not None:
                ev["comms"] = comms
            self._events.append(ev)
        if enabled():
            reg = self.registry()
            reg.histogram(
                COMPILE_SECONDS, buckets=_COMPILE_BUCKETS,
                help="wall time per watchdog-observed compilation "
                     "(trace + XLA compile)").observe(seconds)
            reg.counter(COMPILE_TOTAL,
                        help="compilations across all watchdog sites"
                        ).inc()
            reg.counter(SITE_COMPILE_TOTAL % site.sane,
                        help="compilations at site %s" % site.name).inc()
            if duplicate:
                reg.counter(
                    COMPILE_DUPLICATE_TOTAL,
                    help="recompiles of an already-seen signature (cold "
                         "executable cache, e.g. an engine restart)"
                    ).inc()
            if memory:
                reg.gauge(EXEC_ARG_BYTES % site.sane,
                          help="argument bytes, latest executable"
                          ).set(memory["argument_bytes"])
                reg.gauge(EXEC_OUT_BYTES % site.sane,
                          help="output bytes, latest executable"
                          ).set(memory["output_bytes"])
                reg.gauge(EXEC_TEMP_BYTES % site.sane,
                          help="temp (live-activation) bytes, latest "
                               "executable").set(memory["temp_bytes"])
                reg.gauge(EXEC_CODE_BYTES % site.sane,
                          help="generated-code bytes, latest executable"
                          ).set(memory["code_bytes"])
                reg.gauge(EXEC_HBM_BYTES % site.sane,
                          help="total device footprint (args + outputs "
                               "- aliased + temps + code), latest "
                               "executable").set(memory["hbm_bytes"])
            if flops:
                reg.gauge(EXEC_FLOPS % site.sane,
                          help="declared flops, latest executable"
                          ).set(flops)
            if comms is not None:
                for kind, k in comms["kinds"].items():
                    reg.gauge(COMMS_BYTES % (site.sane, kind),
                              help="per-device %s payload bytes per "
                                   "step, latest executable"
                              % kind.replace("_", "-")).set(k["bytes"])
                    reg.gauge(COMMS_OPS % (site.sane, kind),
                              help="%s instructions in the latest "
                                   "executable"
                              % kind.replace("_", "-")).set(k["ops"])
                # the gauges claim "latest executable": a recompile
                # whose lowering DROPPED a kind must zero that kind's
                # existing gauges, not leave them advertising
                # collectives the running program no longer contains
                for kind in COLLECTIVE_KINDS:
                    if kind in comms["kinds"]:
                        continue
                    for tmpl in (COMMS_BYTES, COMMS_OPS):
                        name = tmpl % (site.sane, kind)
                        if name in reg._metrics:
                            reg.gauge(name).set(0)
                if comms["fraction"] is not None:
                    reg.gauge(COMMS_FRACTION % site.sane,
                              help="collective payload / total bytes "
                                   "accessed, latest executable"
                              ).set(comms["fraction"])
            if start_us is None:
                start_us = time.perf_counter_ns() // 1000 \
                    - int(seconds * 1e6)
            from .tracing import record_span
            record_span("compile", start_us, int(seconds * 1e6),
                        category="compile", to_flight=False,
                        site=site.name, reason=reason, phase=phase)
            from .flight import flight
            flight().record("event", "compile", site=site.name,
                            reason=reason, seconds=round(seconds, 4),
                            duplicate=bool(duplicate))
        return ev

    # -- AOT-cache recording (ISSUE 16) -------------------------------------
    def record_cache_hit(self, site, sig, seconds, phase=None):
        """One executable warm-loaded from the persistent AOT cache
        (mxnet_tpu/aot): the signature registers at the site (it IS now
        compiled in this process) but neither `compiles` nor
        `duplicates` advances — a warm load is the ABSENCE of the
        recompile the duplicate counter measures."""
        with self._lock:
            self._seq += 1
            seq = self._seq
            site.cache_hits += 1
            if sig is not None:
                site.signatures.setdefault(sig, seq)
            self.total_seconds += seconds
            ev = {"seq": seq, "site": site.name,
                  "reason": "warm-loaded from the AOT executable cache",
                  "seconds": seconds, "phase": phase, "duplicate": False,
                  "cache_hit": True, "t": time.time()}
            self._events.append(ev)
        if enabled():
            reg = self.registry()
            reg.counter(COMPILE_CACHE_HITS,
                        help="executables warm-loaded from the "
                             "persistent AOT cache (no XLA compile)"
                        ).inc()
            reg.histogram(COMPILE_CACHE_LOAD_SECONDS,
                          buckets=_CACHE_LOAD_BUCKETS,
                          help="wall time to load + rehydrate one "
                               "cached executable").observe(seconds)
            from .flight import flight
            flight().record("event", "compile_cache_hit", site=site.name,
                            seconds=round(seconds, 4))
        return ev

    def record_cache_miss(self, site):
        """A keyed lookup found no (valid) entry — the compile that
        follows will try to store one."""
        if enabled():
            self.registry().counter(
                COMPILE_CACHE_MISSES,
                help="AOT-cache lookups that fell through to a fresh "
                     "XLA compile").inc()

    def record_cache_store(self, site):
        if enabled():
            self.registry().counter(
                COMPILE_CACHE_STORES,
                help="executables serialized and published to the AOT "
                     "cache (atomic first-wins rename)").inc()

    def record_cache_corrupt(self, site):
        """A truncated/bit-flipped/stale entry failed verification: the
        file was quarantined and the caller recompiles — corruption
        costs one compile, never an error or a wrong executable."""
        if enabled():
            self.registry().counter(
                COMPILE_CACHE_CORRUPT_TOTAL, flight=True,
                help="AOT-cache entries that failed sha256/format/load "
                     "verification (quarantined, recompiled)").inc()
            from .flight import flight
            flight().record("event", "compile_cache_corrupt",
                            site=site.name)

    def check_hbm_budget(self, site, memory):
        """Pre-flight footprint gate, called after compile and BEFORE
        the first dispatch of a new executable."""
        if not memory:
            return
        budget, policy = hbm_budget_bytes()
        if budget is None or memory["hbm_bytes"] <= budget:
            return
        msg = ("executable at site %r needs %.3f GB of device memory "
               "(args %.3f + out %.3f - aliased %.3f + temp %.3f + code "
               "%.3f) but MXNET_HBM_BUDGET_GB=%.3f (%s)"
               % (site.name, memory["hbm_bytes"] / 1024.0 ** 3,
                  memory["argument_bytes"] / 1024.0 ** 3,
                  memory["output_bytes"] / 1024.0 ** 3,
                  memory["alias_bytes"] / 1024.0 ** 3,
                  memory["temp_bytes"] / 1024.0 ** 3,
                  memory["code_bytes"] / 1024.0 ** 3,
                  budget / 1024.0 ** 3, policy))
        if enabled():
            from .flight import flight
            flight().record("event", "hbm_budget_overrun", site=site.name,
                            hbm_bytes=memory["hbm_bytes"])
        if policy == "raise":
            raise HbmBudgetExceeded(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    # -- reading ------------------------------------------------------------
    def events(self, site=None):
        with self._lock:
            out = list(self._events)
        if site is not None:
            out = [e for e in out if e["site"] == site]
        return out

    def mark(self):
        """Opaque marker for `since()` — bench.py brackets one config."""
        with self._lock:
            return self._seq

    def since(self, mark):
        """(compile seconds, peak executable hbm_bytes or None) over the
        events recorded after `mark`."""
        evs = [e for e in self.events() if e["seq"] > mark]
        seconds = sum(e["seconds"] for e in evs)
        peaks = [e["hbm_bytes"] for e in evs if e.get("hbm_bytes")]
        return seconds, (max(peaks) if peaks else None)


#: per-thread count of compiles PAID by this thread's dispatches — the
#: attribution seam for callers (the serving engine) that share
#: instrumented jits across instances: bracket your own call with
#: `dispatch_mark()`/`dispatch_compiles_since()` and you count exactly
#: the compilations your call triggered, never a sibling's on another
#: thread (when two threads race to compile one signature, only the
#: winner's count advances — the loser dispatched a cached executable)
_dispatch_tls = threading.local()


def dispatch_mark():
    """Opaque marker for `dispatch_compiles_since` (thread-local)."""
    return getattr(_dispatch_tls, "count", 0)


def dispatch_compiles_since(mark):
    """Compiles this thread paid inside instrumented-jit dispatches
    since `mark` (survives MXNET_TELEMETRY=0: attribution is behavior,
    not telemetry)."""
    return getattr(_dispatch_tls, "count", 0) - mark


def dispatch_warm_mark():
    """Opaque marker for `dispatch_warm_loads_since` (thread-local):
    executables this thread warm-loaded from the AOT cache instead of
    compiling — the counterpart attribution seam to `dispatch_mark`."""
    return getattr(_dispatch_tls, "warm", 0)


def dispatch_warm_loads_since(mark):
    """Warm AOT-cache loads this thread's dispatches performed since
    `mark` (like compiles, attribution is behavior, not telemetry)."""
    return getattr(_dispatch_tls, "warm", 0) - mark


_watchdog = None
_watchdog_lock = threading.Lock()


def watchdog():
    """The process-wide watchdog (created on first use)."""
    global _watchdog
    if _watchdog is None:
        with _watchdog_lock:
            if _watchdog is None:
                _watchdog = Watchdog()
    return _watchdog


def reset():
    """Drop all sites/events (tests). Instances created before the reset
    keep recording into the OLD watchdog's sites."""
    global _watchdog
    with _watchdog_lock:
        _watchdog = None


# ---------------------------------------------------------------------------
# the instrumented jit wrapper
# ---------------------------------------------------------------------------


class InstrumentedJit:
    """Owns a jitted callable's executable cache so compiles are explicit.

    `owned=True` (default): a new signature triggers `lower().compile()`
    — the compile is timed WITHOUT the first execution, the executable's
    memory/cost analyses are pulled, the HBM pre-flight check runs, and
    subsequent same-signature calls dispatch the cached executable
    directly. `owned=False` observes a callable the wrapper can't AOT
    (e.g. a deserialized `jax.export` artifact): a first-seen signature
    is timed as compile+run (disclosed on the event) and no memory
    analysis is available.

    `.lower` and `.__wrapped__` delegate to the underlying jit, so AOT
    consumers (bench cost probes, bytes reports, `export_train_step`)
    keep working on the wrapped object.

    Dispatch cost: owning the cache means recomputing the signature on
    every call — O(leaves) Python work (measured ~0.3 ms for a 160-leaf
    train step, ~25 us for a 2-arg serving step, with the sharding/dtype
    rendering memoized). That is host-side work a real device step
    overlaps; the alternative (let jax dispatch and observe), would lose
    the pre-flight HBM gate (which must run BEFORE the first dispatch)
    and compile timing isolated from the first execution.

    Per-instance `compiles` / `compiles_by_phase` are the FUNCTIONAL
    counters (the serving engine's `prefill_compilations` /
    `decode_compilations` read them); they advance regardless of
    `MXNET_TELEMETRY` — only the recording is telemetry.
    """

    def __init__(self, jitted, site, argnames=None, phase=None,
                 owned=True, static_argnums=(), variant=None):
        self._jitted = jitted
        self._site = watchdog().site(site)
        self._argnames = tuple(argnames) if argnames else None
        self._phase = phase
        self._owned = owned
        # AOT-cache variant tag: two jits can share one site AND one
        # signature (the gather and paged decode steps do) — the tag,
        # with the lowered-text hash, keeps their disk entries apart
        self._variant = variant
        # a lowered executable takes only the DYNAMIC arguments; static
        # ones (part of the signature, so part of the cache key) must be
        # stripped at dispatch
        self._static = frozenset(static_argnums)
        self._compiled = {}            # sig -> executable (or jitted)
        # RLock: _compile_and_call runs UNDER it (two serving threads
        # sharing one adapter must not both pay the same XLA compile —
        # plain jax.jit was internally thread-safe here) and
        # _record_instance_compile re-enters it
        self._lock = threading.RLock()
        self.compiles = 0
        self.compiles_by_phase = {}
        # warm loads are counted APART from compiles: the engine's
        # recompile-bound tests (<=2 prefill / <=6 decode) stay
        # meaningful with the cache on, and `warm_loads` is the
        # restart-MTTR signal (how much XLA work the cache absorbed)
        self.warm_loads = 0
        self.warm_loads_by_phase = {}

    @property
    def site(self):
        return self._site.name

    @property
    def __wrapped__(self):
        return self._jitted.__wrapped__

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def _cache_size(self):
        """Distinct executables this instance holds (mirrors jax's
        `jit._cache_size`, which the wrapper replaces as cache owner)."""
        return len(self._compiled)

    def _record_instance_compile(self, phase):
        _dispatch_tls.count = getattr(_dispatch_tls, "count", 0) + 1
        with self._lock:
            self.compiles += 1
            if phase:
                self.compiles_by_phase[phase] = \
                    self.compiles_by_phase.get(phase, 0) + 1

    def _record_instance_warm_load(self, phase):
        _dispatch_tls.warm = getattr(_dispatch_tls, "warm", 0) + 1
        with self._lock:
            self.warm_loads += 1
            if phase:
                self.warm_loads_by_phase[phase] = \
                    self.warm_loads_by_phase.get(phase, 0) + 1

    def _dynamic(self, args):
        if not self._static:
            return args
        return tuple(a for i, a in enumerate(args) if i not in self._static)

    def __call__(self, *args, _phase=None):
        sig = signature(args)
        entry = self._compiled.get(sig)
        if entry is None:
            with self._lock:
                entry = self._compiled.get(sig)     # racing thread won?
                if entry is None:
                    if not self._owned:
                        # can't AOT: timed WITH the first execution,
                        # which therefore stays under the lock
                        return self._observe_first_call(sig, args,
                                                        _phase
                                                        or self._phase)
                    entry = self._compile(sig, args,
                                          _phase or self._phase)
            # the fresh executable's FIRST run happens outside the
            # lock — other signatures' compiles must not queue behind
            # this one's execution
        # an unowned entry is the jit itself: it takes every arg
        return entry(*(self._dynamic(args) if self._owned else args))

    def _diff_and_gate(self, wd, sig, gate=True):
        site = self._site
        with wd._lock:
            duplicate = sig in site.signatures
            cached = tuple(site.signatures)
        reason = ("signature already compiled in this process — cold "
                  "executable cache (engine restart / new instance)"
                  if duplicate
                  else diff_reason(self._argnames, cached, sig))
        if gate:
            wd.check_budget(site)
        return duplicate, reason

    # -- persistent AOT cache hooks (ISSUE 16) ------------------------------
    def _cache_key(self, site, sig, args):
        """(cache, key, lowered) for this call, or (None, None, None)
        when caching is off or this program can't be content-keyed (no
        deterministic lowered text) — an unkeyable program is simply
        never cached, it cannot hit a wrong entry."""
        if not self._owned:
            return None, None, None
        from .. import aot
        c = aot.cache()
        if c is None:
            return None, None, None
        try:
            lowered = self._jitted.lower(*args)
            text = lowered.as_text()
        except Exception:
            return None, None, None
        if not text:
            return None, None, None
        try:
            key = aot.key_for(site.name, sig, text,
                              variant=self._variant,
                              placement=aot.placement_key(args))
        except Exception:
            return None, None, None
        return c, key, lowered

    def _cache_load(self, wd, cache, site, key, sig, phase):
        """Warm-load one verified entry: corrupt/stale/undeserializable
        entries are quarantined and read as a miss (NEVER an error —
        the cache switches where the executable comes from, not what it
        computes)."""
        from .. import aot
        t0 = time.perf_counter()
        try:
            rec = cache.load(site.sane, key)
        except aot.CorruptEntry:
            wd.record_cache_corrupt(site)
            rec = None
        if rec is None:
            wd.record_cache_miss(site)
            return None
        payload, in_tree, out_tree, meta = rec
        try:
            compiled = aot.load_executable(payload, in_tree, out_tree,
                                           meta["devices"])
        except Exception:
            cache.invalidate(site.sane, key)
            wd.record_cache_corrupt(site)
            wd.record_cache_miss(site)
            return None
        wd.record_cache_hit(site, sig, time.perf_counter() - t0,
                            phase=phase)
        self._record_instance_warm_load(phase)
        # the stored memory analysis re-arms the HBM pre-flight: a warm
        # load must refuse an over-budget executable exactly like the
        # compile that produced it did
        return self._gate_entry(wd, site, sig, compiled,
                                meta.get("memory"))

    def _cache_store(self, wd, cache, site, key, compiled, memory):
        try:
            from .. import aot
            payload, trees, devices = \
                aot.serialize_executable_blob(compiled)
            if cache.store(site.sane, key, payload, trees,
                           extra={"watchdog_site": site.name,
                                  "variant": self._variant,
                                  "memory": memory,
                                  "devices": devices}):
                wd.record_cache_store(site)
        except Exception:
            # persistence must never break the serving/train path: an
            # unserializable executable just stays process-local
            pass

    def _gate_entry(self, wd, site, sig, compiled, memory):
        """HBM pre-flight + executable-cache insert, shared by the
        fresh-compile and warm-load paths."""
        try:
            # pre-flight: refuse (or warn about) an over-budget
            # executable BEFORE its first dispatch
            wd.check_hbm_budget(site, memory)
        except HbmBudgetExceeded:
            # cache a re-checking refuser, not nothing: a same-sig retry
            # must neither pay the compile again nor read as a
            # `duplicate` (the engine-restart signal) — and a budget
            # lifted live re-admits the already-built executable
            def entry(*dyn, _c=compiled, _m=memory, _s=site, _sig=sig):
                wd.check_hbm_budget(_s, _m)          # still over: raises
                self._compiled[_sig] = _c            # budget lifted
                return _c(*dyn)
        else:
            entry = compiled
        self._compiled[sig] = entry
        return entry

    def _compile(self, sig, args, phase):
        # caller holds self._lock: one compile per signature, fleet-wide
        wd = watchdog()
        site = self._site
        duplicate, reason = self._diff_and_gate(wd, sig, gate=False)
        cache, key, lowered = self._cache_key(site, sig, args)
        if cache is not None:
            entry = self._cache_load(wd, cache, site, key, sig, phase)
            if entry is not None:
                return entry
        # the compile budget gates only REAL compiles: a warm load
        # costs no XLA work, so it must neither consume
        # MXNET_COMPILE_BUDGET nor trip it
        wd.check_budget(site)
        t0_us = time.perf_counter_ns() // 1000
        t0 = time.perf_counter()
        if lowered is None:
            lowered = self._jitted.lower(*args)
        compiled = lowered.compile()
        seconds = time.perf_counter() - t0
        memory, flops, bytes_accessed = _analyses(compiled)
        # the ledger walk is pure telemetry (an HLO-text pass per
        # compile); under MXNET_TELEMETRY=0 it never runs
        comms = comms_ledger(compiled, bytes_accessed) if enabled() \
            else None
        wd.record(site, sig, reason, seconds, phase=phase,
                  memory=memory, flops=flops, duplicate=duplicate,
                  start_us=t0_us, comms=comms)
        self._record_instance_compile(phase)
        if cache is not None:
            self._cache_store(wd, cache, site, key, compiled, memory)
        return self._gate_entry(wd, site, sig, compiled, memory)

    def _observe_first_call(self, sig, args, phase):
        wd = watchdog()
        duplicate, reason = self._diff_and_gate(wd, sig)
        t0_us = time.perf_counter_ns() // 1000
        t0 = time.perf_counter()
        out = self._jitted(*args)
        wd.record(self._site, sig,
                  reason + " (timed with first execution)",
                  time.perf_counter() - t0, phase=phase,
                  duplicate=duplicate, start_us=t0_us)
        self._record_instance_compile(phase)
        self._compiled[sig] = self._jitted
        return out


def instrument(jitted, site, argnames=None, phase=None, owned=True,
               static_argnums=(), variant=None):
    """Register a jitted callable at a watchdog site. The one-line seam
    every framework jit entry point goes through. `static_argnums` must
    restate the jit's own (jax doesn't expose them on the jitted
    object): the lowered executable takes only the dynamic arguments.
    `variant` tags this instance's entries in the persistent AOT cache
    (mxnet_tpu/aot) — required disambiguation when two different jits
    register at one site and can trace identical signatures."""
    return InstrumentedJit(jitted, site, argnames=argnames, phase=phase,
                           owned=owned, static_argnums=static_argnums,
                           variant=variant)


@contextlib.contextmanager
def compile_region(site, phase=None, **attrs):
    """Time an explicit whole-compile region (jax.export in
    `predict.export_model` / `export_train_step`) as one watchdog
    compile event — no signature cache, every entry is a compile."""
    wd = watchdog()
    s = wd.site(site)
    wd.check_budget(s)
    t0_us = time.perf_counter_ns() // 1000
    t0 = time.perf_counter()
    # no try/finally: a region that RAISES produced no executable, so
    # recording it would masquerade the failure as a normal compile
    # (and bench's compile_s would absorb the aborted attempt's wall
    # time); the exception itself is the loud signal
    yield
    wd.record(s, None,
              "explicit compile region%s"
              % (" (%s)" % ", ".join("%s=%s" % kv
                                     for kv in sorted(attrs.items()))
                 if attrs else ""),
              time.perf_counter() - t0, phase=phase, start_us=t0_us)


def compile_events(site=None):
    """Recorded compile events, oldest first (`site=` filters)."""
    return watchdog().events(site)


def site_comms(site):
    """The latest compiled executable's collective-comms ledger at a
    site — {"kinds": {kind: {"bytes", "ops"}}, "total_bytes",
    "bytes_accessed", "fraction"} — or None before the first compile
    there (or under MXNET_TELEMETRY=0, where the HLO walk never runs)."""
    s = watchdog().sites().get(site)
    return s.comms if s is not None else None
