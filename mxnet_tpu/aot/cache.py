"""Persistent AOT executable cache (ISSUE 16).

The compile watchdog (telemetry/introspect.py) already funnels every
framework jit through ONE `lower().compile()` choke point; this module
makes that choke point durable. A compiled executable is serialized via
jax's AOT serialization (`jax.experimental.serialize_executable` — the
same compile-once idea `predict.py`'s `.mxtpu` artifacts prove for
exported models) and published to a disk directory keyed by a content
hash of everything that determines the program:

  * the environment **fingerprint**: jax / jaxlib / framework versions,
    backend platform, device kind and count, compiler-flag env
    (`XLA_FLAGS`, `LIBTPU_INIT_ARGS`) and the lowering-relevant
    `MXNET_*` env vars;
  * the watchdog **site** and the traced **signature** (shapes, dtypes,
    shardings, static values — exactly the watchdog's cache key);
  * the **placement**: the sorted device ids the call's committed
    arguments live on (two tp replicas on different device windows
    compile different programs from identical shapes — the identity-free
    sharding description deliberately can't tell them apart, this can);
  * an explicit **variant** tag from the instrument site (the gather and
    paged decode jits share one site and can share a signature — the
    tag plus the lowered-text hash below make a wrong-executable hit
    structurally impossible);
  * the sha256 of the deterministic **lowered StableHLO text** — the
    program's actual content, the belt under every brace above.

Entries are single zip files published by atomic rename (first writer
wins, a racing loser discards its temp file and reuses the published
entry), with sha256 digests over the payload verified on every load.
A corrupt, truncated, or stale entry is NEVER an error: the loader
quarantines it and the caller falls back to a fresh compile — the cache
switches where an executable comes from, never what it computes.

Surface: `MXNET_AOT_CACHE_DIR` env, `configure(path)` (what
`Engine(aot_cache=...)` and `serve --aot-cache` call), and
`tools/aot_warm.py` for pre-populating/verifying a directory.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import threading
import time
import zipfile

from ..base import MXNetError

#: entry format version — bumped on any layout change (old entries then
#: fail the meta check and are recompiled, never misread)
FORMAT = 2

#: entry file suffix (one zip per executable)
SUFFIX = ".mxaot"

#: env vars that change what XLA is asked to build — part of the key's
#: environment fingerprint (flags switch placement/codegen, never logits,
#: so a mismatch is a MISS, not an error)
_FLAG_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "JAX_ENABLE_X64",
             "MXNET_PAGED_ATTENTION", "MXNET_PALLAS_INTERPRET",
             "MXNET_SERVING_TP", "MXNET_QUANTIZED_KV",
             "MXNET_QUANTIZED_WEIGHTS")


class CorruptEntry(MXNetError):
    """A cache entry failed its sha256 / format verification (truncated
    write, bit flip, stale layout). The loader quarantines the file and
    the caller recompiles — corruption costs a compile, never an error
    or a wrong executable."""


def fingerprint():
    """The environment part of every cache key: anything here changing
    invalidates the whole cache (by missing, not by erroring)."""
    import jax
    fp = {"jax": getattr(jax, "__version__", "?")}
    try:
        import jaxlib
        fp["jaxlib"] = getattr(jaxlib, "__version__", "?")
    except Exception:                                    # pragma: no cover
        fp["jaxlib"] = "?"
    try:
        from ..libinfo import __version__ as fw
        fp["framework"] = fw
    except Exception:                                    # pragma: no cover
        fp["framework"] = "?"
    try:
        devs = jax.devices()
        fp["platform"] = devs[0].platform
        fp["device_kind"] = devs[0].device_kind
        fp["device_count"] = len(devs)
    except Exception:                                    # pragma: no cover
        fp["platform"] = fp["device_kind"] = "?"
        fp["device_count"] = 0
    fp["env"] = {k: os.environ.get(k, "") for k in _FLAG_ENV}
    return fp


def placement_key(args):
    """Sorted device ids the call's COMMITTED argument leaves live on.
    Host/uncommitted inputs contribute nothing; a call with no committed
    leaf keys on the default device (where it will execute). This is
    what distinguishes two tp replicas' device windows — their shapes,
    dtypes, and identity-free sharding descriptions are all equal."""
    import jax
    ids = set()
    for leaf in jax.tree.leaves(args):
        s = getattr(leaf, "sharding", None)
        if s is None or not getattr(leaf, "_committed", True):
            continue
        try:
            ids.update(d.id for d in s.device_set)
        except Exception:                                # pragma: no cover
            pass
    if not ids:
        try:
            ids = {jax.devices()[0].id}
        except Exception:                                # pragma: no cover
            return ()
    return tuple(sorted(ids))


def key_for(site, sig, lowered_text, variant=None, placement=(),
            fp=None):
    """The content-hash key of one executable. Any component changing —
    version, device topology, signature/sharding, compiler flags, the
    lowered program itself — produces a different key, so staleness is
    structurally a MISS: the cache can serve the wrong-vintage
    executable only if sha256 collides."""
    fp = fingerprint() if fp is None else fp
    h = hashlib.sha256()
    h.update(json.dumps(fp, sort_keys=True).encode())
    h.update(b"\x00site:" + site.encode())
    h.update(b"\x00variant:" + repr(variant).encode())
    h.update(b"\x00placement:" + repr(tuple(placement)).encode())
    h.update(b"\x00sig:" + repr(sig).encode())
    h.update(b"\x00hlo:")
    h.update(hashlib.sha256(lowered_text.encode()).digest())
    return h.hexdigest()[:40]


# ---------------------------------------------------------------------------
# executable (de)serialization
# ---------------------------------------------------------------------------


def serialize_executable_blob(compiled):
    """(payload bytes, pickled (in_tree, out_tree), device ids) for a
    compiled executable. The device ids are the executable's own, in
    assignment order: `load_executable` must be handed them back."""
    from jax.experimental.serialize_executable import serialize
    payload, in_tree, out_tree = serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return bytes(payload), pickle.dumps((in_tree, out_tree)), ids


def load_executable(payload, in_tree, out_tree, device_ids):
    """Rehydrate a serialized executable into a callable taking the
    original dynamic arguments — zero XLA compilation. It is loaded onto
    the devices it was compiled for (`device_ids`): left to its default,
    jax loads onto every device of the backend and a one-device
    executable then expects one shard per device."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# ---------------------------------------------------------------------------
# the on-disk cache
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def atomic_publish(path):
    """Write-to-temp + atomic-rename publish: yields the temp path to
    write, renames over `path` on success, removes the temp on failure.
    Readers never observe a half-written file (predict.py's artifact
    writers share this)."""
    tmp = "%s.tmp.%d.%x" % (path, os.getpid(), threading.get_ident())
    try:
        yield tmp
        os.replace(tmp, path)
    finally:
        with contextlib.suppress(OSError):
            os.remove(tmp)


class AOTCache:
    """One cache directory: load / store / verify over `.mxaot` entry
    zips. Thread- and process-safe by construction — every publish is
    an atomic rename and every load verifies digests, so concurrent
    writers and readers need no locks."""

    def __init__(self, path):
        self.path = str(path)
        os.makedirs(self.path, exist_ok=True)

    def entry_path(self, site_sane, key):
        return os.path.join(self.path, "%s-%s%s" % (site_sane, key,
                                                    SUFFIX))

    # -- store ---------------------------------------------------------------

    def store(self, site_sane, key, payload, trees, extra=None):
        """Publish one entry. First writer wins: if the entry already
        exists (another replica/process got there first) nothing is
        written and False is returned — the loser simply reuses the
        published copy on its next load."""
        final = self.entry_path(site_sane, key)
        if os.path.exists(final):
            return False
        meta = {"format": FORMAT, "key": key, "site": site_sane,
                "payload_sha256": hashlib.sha256(payload).hexdigest(),
                "trees_sha256": hashlib.sha256(trees).hexdigest(),
                "created": time.time()}
        if extra:
            meta.update(extra)
        tmp = "%s.tmp.%d.%x" % (final, os.getpid(),
                                threading.get_ident())
        try:
            with zipfile.ZipFile(tmp, "w") as z:
                z.writestr("meta.json", json.dumps(meta))
                z.writestr("payload.bin", payload)
                z.writestr("trees.pkl", trees)
            if os.path.exists(final):        # lost the race mid-write
                return False
            os.replace(tmp, final)
            return True
        finally:
            with contextlib.suppress(OSError):
                os.remove(tmp)

    # -- load ----------------------------------------------------------------

    def load(self, site_sane, key):
        """(payload, in_tree, out_tree, meta) for a verified entry, None
        on a miss, CorruptEntry (after quarantining the file) on any
        verification failure — the caller recompiles either way."""
        path = self.entry_path(site_sane, key)
        if not os.path.exists(path):
            return None
        try:
            with zipfile.ZipFile(path) as z:
                meta = json.loads(z.read("meta.json"))
                payload = z.read("payload.bin")
                trees = z.read("trees.pkl")
        except Exception as e:
            self._quarantine(path)
            raise CorruptEntry("unreadable cache entry %s: %s"
                               % (os.path.basename(path), e))
        if meta.get("format") != FORMAT \
                or meta.get("payload_sha256") \
                != hashlib.sha256(payload).hexdigest() \
                or meta.get("trees_sha256") \
                != hashlib.sha256(trees).hexdigest():
            self._quarantine(path)
            raise CorruptEntry("cache entry %s failed sha256/format "
                               "verification"
                               % os.path.basename(path))
        try:
            in_tree, out_tree = pickle.loads(trees)
        except Exception as e:
            self._quarantine(path)
            raise CorruptEntry("cache entry %s has undecodable trees: %s"
                               % (os.path.basename(path), e))
        return payload, in_tree, out_tree, meta

    def invalidate(self, site_sane, key):
        """Quarantine one entry whose payload deserialized but failed to
        load as an executable (a hash-valid but unusable vintage)."""
        self._quarantine(self.entry_path(site_sane, key))

    def _quarantine(self, path):
        with contextlib.suppress(OSError):
            os.remove(path)

    # -- inventory -----------------------------------------------------------

    def entries(self):
        """Sorted entry file names currently published."""
        try:
            names = os.listdir(self.path)
        except OSError:
            return []
        return sorted(n for n in names if n.endswith(SUFFIX))

    def verify(self):
        """Non-destructive re-hash of every entry: (ok names, corrupt
        names). `tools/aot_warm.py --verify` renders this."""
        ok, bad = [], []
        for name in self.entries():
            path = os.path.join(self.path, name)
            try:
                with zipfile.ZipFile(path) as z:
                    meta = json.loads(z.read("meta.json"))
                    payload = z.read("payload.bin")
                    trees = z.read("trees.pkl")
                good = (meta.get("format") == FORMAT
                        and meta.get("payload_sha256")
                        == hashlib.sha256(payload).hexdigest()
                        and meta.get("trees_sha256")
                        == hashlib.sha256(trees).hexdigest())
            except Exception:
                good = False
            (ok if good else bad).append(name)
        return ok, bad


# ---------------------------------------------------------------------------
# process-wide configuration: configure() override > MXNET_AOT_CACHE_DIR
# ---------------------------------------------------------------------------

_ENV = object()          # sentinel: defer to the env var
_override = _ENV
_cache_lock = threading.Lock()
_caches = {}             # dir -> AOTCache (memoized: makedirs once)


def configure(path=_ENV):
    """Set the process-wide cache directory (`Engine(aot_cache=...)` /
    `serve --aot-cache` land here). `None` disables caching regardless
    of the env var; calling with no argument restores env-var control
    (MXNET_AOT_CACHE_DIR)."""
    global _override
    _override = str(path) if path not in (None, _ENV) else path


def cache_dir():
    """The resolved cache directory, or None when caching is off."""
    if _override is not _ENV:
        return _override
    return os.environ.get("MXNET_AOT_CACHE_DIR") or None


def cache():
    """The process-wide AOTCache, or None when caching is off (no dir
    configured)."""
    d = cache_dir()
    if not d:
        return None
    with _cache_lock:
        c = _caches.get(d)
        if c is None:
            try:
                c = _caches[d] = AOTCache(d)
            except OSError:
                return None
        return c
