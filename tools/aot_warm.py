#!/usr/bin/env python
"""Pre-populate (or verify) a persistent AOT executable cache.

A serving fleet with MXNET_AOT_CACHE_DIR set warm-loads its compiled
prefill/decode executables from disk instead of paying XLA at startup —
but somebody has to pay the FIRST compile. This tool pays it offline:
it builds one engine with the exact serving flags (paged/tp/block-size/
max-batch/prefill-chunk are all part of the cache key — a warmer run
with different flags warms nothing) and drives it across the shape
lattice serving will hit: one prefill per prompt-length bucket, one
decode step per power-of-two batch bucket. Every executable compiled is
published to the cache; a later `serve.py --aot-cache DIR` (or a
scale-up/respawn inside an autoscaled fleet) then starts with zero
fresh compiles and bit-identical logits.

    python tools/aot_warm.py --cache /var/cache/mxtpu --demo --paged
    python tools/aot_warm.py --cache /var/cache/mxtpu --model lm.mxtpu \
        --max-batch 8 --block-size 16
    python tools/aot_warm.py --cache /var/cache/mxtpu --verify
    python tools/aot_warm.py --cache /var/cache/mxtpu --purge

`--verify` integrity-checks every entry (sha256 over the serialized
executable, format, readability) without loading any onto a device;
exit status 1 when any entry is corrupt. The supervised-relaunch loop
(tools/train_supervise.py --prewarm-cmd) can run this tool before each
incarnation so a crashed trainer restarts warm.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _buckets(spec, hi):
    out = []
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        n = int(tok)
        if n > 0 and n <= hi and n not in out:
            out.append(n)
    return out or [min(8, hi)]


def _batch_lattice(max_batch):
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


def warm(args):
    from mxnet_tpu import serving

    if args.demo:
        import jax
        from mxnet_tpu.models.transformer import (TransformerConfig,
                                                  init_transformer_params)
        cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                n_layers=2, d_ff=128, max_len=128)
        params = init_transformer_params(jax.random.PRNGKey(0), cfg)
        adapter = serving.TransformerLM(params, cfg)
    elif args.model:
        adapter = serving.ExportedLM(args.model)
    else:
        raise SystemExit("pass --model artifact.mxtpu or --demo "
                         "(or --verify/--purge)")

    eng = serving.Engine(adapter, max_batch=args.max_batch,
                         block_size=args.block_size,
                         paged=args.paged,
                         prefill_chunk=args.prefill_chunk,
                         tp=args.tp,
                         aot_cache=args.cache)
    if eng.aot_cache is None:
        raise SystemExit("no cache directory (pass --cache or set "
                         "MXNET_AOT_CACHE_DIR)")
    max_len = getattr(adapter, "max_len", None) or 128
    lens = _buckets(args.prompt_buckets, max(1, max_len - 2))
    print("warming %s: paged=%s tp=%s max_batch=%d block_size=%d "
          "prompt buckets %s, batch lattice %s"
          % (eng.aot_cache, "on" if eng.paged else "off",
             args.tp or 1, args.max_batch, args.block_size,
             lens, _batch_lattice(args.max_batch)))
    # one prefill per prompt-length bucket, one decode per batch bucket
    for bs in _batch_lattice(args.max_batch):
        for plen in lens:
            seqs = [eng.start([(i + t) % 32 + 1 for t in range(plen)],
                              max_new=2)
                    for i in range(bs)]
            eng.decode_step(seqs)
            for s in seqs:
                eng.release(s)
    cache = _cache(args)
    n = len(cache.entries()) if cache is not None else 0
    print("done: %d compile(s), %d warm load(s), %d cache entr%s"
          % (eng.prefill_compilations + eng.decode_compilations,
             eng.warm_loads, n, "y" if n == 1 else "ies"))
    try:
        eng.close()
    except Exception:
        pass
    return 0


def _cache(args):
    from mxnet_tpu import aot
    cdir = args.cache or aot.cache_dir()
    return aot.AOTCache(cdir) if cdir else None


def verify(args):
    cache = _cache(args)
    if cache is None:
        raise SystemExit("no cache directory (pass --cache or set "
                         "MXNET_AOT_CACHE_DIR)")
    ok, bad = cache.verify()
    print("verified %s: %d ok, %d corrupt"
          % (cache.path, len(ok), len(bad)))
    for name in bad:
        print("  CORRUPT %s" % name)
    return 1 if bad else 0


def purge(args):
    cache = _cache(args)
    if cache is None:
        raise SystemExit("no cache directory (pass --cache or set "
                         "MXNET_AOT_CACHE_DIR)")
    names = cache.entries()
    for name in names:
        try:
            os.remove(os.path.join(cache.path, name))
        except OSError:
            pass
    print("purged %d entr%s from %s"
          % (len(names), "y" if len(names) == 1 else "ies", cache.path))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cache", default=None, metavar="DIR",
                    help="AOT cache directory (default: "
                         "MXNET_AOT_CACHE_DIR)")
    ap.add_argument("--model", default=None,
                    help=".mxtpu artifact from predict.export_model")
    ap.add_argument("--demo", action="store_true",
                    help="warm for the tools/serve.py --demo model")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--paged", action="store_true", default=None,
                    help="warm the paged-attention decode path "
                         "(default: MXNET_PAGED_ATTENTION)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill chunk length (paged path)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree (default: "
                         "MXNET_SERVING_TP or 1)")
    ap.add_argument("--prompt-buckets", default="4,8,16,32",
                    metavar="L1,L2,...",
                    help="prompt-length buckets to prefill-warm "
                         "(default 4,8,16,32; clipped to the model's "
                         "max_len)")
    ap.add_argument("--verify", action="store_true",
                    help="integrity-check every cache entry instead of "
                         "warming; exit 1 on any corrupt entry")
    ap.add_argument("--purge", action="store_true",
                    help="delete every cache entry, then exit")
    args = ap.parse_args(argv)
    if args.verify:
        return verify(args)
    if args.purge:
        return purge(args)
    return warm(args)


if __name__ == "__main__":
    sys.exit(main())
