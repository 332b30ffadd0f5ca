"""mxnet_tpu.serving — continuous-batching LM inference.

The reference's serving story was the one-shot c_predict_api
(Predictor.set_input/forward/get_output). This subsystem is the
production-shape replacement for autoregressive models: a paged KV-cache
(fixed-shape block pools, jit-stable decode), a prefill/decode engine
with bucketed shapes — and, under `MXNET_PAGED_ATTENTION=1`, a ragged
paged-attention Pallas kernel that reads the cache in place plus
chunked prefill (ops/pallas_paged.py) — a continuous-batching scheduler
with backpressure, a per-iteration token budget, priority classes and
per-tenant token budgets, a content-addressed prefix cache
(`MXNET_PREFIX_CACHE=1`, prefix_cache.py: shared prompt prefixes hit
resident refcounted blocks, copy-on-write on divergence, LRU eviction),
serving metrics, draft-model speculative decoding through the paged
engine (`MXNET_SPEC_DECODE=1`, spec.py: a small draft proposes k tokens,
the target scores all k+1 positions in one ragged paged pass, greedy
verification keeps the output token-identical to the non-speculative
path), and an in-process `serve()` API with a stdlib HTTP frontend
(tools/serve.py).

Quickstart::

    from mxnet_tpu import serving
    srv = serving.serve((params, cfg), max_batch=8)   # or "model.mxtpu"
    out = srv.generate([1, 2, 3], max_new_tokens=16)
    print(out, srv.snapshot()["throughput"])
    srv.close()
"""
from .kv_cache import BlockPool, PagedKVCache, CacheOverflow
from .prefix_cache import PrefixCache, prefix_cache_enabled
from .engine import (Engine, Sequence, TransformerLM, BlockLM, ExportedLM,
                     PoolsLost, pow2_bucket)
from .latent_lm import LatentMoELM
from .afmoe_lm import AfmoeLM
from .falcon_h1_lm import FalconH1LM
from .nemotron_h_lm import NemotronHLM
from .scheduler import (Scheduler, Request, QueueFull, RequestTimeout,
                        DeadlineExceeded, DeadlineUnmeetable,
                        BrownoutShed, make_resume)
from .metrics import ServingMetrics
from .server import LMServer, serve, spawn_resume, spawn_migrate
from .router import (ReplicatedLMServer, serving_replicas,
                     serving_respawn_max, serving_roles,
                     NoHealthyReplicas)
from .autoscale import Autoscaler, AutoscaleConfig, autoscale_enabled
from .rollout import (RolloutController, RejectionRoster, rollout_dir,
                      rollout_stages, rollout_window_s,
                      rollout_parity_prompts)
from .tp import serving_tp, tp_cache_variant
from .spec import (DraftLM, self_draft, spec_decode_enabled, spec_k,
                   spec_draft_layers)

__all__ = [
    "BlockPool", "PagedKVCache", "CacheOverflow",
    "PrefixCache", "prefix_cache_enabled",
    "Engine", "Sequence", "TransformerLM", "LatentMoELM", "AfmoeLM",
    "FalconH1LM", "NemotronHLM", "BlockLM", "ExportedLM",
    "PoolsLost", "pow2_bucket",
    "Scheduler", "Request", "QueueFull", "RequestTimeout",
    "DeadlineExceeded", "DeadlineUnmeetable", "BrownoutShed",
    "make_resume", "spawn_resume", "spawn_migrate",
    "ServingMetrics", "LMServer", "serve",
    "ReplicatedLMServer", "serving_replicas", "serving_respawn_max",
    "serving_roles",
    "serving_tp", "tp_cache_variant", "NoHealthyReplicas",
    "Autoscaler", "AutoscaleConfig", "autoscale_enabled",
    "RolloutController", "RejectionRoster", "rollout_dir",
    "rollout_stages", "rollout_window_s", "rollout_parity_prompts",
    "DraftLM", "self_draft", "spec_decode_enabled", "spec_k",
    "spec_draft_layers",
]
