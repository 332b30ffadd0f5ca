"""Family `afmoe_lm`: a language model whose layers attend over a window or
over everything, grouped-query and gated, with dropless sparse experts; one
chip's share of an expert-parallel deployment, served through
`mxnet_tpu.serving.serve` like the other two language-model families.

The benchmark makes the weights a leaf at a time on the device, from the
seed, in the dtype they are served in and in the layout of
`chipbench/reference/afmoe_lm.py`; the program takes the same arrays under
`layer<i>_<leaf>`. After the window a sample of what was served is compared
with the reference's forward over the same weights and the same share of the
experts. The functions under "work from shapes" count what the per-layer
readers divide by: `decode_step_min_bytes` (`decode_hbm_share.swa`),
`prefill_flops` (`prefill_mxu_share`), `decode_keys_walked` and
`decode_keys_live` (`attn_walk_over_live`), `held_over_full`
(`kv_held_over_full`).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.models.afmoe import AfmoeConfig
from mxnet_tpu.models.latent_moe import held_range

from chipbench.families import latent_moe_lm, transformer_lm
from chipbench.harness import util
from chipbench.reference import afmoe_lm as reference

sample_finished = transformer_lm.sample_finished
_itemsize = transformer_lm._itemsize
_normal = latent_moe_lm._normal
_size = latent_moe_lm._size

BLOCK_SIZE = 16               # the server's default, which the cell leaves
WALK_CHUNK_TOKENS = 128       # keys one pass of the decode walk folds in
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_shapes(config, index):
    """{leaf: shape} of layer `index`, matrices only."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wg": (d, q),
              "wo": (q, d)}
    if index < config["num_dense_layers"]:
        f = config["intermediate_size"]
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        f, held = config["moe_intermediate_size"], config["num_experts"]
        fs = f * config["num_shared_experts"]
        shapes.update(router=(d, config["num_experts_published"]),
                      ws_gate=(d, fs), ws_up=(d, fs), ws_down=(fs, d),
                      we_gate=(held, d, f), we_up=(held, d, f),
                      we_down=(held, f, d))
    return shapes


def gain_shapes(config):
    d, dh = config["hidden_size"], config["head_dim"]
    return {"norm_in_g": (d,), "norm_post_attn_g": (d,),
            "norm_pre_mlp_g": (d,), "norm_post_mlp_g": (d,),
            "q_norm_g": (dh,), "k_norm_g": (dh,)}


def make_weights(config, seed):
    """Reference-layout weights on the device, one leaf a call so that no
    more than one float32 leaf lies beside the bf16 ones: N(0, 0.02)
    matrices, gains N(1, 0.1), the selection bias N(0, 0.01) in float32."""
    dtype = jnp.dtype(config["dtype"])
    d, vocab = config["hidden_size"], config["vocab_size"]
    keys = iter(jax.random.split(util.prng_key(seed), 4096))
    matrix = lambda shape: _normal(next(keys), shape, dtype, 0.02, 0.0)
    gain = lambda shape: _normal(next(keys), shape, dtype, 0.1, 1.0)
    weights = {"embed": matrix((vocab, d)), "head": matrix((d, vocab)),
               "normf_g": gain((d,)), "layers": []}
    for i in range(config["num_hidden_layers"]):
        lw = {n: matrix(s) for n, s in sorted(layer_shapes(config, i).items())}
        lw.update((n, gain(s)) for n, s in sorted(gain_shapes(config).items()))
        if "router" in lw:
            lw["router_bias"] = _normal(next(keys), (lw["router"].shape[1],),
                                        jnp.dtype("float32"), 0.01, 0.0)
        weights["layers"].append(lw)
    return weights


def program_params(weights):
    """The same arrays under the names `models/afmoe.py` gives them."""
    p = {k: v for k, v in weights.items() if k != "layers"}
    for i, lw in enumerate(weights["layers"]):
        p.update(("layer%d_%s" % (i, n), a) for n, a in lw.items())
    return p


def program_config(config, max_len):
    return AfmoeConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["num_dense_layers"],
        layer_kinds=tuple(KINDS[t] for t in config["layer_types"]),
        window=config["sliding_window"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["num_shared_experts"],
        n_experts=config["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        route_scale=float(config["route_scale"]),
        experts_held=held_range(config["expert_rank"],
                                config["expert_parallel"],
                                config["num_experts_published"]),
        rope_base=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        scale_embed=bool(config["mup_enabled"]), max_len=max_len,
        dtype=jnp.dtype(config["dtype"]))


# -- work from shapes ---------------------------------------------------------

def layers_by_kind(config):
    """(full layers, window layers)."""
    types = config["layer_types"]
    return types.count("full_attention"), types.count("sliding_attention")


def weight_bytes(config):
    """Every matrix held here: embedding, head and the layers'."""
    total = 2 * config["hidden_size"] * config["vocab_size"]
    for i in range(config["num_hidden_layers"]):
        total += _size(layer_shapes(config, i))
    return total * _itemsize(config)


def kv_bytes_per_token_layer(config):
    """Keys and values of one token in one layer, in the pool's dtype."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * _itemsize(config)


def ring_blocks(config, block_size=BLOCK_SIZE):
    return config["sliding_window"] // block_size + 1


def pool_bytes(config, block_size=BLOCK_SIZE):
    """(the full layers' planes, the window layers' planes) as the engine
    sizes them for the cell's server: max_batch sequences of max_len, every
    block of them or a ring's worth, and the null block."""
    server = config["server"]
    nblk = math.ceil(server["max_len"] / block_size)
    full, window = layers_by_kind(config)
    block = block_size * kv_bytes_per_token_layer(config)
    return (full * (server["max_batch"] * nblk + 1) * block,
            window * (server["max_batch"]
                      * min(nblk, ring_blocks(config, block_size)) + 1) * block)


def expert_bytes(config):
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * _itemsize(config)


def dense_params(config):
    """Parameters of every layer's matrices outside its routed experts."""
    return sum(_size({n: s for n, s in layer_shapes(config, i).items()
                      if not n.startswith("we_")})
               for i in range(config["num_hidden_layers"]))


def dense_bytes_per_step(config):
    """Bytes every decode step reads whatever it routes: each layer's
    matrices outside its routed experts, and the head (the embedding is
    read a row per sequence)."""
    return (dense_params(config)
            + config["hidden_size"] * config["vocab_size"]) * _itemsize(config)


def decode_step_min_bytes(config, live_full, live_window, experts_touched):
    """The least a decode step must read: what every step reads, each held
    expert that got a row (counted over all expert layers) once, and the
    keys and values its sequences hold once: `live_full` tokens on each
    full layer, `live_window` (each sequence capped at the window) on each
    window layer. An expert no row chose is not counted, so this is a lower
    bound."""
    full, window = layers_by_kind(config)
    return dense_bytes_per_step(config) \
        + experts_touched * expert_bytes(config) \
        + (full * live_full + window * live_window) \
        * kv_bytes_per_token_layer(config)


def prefill_flops(config, bucket, pairs=None):
    """Operations a whole-prompt prefill over `bucket` rows needs: the
    matrices outside the routed experts over every row, the routed (row,
    held expert) pairs (`pairs`; by expectation bucket x experts per token x
    held / published where it is not given), and attention inside the
    causal band: query t against min(t + 1, window) keys on a window layer,
    t + 1 on a full one, two products of 2 x heads x head_dim each. The
    head scores one row and is left out."""
    if pairs is None:
        pairs = bucket * config["num_experts_per_tok"] * config["num_experts"] \
            / config["num_experts_published"]
    full, window = layers_by_kind(config)
    t = np.arange(1, bucket + 1, dtype=np.float64)
    keys = full * t.sum() + window * np.minimum(
        t, config["sliding_window"]).sum()
    per_key = 4 * config["num_attention_heads"] * config["head_dim"]
    per_pair = 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return 2.0 * bucket * dense_params(config) + pairs * per_pair \
        + keys * per_key


def decode_keys_walked(config, batch, live_max, block_size=BLOCK_SIZE):
    """Keys the decode step's attention loops visit over all layers: every
    row of the batch's bucket walks as far as the longest live sequence, in
    chunks; on a window layer no further than the ring."""
    rows = 1
    while rows < batch:
        rows *= 2
    chunks = (live_max - 1) // WALK_CHUNK_TOKENS + 1
    ring_chunks = math.ceil(ring_blocks(config, block_size)
                            / (WALK_CHUNK_TOKENS // block_size))
    full, window = layers_by_kind(config)
    return rows * WALK_CHUNK_TOKENS * (full * chunks
                                       + window * min(chunks, ring_chunks))


def decode_keys_live(config, live_full, live_window):
    """Keys those loops had to visit: what the rows really hold."""
    full, window = layers_by_kind(config)
    return full * live_full + window * live_window


def held_over_full(config, blocks_full, blocks_window):
    """Bytes both pools hold (blocks in use, by kind, at one moment) over
    the bytes the same sequences would hold if every layer kept every
    token, %."""
    full, window = layers_by_kind(config)
    return 100.0 * (full * blocks_full + window * blocks_window) \
        / ((full + window) * blocks_full)


class Server(latent_moe_lm.Server):
    def __init__(self, cell, serve_options=None):
        from mxnet_tpu import serving
        cfg = cell.config
        self.cell = cell
        self.options = dict(cfg["server"])
        self.options.update(serve_options or {})
        self.weights = make_weights(cfg, cell.seed)
        self.srv = serving.serve(
            (program_params(self.weights),
             program_config(cfg, self.options["max_len"])), **self.options)
        self.max_batch = self.options["max_batch"]
        self.vocab = cfg["vocab_size"]
        self._counters = {}
        self._rows_at = []

    def tokens_generated(self):
        """The generators read this at the window's two ends. At the first,
        the pools' high-water marks start over: the warm-up sends its
        prompts sorted by length, 32 of the longest at once, which no
        window does, and the cache manager's metrics are of the window."""
        if not self._rows_at:
            cache = self.srv.engine.cache
            for pool in cache.pools:
                pool.high_water = pool.in_use
            cache.held_at_high_water = tuple(p.in_use for p in cache.pools)
        return super().tokens_generated()

    def counters(self):
        if self.srv is not None:
            eng = self.srv.engine
            cache = eng.cache
            super().counters()
            full, window = cache.pools
            self._counters.update({
                "pool_kinds": list(cache.spec.kinds),
                "kv_window_high_water_blocks": window.high_water,
                "kv_window_num_blocks": window.num_blocks - 1,
                "kv_window_recycled_blocks": cache.recycled,
                "kv_blocks_at_high_water": list(cache.held_at_high_water)})
        return self._counters

    def check(self, record, control_bits=None):
        """As `latent_moe_lm`'s, against this family's reference: a sample
        of the requests the window finished, the longest in it,
        teacher-forced through the reference with the same share of the
        experts; per served token the gap between the reference's best
        logit and its logit of the served token; the mean, the 99th
        percentile and the widest are each held to a limit."""
        self.close()
        limits = self.cell.config["check"]
        done = [r for r in record["requests"] if r["ok"] and r["served"]]
        if not done:
            return [util.compared("requests_finished", 0, 1, ok=False)]
        sample = sample_finished(done, limits["sample_requests"],
                                 self.cell.seed)
        gaps = np.concatenate([
            np.asarray(reference.served_gaps(
                self.weights, self.cell.config, r["prompt"], r["served"],
                control_bits=control_bits))
            for r in sample])
        bad_ids = sum(1 for r in done for t in r["served"]
                      if not 0 <= t < self.vocab)
        return [
            util.compared("served_gap_max", float(gaps.max()),
                          limits["served_gap_max"]),
            util.compared("served_gap_p99", float(np.percentile(gaps, 99)),
                          limits["served_gap_p99"]),
            util.compared("served_gap_mean", float(gaps.mean()),
                          limits["served_gap_mean"]),
            util.compared("tokens_out_of_vocab", bad_ids, 0),
            util.note("sample_requests", len(sample)),
            util.note("sample_served_tokens", int(gaps.size)),
            util.note("sample_longest_tokens",
                      len(sample[0]["prompt"]) + len(sample[0]["served"])),
        ]


def build(cell):
    return Server(cell)
