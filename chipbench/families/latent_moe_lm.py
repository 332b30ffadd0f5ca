"""Family `latent_moe_lm`: a latent-attention language model with dropless
sparse experts, one chip's share of an expert-parallel deployment, served
through `mxnet_tpu.serving.serve` like `transformer_lm`, whose `Server` this
one extends.

The benchmark makes the weights a leaf at a time on the device, from the
seed, in the dtype they are served in and in the layout of
`chipbench/reference/latent_moe_lm.py`; the program takes the same arrays
under `layer<i>_<leaf>`. After the window a sample of what was served is
compared with the reference's forward over the same weights and the same
share of the experts. `decode_step_min_bytes` is the numerator of
`decode_hbm_share.moe`, from shapes and from the number of held experts a
step really touched.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.models.latent_moe import LatentMoEConfig, held_range

from chipbench.families import transformer_lm
from chipbench.harness import util
from chipbench.reference import latent_moe_lm as reference

sample_finished = transformer_lm.sample_finished
_itemsize = transformer_lm._itemsize


def layer_shapes(config, index):
    """{leaf: shape} of layer `index`, matrices only."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    r, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    shapes = {
        "wq_a": (d, config["q_lora_rank"]),
        "wq_b": (config["q_lora_rank"], h * (config["qk_nope_head_dim"] + dr)),
        "wkv_a": (d, r + dr),
        "wk_b": (r, h * config["qk_nope_head_dim"]),
        "wv_b": (r, h * config["v_head_dim"]),
        "wo": (h * config["v_head_dim"], d)}
    if index < config["first_k_dense_replace"]:
        f = config["intermediate_size"]
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        f, held = config["moe_intermediate_size"], config["n_routed_experts"]
        fs = f * config["n_shared_experts"]
        shapes.update(router=(d, config["n_routed_experts_published"]),
                      ws_gate=(d, fs), ws_up=(d, fs), ws_down=(fs, d),
                      we_gate=(held, d, f), we_up=(held, d, f),
                      we_down=(held, f, d))
    return shapes


def gain_shapes(config):
    d = config["hidden_size"]
    return {"norm1_g": (d,), "norm2_g": (d,),
            "q_norm_g": (config["q_lora_rank"],),
            "kv_norm_g": (config["kv_lora_rank"],)}


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "scale", "mean"))
def _normal(key, shape, dtype, scale, mean):
    return (mean + scale * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


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
    """The same arrays under the names `models/latent_moe.py` gives them."""
    p = {k: v for k, v in weights.items() if k != "layers"}
    for i, lw in enumerate(weights["layers"]):
        p.update(("layer%d_%s" % (i, n), a) for n, a in lw.items())
    return p


def program_config(config, max_len):
    sc = config["rope_scaling"]
    return LatentMoEConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["n_shared_experts"],
        n_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"], n_groups=config["n_group"],
        top_groups=config["topk_group"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_held=held_range(config["expert_rank"],
                                config["expert_parallel"],
                                config["n_routed_experts_published"]),
        rope_base=float(config["rope_theta"]), rope_factor=float(sc["factor"]),
        rope_orig_len=sc["original_max_position_embeddings"],
        rope_beta_fast=float(sc["beta_fast"]),
        rope_beta_slow=float(sc["beta_slow"]), rope_mscale=float(sc["mscale"]),
        rope_mscale_all_dim=float(sc["mscale_all_dim"]),
        norm_eps=float(config["rms_norm_eps"]), max_len=max_len,
        dtype=jnp.dtype(config["dtype"]))


# -- work from shapes ---------------------------------------------------------

def _size(shapes):
    return sum(int(np.prod(s)) for s in shapes.values())


def expert_bytes(config):
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * _itemsize(config)


def dense_bytes_per_step(config):
    """Bytes every decode step reads whatever it routes: each layer's
    matrices outside its routed experts, and the head (the embedding is
    read a row per sequence)."""
    total = config["hidden_size"] * config["vocab_size"]
    for i in range(config["num_hidden_layers"]):
        shapes = layer_shapes(config, i)
        total += _size({n: s for n, s in shapes.items()
                        if not n.startswith("we_")})
    return total * _itemsize(config)


def kv_bytes_per_token(config):
    """Bytes of one token's latent rows over all layers, in the pool's dtype
    (the served dtype)."""
    return config["num_hidden_layers"] * _itemsize(config) \
        * (config["kv_lora_rank"] + config["qk_rope_head_dim"])


def decode_step_min_bytes(config, live_tokens, experts_touched):
    """The least a decode step must read: what every step reads, each held
    expert that got a row (counted over all expert layers) once, and the
    latent rows of the tokens its sequences hold once. An expert no row
    chose is not counted, so this is a lower bound."""
    return dense_bytes_per_step(config) \
        + experts_touched * expert_bytes(config) \
        + live_tokens * kv_bytes_per_token(config)


class Server(transformer_lm.Server):
    trace_slice_s = 4.0

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
        self._rows_at = []      # the program's expert tally at each reading

    def tokens_generated(self):
        """The generators read this at the window's two ends: the tally of
        rows per held expert is read with it, so that the window's own rows
        are the last reading less the first."""
        self._rows_at.append(self.srv.engine.model.expert_rows.copy())
        return super().tokens_generated()

    def counters(self):
        if self.srv is not None:
            eng = self.srv.engine
            window = self._rows_at[-1] - self._rows_at[0] \
                if len(self._rows_at) > 1 else eng.model.expert_rows
            self._counters = {
                "kv_high_water_blocks": eng.cache.pool.high_water,
                "kv_num_blocks": eng.cache.num_blocks - 1,
                "max_batch": self.max_batch,
                "paged": bool(eng.paged),
                "kv_quant": bool(eng.kv_quant),
                "weight_quant": eng.weight_quant,
                "pool_layout": eng.cache.layout,
                "pool_dtype": str(eng.cache.arrays()[0].dtype),
                "kv_bytes_per_token": eng.kv_bytes_per_token(),
                "moe_expert_tokens": eng.model.expert_rows.tolist(),
                "moe_expert_tokens_window": window.tolist()}
        return self._counters

    def close(self):
        """Stop the server without waiting for what is still in flight and
        give its pool back, so the reference fits beside the weights."""
        if self.srv is None:
            return
        self.counters()
        srv, self.srv = self.srv, None
        srv.close(drain=False, timeout=30.0)
        srv.engine.cache.drop()

    def check(self, record, control_bits=None):
        """As `transformer_lm`'s: a sample of the requests the window
        finished, the longest in it, teacher-forced through the reference
        with the same share of the experts; per served token the gap
        between the reference's best logit and its logit of the served
        token; the mean, the 99th percentile and the widest are each held
        to a limit."""
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
        ]


def build(cell):
    return Server(cell)
