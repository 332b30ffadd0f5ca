"""Family `falcon_h1_lm`: a language model whose every layer has attention
heads and state-space (Mamba-2) heads side by side, served through
`mxnet_tpu.serving.serve` like the other three language-model families.

The benchmark makes the weights a leaf at a time on the device, from the
seed, in the dtype they are served in and in the layout of
`chipbench/reference/falcon_h1_lm.py`; the program takes the same arrays
under `layer<i>_<leaf>`. After the window a sample of what was served is
compared with the reference's forward over the same weights. The functions
under "work from shapes" count what the per-layer readers divide by:
`decode_step_min_bytes` (`decode_hbm_share.ssm`), `prefill_flops`
(`prefill_mxu_share`), `ssm_step_bytes` (`ssm_step_hbm_share`),
`cache_state_share`.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.models.falcon_h1 import FalconH1Config

from chipbench.families import latent_moe_lm, transformer_lm
from chipbench.harness import util
from chipbench.reference import falcon_h1_lm as reference

sample_finished = transformer_lm.sample_finished
_itemsize = transformer_lm._itemsize
_normal = latent_moe_lm._normal
_size = latent_moe_lm._size

BLOCK_SIZE = 16               # the server's default, which the cell leaves


def conv_channels(config):
    """x | B | C: what goes through the convolution."""
    return config["mamba_d_ssm"] \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def layer_shapes(config):
    """{leaf: shape} of one layer's matrices (every layer is alike)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    ssm = config["mamba_d_ssm"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_in": (d, ssm + conv_channels(config) + config["mamba_n_heads"]),
            "w_out": (ssm, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def gain_shapes(config):
    d = config["hidden_size"]
    return {"norm_in_g": (d,), "norm_mlp_g": (d,),
            "ssm_norm_g": (config["mamba_d_ssm"],)}


def vector_shapes(config):
    """The mixer's small leaves: the convolution's taps (in the weights'
    dtype) and, in float32, its bias and a number a head."""
    heads = (config["mamba_n_heads"],)
    return {"conv_w": (config["mamba_d_conv"], conv_channels(config)),
            "conv_b": (conv_channels(config),),
            "dt_bias": heads, "A_log": heads, "D": heads}


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "lo", "hi",
                                             "how"))
def _drawn(key, shape, dtype, lo, hi, how):
    """`uniform` in [lo, hi); `log_uniform`: its logarithm; `dt_bias`: the
    inverse softplus of a log-uniform step."""
    if how == "uniform":
        v = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    else:
        v = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       math.log(lo), math.log(hi)))
        v = jnp.log(v) if how == "log_uniform" else v + jnp.log(-jnp.expm1(-v))
    return v.astype(dtype)


def make_weights(config, seed):
    """Reference-layout weights on the device, one leaf a call so that no
    more than one float32 leaf lies beside the bf16 ones: N(0, 0.02)
    matrices, gains N(1, 0.1); as the Mamba-2 code initialises them, the
    convolution's taps and bias uniform in +-1/sqrt(taps), `A_log` the log
    of uniform(1, 16), `dt_bias` the inverse softplus of log-uniform(0.001,
    0.1), `D` N(1, 0.1) (the last four in float32)."""
    dtype, f32 = jnp.dtype(config["dtype"]), jnp.dtype("float32")
    d, vocab = config["hidden_size"], config["vocab_size"]
    keys = iter(jax.random.split(util.prng_key(seed), 4096))
    matrix = lambda shape: _normal(next(keys), shape, dtype, 0.02, 0.0)
    gain = lambda shape: _normal(next(keys), shape, dtype, 0.1, 1.0)
    bound = config["mamba_d_conv"] ** -0.5
    weights = {"embed": matrix((vocab, d)), "head": matrix((d, vocab)),
               "normf_g": gain((d,)), "layers": []}
    for _ in range(config["num_hidden_layers"]):
        lw = {n: matrix(s) for n, s in sorted(layer_shapes(config).items())}
        lw.update((n, gain(s)) for n, s in sorted(gain_shapes(config).items()))
        small = vector_shapes(config)
        lw["conv_w"] = _drawn(next(keys), small["conv_w"], dtype, -bound,
                              bound, "uniform")
        lw["conv_b"] = _drawn(next(keys), small["conv_b"], f32, -bound, bound,
                              "uniform")
        lw["A_log"] = _drawn(next(keys), small["A_log"], f32, 1.0, 16.0,
                             "log_uniform")
        lw["dt_bias"] = _drawn(next(keys), small["dt_bias"], f32, 0.001, 0.1,
                               "dt_bias")
        lw["D"] = _normal(next(keys), small["D"], f32, 0.1, 1.0)
        weights["layers"].append(lw)
    return weights


def program_params(weights):
    """The same arrays under the names `models/falcon_h1.py` gives them."""
    p = {k: v for k, v in weights.items() if k != "layers"}
    for i, lw in enumerate(weights["layers"]):
        p.update(("layer%d_%s" % (i, n), a) for n, a in lw.items())
    return p


def program_config(config, max_len):
    return FalconH1Config(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        ssm_heads=config["mamba_n_heads"], ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], ssm_groups=config["mamba_n_groups"],
        conv_taps=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
        rope_base=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]),
        max_len=max_len, dtype=jnp.dtype(config["dtype"]),
        state_dtype=jnp.dtype(config["state_dtype"]))


# -- work from shapes ---------------------------------------------------------

def layer_params(config):
    """Every parameter of one layer: matrices, gains and the mixer's small
    leaves."""
    return _size(layer_shapes(config)) + _size(gain_shapes(config)) \
        + _size(vector_shapes(config))


def weight_bytes(config):
    """Every matrix held here: embedding, head and the layers' (the small
    float32 leaves are counted at the weights' width: 128 numbers a layer)."""
    return (2 * config["hidden_size"] * config["vocab_size"]
            + config["num_hidden_layers"] * layer_params(config)) \
        * _itemsize(config)


def kv_bytes_per_token_layer(config):
    """Keys and values of one token in one layer, in the pool's dtype."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * _itemsize(config)


def state_bytes_per_layer(config):
    """(the recurrence's state, the convolution's last inputs) of ONE
    sequence in one layer, whatever its length."""
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"]
            * jnp.dtype(config["state_dtype"]).itemsize,
            (config["mamba_d_conv"] - 1) * conv_channels(config)
            * _itemsize(config))


def pool_bytes(config, block_size=BLOCK_SIZE):
    """(the K/V planes, the state plane, the convolution plane) as the
    engine sizes them for the cell's server: max_batch sequences of
    max_len and the null block; a slot a sequence and the null slot."""
    server = config["server"]
    layers = config["num_hidden_layers"]
    nblk = math.ceil(server["max_len"] / block_size)
    state, conv = state_bytes_per_layer(config)
    slots = server["max_batch"] + 1
    return (layers * (server["max_batch"] * nblk + 1) * block_size
            * kv_bytes_per_token_layer(config),
            layers * slots * state, layers * slots * conv)


def matrix_bytes_per_step(config):
    """Bytes every decode step reads whatever its rows: each layer's
    matrices and the head (the embedding is read a row per sequence)."""
    return (config["num_hidden_layers"] * _size(layer_shapes(config))
            + config["hidden_size"] * config["vocab_size"]) * _itemsize(config)


def decode_step_min_bytes(config, state_rows, live_full):
    """The least a decode step must move: the weights and the head once;
    each row's state and convolution inputs read once and written once a
    layer; the keys and values of the `live_full` tokens its rows hold once
    a layer."""
    layers = config["num_hidden_layers"]
    return matrix_bytes_per_step(config) \
        + 2 * state_rows * layers * sum(state_bytes_per_layer(config)) \
        + live_full * layers * kv_bytes_per_token_layer(config)


def prefill_flops(config, bucket, pairs=None):
    """Operations a whole-prompt prefill over `bucket` rows needs: the
    matrices over every row, attention inside the causal triangle (query t
    against t + 1 keys, two products of 2 x heads x head_dim each), the
    convolution, and the scan in its matrix form, a chunk of
    `mamba_chunk_size` positions at a time: C B^T a group, its masked
    product with x a head, the chunk's state from B^T x and the carried
    state through C. The head scores one row and is left out. `pairs` is
    the expert families' and is not read."""
    layers = config["num_hidden_layers"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    chunk = min(config["mamba_chunk_size"], bucket)
    chunks = math.ceil(bucket / chunk)
    scan = chunks * (2 * chunk * chunk * (groups * n + heads * p)
                     + 4 * chunk * heads * p * n)
    keys = bucket * (bucket + 1) / 2
    per_key = 4 * config["num_attention_heads"] * config["head_dim"]
    conv = 2 * bucket * config["mamba_d_conv"] * conv_channels(config)
    return layers * (2.0 * bucket * _size(layer_shapes(config))
                     + keys * per_key + conv + scan)


def ssm_step_bytes(config, rows):
    """Bytes ONE call of the recurrence-step kernel (one layer) must move
    for `rows` rows: each row's state read once and written once, and per
    (row, group) B, C, the decays, dt x and y, float32."""
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    slab = config["mamba_n_heads"] // groups * config["mamba_d_head"]
    return 4 * rows * groups * (2 * n * slab + 2 * n + 3 * slab)


def cache_state_share(config, blocks, slots, block_size=BLOCK_SIZE):
    """Of the bytes sequences hold at one moment (`blocks` K/V blocks and
    `slots` state slots in use), the share in state slots, %: what does
    not grow with their length."""
    layers = config["num_hidden_layers"]
    state = slots * layers * sum(state_bytes_per_layer(config))
    kv = blocks * block_size * layers * kv_bytes_per_token_layer(config)
    return 100.0 * state / (state + kv)


class Server(transformer_lm.Server):
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
        self._reset = False

    def tokens_generated(self):
        """The generators read this at the window's two ends. At the first,
        the pools' high-water marks start over: the warm-up sends its
        prompts sorted by length, which no window does, and the cache
        manager's metrics are of the window."""
        if not self._reset:
            self._reset = True
            cache = self.srv.engine.cache
            for pool in cache.pools:
                pool.high_water = pool.in_use
            cache.held_at_high_water = tuple(p.in_use for p in cache.pools)
        return super().tokens_generated()

    def counters(self):
        if self.srv is not None:
            eng = self.srv.engine
            cache = eng.cache
            state = cache.pools[cache.spec.kinds.index("state")]
            self._counters = {
                "kv_high_water_blocks": cache.pool.high_water,
                "kv_num_blocks": cache.num_blocks - 1,
                "max_batch": self.max_batch,
                "paged": bool(eng.paged),
                "kv_quant": bool(eng.kv_quant),
                "weight_quant": eng.weight_quant,
                "pool_kinds": list(cache.spec.kinds),
                "pool_dtype": str(cache.k.dtype),
                "state_dtype": str(cache.ssm_state.dtype),
                "block_size": cache.block_size,
                "kv_bytes_per_token": eng.kv_bytes_per_token(),
                "state_bytes_per_sequence": cache.spec.state_bytes(),
                "state_high_water_slots": state.high_water,
                "state_num_slots": state.num_blocks - 1,
                "kv_blocks_at_high_water": list(cache.held_at_high_water),
                "walk_fallback": eng.walk_fallback,
                "state_step_fallback": eng.state_step_fallback}
        return self._counters

    def close(self):
        """Stop the server without waiting for what is still in flight and
        give its pools back, so the reference fits beside the weights."""
        if self.srv is None:
            return
        self.counters()
        srv, self.srv = self.srv, None
        srv.close(drain=False, timeout=30.0)
        srv.engine.cache.drop()

    def check(self, record, control_bits=None):
        """As the other families': a sample of the requests the window
        finished, the longest in it, teacher-forced through the reference;
        per served token the gap between the reference's best logit and its
        logit of the served token; the mean, the 99th percentile and the
        widest are each held to a limit."""
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
