"""Family `nemotron_h_lm`: a language model whose every layer is ONE mixer
by a pattern's letter (a Mamba-2 mixer, a routed squared-ReLU expert layer or
a grouped-query attention), one chip's share of a pipelined, expert-parallel
deployment, served through `mxnet_tpu.serving.serve` like the other four
language-model families.

The benchmark makes the weights a leaf at a time on the device, from the
seed, in the dtype they are served in and in the layout of
`chipbench/reference/nemotron_h_lm.py`; the program takes the same arrays
under `layer<i>_<leaf>`. After the window a sample of what was served is
compared with the reference's forward over the same weights and the same
share of the experts. The functions under "work from shapes" count what the
per-layer readers divide by, each by the PATTERN's letters (six of this
cut's thirteen layers keep a state, two keep keys and values, five hold
experts): `decode_step_min_bytes` (`decode_hbm_share.ssm_moe`),
`prefill_flops` (`prefill_mxu_share`), `ssm_step_bytes`
(`ssm_step_hbm_share.hybrid`), `cache_state_share`.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.models.latent_moe import held_range
from mxnet_tpu.models.nemotron_h import NemotronHConfig

from chipbench.families import falcon_h1_lm, latent_moe_lm, transformer_lm
from chipbench.harness import util
from chipbench.reference import nemotron_h_lm as reference

sample_finished = transformer_lm.sample_finished
_itemsize = transformer_lm._itemsize
_normal = latent_moe_lm._normal
_size = latent_moe_lm._size
_drawn = falcon_h1_lm._drawn

BLOCK_SIZE = 16               # the server's default, which the cell leaves


def layers_of(config, letter):
    """How many layers of the pattern are M, E or *."""
    return config["hybrid_override_pattern"].count(letter)


def d_ssm(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def conv_channels(config):
    """x | B | C: what goes through the convolution."""
    return d_ssm(config) + 2 * config["n_groups"] * config["ssm_state_size"]


def layer_shapes(config, letter):
    """{leaf: shape} of a layer of one letter, matrices only."""
    d = config["hidden_size"]
    if letter == "M":
        return {"w_in": (d, d_ssm(config) + conv_channels(config)
                         + config["mamba_num_heads"]),
                "w_out": (d_ssm(config), d)}
    if letter == "E":
        f, held = config["moe_intermediate_size"], config["n_routed_experts"]
        fs = config["moe_shared_expert_intermediate_size"]
        return {"router": (d, config["n_routed_experts_published"]),
                "ws_up": (d, fs), "ws_down": (fs, d),
                "we_up": (held, d, f), "we_down": (held, f, d)}
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


def gain_shapes(config, letter):
    gains = {"norm_g": (config["hidden_size"],)}
    if letter == "M":
        gains["ssm_norm_g"] = (d_ssm(config),)
    return gains


def vector_shapes(config, letter):
    """A layer's small leaves: a mixer's convolution taps (in the weights'
    dtype) and, in float32, its bias and a number a head; a router's
    selection bias."""
    if letter == "M":
        heads = (config["mamba_num_heads"],)
        return {"conv_w": (config["conv_kernel"], conv_channels(config)),
                "conv_b": (conv_channels(config),),
                "dt_bias": heads, "A_log": heads, "D": heads}
    if letter == "E":
        return {"router_bias": (config["n_routed_experts_published"],)}
    return {}


def make_weights(config, seed):
    """Reference-layout weights on the device, one leaf a call so that no
    more than one float32 leaf lies beside the bf16 ones: N(0, 0.02)
    matrices, gains N(1, 0.1), the selection bias N(0, 0.01) in float32; a
    mixer's small leaves as `falcon_h1_lm.make_weights` draws them."""
    dtype, f32 = jnp.dtype(config["dtype"]), jnp.dtype("float32")
    d, vocab = config["hidden_size"], config["vocab_size"]
    keys = iter(jax.random.split(util.prng_key(seed), 4096))
    matrix = lambda shape: _normal(next(keys), shape, dtype, 0.02, 0.0)
    gain = lambda shape: _normal(next(keys), shape, dtype, 0.1, 1.0)
    bound = config["conv_kernel"] ** -0.5
    weights = {"embed": matrix((vocab, d)), "head": matrix((d, vocab)),
               "normf_g": gain((d,)), "layers": []}
    for letter in config["hybrid_override_pattern"]:
        lw = {n: matrix(s)
              for n, s in sorted(layer_shapes(config, letter).items())}
        lw.update((n, gain(s))
                  for n, s in sorted(gain_shapes(config, letter).items()))
        small = vector_shapes(config, letter)
        if letter == "M":
            lw["conv_w"] = _drawn(next(keys), small["conv_w"], dtype, -bound,
                                  bound, "uniform")
            lw["conv_b"] = _drawn(next(keys), small["conv_b"], f32, -bound,
                                  bound, "uniform")
            lw["A_log"] = _drawn(next(keys), small["A_log"], f32, 1.0, 16.0,
                                 "log_uniform")
            lw["dt_bias"] = _drawn(next(keys), small["dt_bias"], f32, 0.001,
                                   0.1, "dt_bias")
            lw["D"] = _normal(next(keys), small["D"], f32, 0.1, 1.0)
        elif letter == "E":
            lw["router_bias"] = _normal(next(keys), small["router_bias"], f32,
                                        0.01, 0.0)
        weights["layers"].append(lw)
    return weights


def program_params(weights):
    """The same arrays under the names `models/nemotron_h.py` gives them."""
    p = {k: v for k, v in weights.items() if k != "layers"}
    for i, lw in enumerate(weights["layers"]):
        p.update(("layer%d_%s" % (i, n), a) for n, a in lw.items())
    return p


def program_config(config, max_len):
    return NemotronHConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        conv_taps=config["conv_kernel"], chunk=config["chunk_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"], n_groups=config["n_group"],
        top_groups=config["topk_group"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_held=held_range(config["expert_rank"],
                                config["expert_parallel"],
                                config["n_routed_experts_published"]),
        norm_eps=float(config["layer_norm_epsilon"]), max_len=max_len,
        dtype=jnp.dtype(config["dtype"]),
        state_dtype=jnp.dtype(config["state_dtype"]))


# -- work from shapes ---------------------------------------------------------

def layer_params(config, letter):
    """Every parameter of one layer of a letter: matrices, gains and small
    leaves."""
    return _size(layer_shapes(config, letter)) \
        + _size(gain_shapes(config, letter)) \
        + _size(vector_shapes(config, letter))


def param_count(config):
    """Every parameter held here: embedding, head, the last norm's gain and
    the pattern's layers."""
    d = config["hidden_size"]
    return 2 * d * config["vocab_size"] + d + sum(
        layer_params(config, letter)
        for letter in config["hybrid_override_pattern"])


def weight_bytes(config):
    """`param_count` at the weights' width (the float32 small leaves are
    counted at it too: a few hundred numbers a layer)."""
    return param_count(config) * _itemsize(config)


def expert_bytes(config):
    """One routed expert's two matrices."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"] \
        * _itemsize(config)


def kv_bytes_per_token_layer(config):
    """Keys and values of one token in one ATTENTION layer, in the pool's
    dtype."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * _itemsize(config)


def state_bytes_per_layer(config):
    """(the recurrence's state, the convolution's last inputs) of ONE
    sequence in one STATE layer, whatever its length."""
    return (d_ssm(config) * config["ssm_state_size"]
            * jnp.dtype(config["state_dtype"]).itemsize,
            (config["conv_kernel"] - 1) * conv_channels(config)
            * _itemsize(config))


def pool_bytes(config, block_size=BLOCK_SIZE):
    """(the K/V planes, the state plane, the convolution plane) as the
    engine sizes them for the cell's server, each over its own kind's
    layers: max_batch sequences of max_len and the null block on the
    attention layers; a slot a sequence and the null slot on the state
    layers."""
    server = config["server"]
    nblk = math.ceil(server["max_len"] / block_size)
    state, conv = state_bytes_per_layer(config)
    slots = server["max_batch"] + 1
    return (layers_of(config, "*") * (server["max_batch"] * nblk + 1)
            * block_size * kv_bytes_per_token_layer(config),
            layers_of(config, "M") * slots * state,
            layers_of(config, "M") * slots * conv)


def dense_params(config):
    """Every layer's matrices outside its routed experts."""
    return sum(_size({n: s for n, s in layer_shapes(config, letter).items()
                      if not n.startswith("we_")})
               for letter in config["hybrid_override_pattern"])


def matrix_bytes_per_step(config):
    """Bytes every decode step reads whatever its rows and whatever it
    routes: each layer's matrices outside its routed experts, and the head
    (the embedding is read a row per sequence)."""
    return (dense_params(config)
            + config["hidden_size"] * config["vocab_size"]) * _itemsize(config)


def decode_step_min_bytes(config, state_rows, live_full, experts_touched):
    """The least a decode step must move: what every step reads; each held
    expert that got a row (counted over all expert layers) once; each row's
    state and convolution inputs read once and written once a STATE layer;
    the keys and values of the `live_full` tokens its rows hold once an
    ATTENTION layer. An expert no row chose is not counted, so this is a
    lower bound."""
    return matrix_bytes_per_step(config) \
        + experts_touched * expert_bytes(config) \
        + 2 * state_rows * layers_of(config, "M") \
        * sum(state_bytes_per_layer(config)) \
        + live_full * layers_of(config, "*") * kv_bytes_per_token_layer(config)


def prefill_flops(config, bucket, pairs=None):
    """Operations a whole-prompt prefill over `bucket` rows needs: the
    matrices outside the routed experts over every row; the routed (row,
    held expert) pairs (`pairs`; by expectation bucket x experts per token x
    held / published where it is not given); on an attention layer query t
    against t + 1 keys, two products of 2 x heads x head_dim each; on a
    state layer the convolution and the scan in its matrix form, a chunk of
    `chunk_size` positions at a time (C B^T a group, its masked product
    with x a head, the chunk's state from B^T x and the carried state
    through C). The head scores one row and is left out."""
    if pairs is None:
        pairs = bucket * config["num_experts_per_tok"] \
            * config["n_routed_experts"] / config["n_routed_experts_published"]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    chunk = min(config["chunk_size"], bucket)
    scan = math.ceil(bucket / chunk) * (
        2 * chunk * chunk * (groups * n + heads * p)
        + 4 * chunk * heads * p * n)
    conv = 2 * bucket * config["conv_kernel"] * conv_channels(config)
    keys = bucket * (bucket + 1) / 2
    per_key = 4 * config["num_attention_heads"] * config["head_dim"]
    per_pair = 2 * 2 * config["hidden_size"] * config["moe_intermediate_size"]
    return 2.0 * bucket * dense_params(config) + pairs * per_pair \
        + layers_of(config, "*") * keys * per_key \
        + layers_of(config, "M") * (conv + scan)


def ssm_step_bytes(config, rows):
    """Bytes ONE call of the recurrence-step kernel (one state layer) must
    move for `rows` rows: each row's state read once and written once, and
    per (row, group) B, C, the decays, dt x and y, float32."""
    groups, n = config["n_groups"], config["ssm_state_size"]
    slab = d_ssm(config) // groups
    return 4 * rows * groups * (2 * n * slab + 2 * n + 3 * slab)


def cache_state_share(config, blocks, slots, block_size=BLOCK_SIZE):
    """Of the bytes sequences hold at one moment (`blocks` K/V blocks and
    `slots` state slots in use), the share in state slots, %: what does
    not grow with their length."""
    state = slots * layers_of(config, "M") * sum(state_bytes_per_layer(config))
    kv = blocks * block_size * layers_of(config, "*") \
        * kv_bytes_per_token_layer(config)
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
        self._rows_at = []      # the program's expert tally at each reading

    def tokens_generated(self):
        """The generators read this at the window's two ends: the tally of
        rows per held expert is read with it, so that the window's own rows
        are the last reading less the first. At the first, the pools'
        high-water marks start over: the warm-up sends its prompts sorted by
        length, which no window does, and the cache manager's metrics are
        of the window."""
        if not self._rows_at:
            cache = self.srv.engine.cache
            for pool in cache.pools:
                pool.high_water = pool.in_use
            cache.held_at_high_water = tuple(p.in_use for p in cache.pools)
        self._rows_at.append(self.srv.engine.model.expert_rows.copy())
        return super().tokens_generated()

    def counters(self):
        if self.srv is not None:
            eng = self.srv.engine
            cache, spec = eng.cache, eng.cache.spec
            state = cache.pools[spec.kinds.index("state")]
            window = self._rows_at[-1] - self._rows_at[0] \
                if len(self._rows_at) > 1 else eng.model.expert_rows
            self._counters = {
                "kv_high_water_blocks": cache.pool.high_water,
                "kv_num_blocks": cache.num_blocks - 1,
                "max_batch": self.max_batch,
                "paged": bool(eng.paged),
                "kv_quant": bool(eng.kv_quant),
                "weight_quant": eng.weight_quant,
                "pool_kinds": list(spec.kinds),
                "layers_by_kind": {k: list(spec.layers_of(k))
                                   for k in spec.kinds},
                "pool_dtype": str(cache.k.dtype),
                "state_dtype": str(cache.ssm_state.dtype),
                "state_shape": list(spec.state_shape),
                "block_size": cache.block_size,
                "kv_bytes_per_token": eng.kv_bytes_per_token(),
                "state_bytes_per_sequence": spec.state_bytes(),
                "state_high_water_slots": state.high_water,
                "state_num_slots": state.num_blocks - 1,
                "kv_blocks_at_high_water": list(cache.held_at_high_water),
                "moe_expert_tokens": eng.model.expert_rows.tolist(),
                "moe_expert_tokens_window": window.tolist(),
                "walk_fallback": eng.walk_fallback,
                "prompt_attn_fallback": eng.prompt_attn_fallback,
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
            util.note("sample_longest_tokens",
                      len(sample[0]["prompt"]) + len(sample[0]["served"])),
        ]


def build(cell):
    return Server(cell)
