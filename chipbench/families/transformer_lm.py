"""Family `transformer_lm`: a decoder-only language model served through
`mxnet_tpu.serving.serve`, the door a user opens.

The benchmark makes the weights (one jitted call on the device, from the
seed, in the dtype they are served in) in the layout of
`chipbench/reference/transformer_lm.py`, hands them to the program under the
program's names, and after the window compares a sample of what was served
with the reference's forward over the same weights. `decode_step_min_bytes`
is the numerator of `decode_hbm_share`, from shapes alone.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import util
from chipbench.reference import transformer_lm as reference

LAYER_LEAVES = ("ln1_g", "ln1_b", "wqkv", "wo", "ln2_g", "ln2_b", "w1", "w2")


def make_weights(config, seed):
    """Reference-layout weights on the device: N(0, 0.02) matrices, LayerNorm
    gains near 1 and biases near 0 (not exactly, so a dropped gain shows)."""
    d, ffn = config["hidden_size"], config["ffn_dim"]
    vocab, n_pos = config["vocab_size"], config["max_position_embeddings"]
    n_layers = config["num_hidden_layers"]
    dtype = jnp.dtype(config["dtype"])
    shapes = {"wqkv": (d, 3 * d), "wo": (d, d), "w1": (d, ffn), "w2": (ffn, d)}

    def normal(key, shape, scale=0.02, mean=0.0):
        return (mean + scale * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    @jax.jit
    def make(key):
        k_embed, k_pos, k_head, k_lnf, k_layers = jax.random.split(key, 5)
        layers = []
        for lk in jax.random.split(k_layers, n_layers):
            ks = dict(zip(LAYER_LEAVES, jax.random.split(lk, len(LAYER_LEAVES))))
            lw = {n: normal(ks[n], s) for n, s in shapes.items()}
            for ln in ("ln1", "ln2"):
                lw[ln + "_g"] = normal(ks[ln + "_g"], (d,), 0.1, 1.0)
                lw[ln + "_b"] = normal(ks[ln + "_b"], (d,), 0.1)
            layers.append(lw)
        kg, kb = jax.random.split(k_lnf)
        return {"embed": normal(k_embed, (vocab, d)),
                "pos": normal(k_pos, (n_pos, d)),
                "lnf_g": normal(kg, (d,), 0.1, 1.0), "lnf_b": normal(kb, (d,), 0.1),
                "head": normal(k_head, (d, vocab)), "layers": layers}

    return make(util.prng_key(seed))


def program_params(weights):
    """The same arrays under the names `models/transformer.py` gives them."""
    p = {"embed": weights["embed"], "pos_embed": weights["pos"],
         "lnf_g": weights["lnf_g"], "lnf_b": weights["lnf_b"],
         "head": weights["head"]}
    for i, lw in enumerate(weights["layers"]):
        for n in LAYER_LEAVES:
            p["layer%d_%s" % (i, n)] = lw[n]
    return p


def _itemsize(config):
    return jnp.dtype(config["dtype"]).itemsize


def weight_bytes_per_step(config):
    """Bytes of every layer's matrices and of the head, read once per decode
    step whatever the batch (the embedding is read a row per sequence)."""
    d, ffn = config["hidden_size"], config["ffn_dim"]
    per_layer = 4 * d * d + 2 * d * ffn
    return _itemsize(config) * (config["num_hidden_layers"] * per_layer
                                + d * config["vocab_size"])


def kv_bytes_per_token(config):
    """Bytes of one token's keys and values over all layers, in the pool's
    dtype (the served dtype)."""
    return 2 * config["num_hidden_layers"] * config["hidden_size"] \
        * _itemsize(config)


def decode_step_min_bytes(config, live_tokens):
    """The least a decode step must read: the weights once, and the keys and
    values of the tokens its sequences hold once."""
    return weight_bytes_per_step(config) \
        + live_tokens * kv_bytes_per_token(config)


class Server:
    trace_slice_s = 4.0       # some 70 decode steps: traces are large

    def __init__(self, cell, serve_options=None):
        from mxnet_tpu import serving
        from mxnet_tpu.models.transformer import TransformerConfig
        cfg = cell.config
        self.cell = cell
        self.options = dict(cfg["server"])
        self.options.update(serve_options or {})
        self.weights = make_weights(cfg, cell.seed)
        tcfg = TransformerConfig(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_layers=cfg["num_hidden_layers"], d_ff=cfg["ffn_dim"],
            max_len=cfg["max_position_embeddings"],
            dtype=jnp.dtype(cfg["dtype"]))
        self.srv = serving.serve((program_params(self.weights), tcfg),
                                 **self.options)
        self.max_batch = self.options["max_batch"]
        self.vocab = cfg["vocab_size"]
        self._counters = {}

    # -- what the generators drive ----------------------------------------

    def submit(self, prompt, max_new):
        return self.srv.submit(prompt, max_new_tokens=max_new)

    def tokens_generated(self):
        """Tokens the server's decode steps have emitted so far (its own
        counter; a request's first token comes from prefill and is not in
        it)."""
        return self.srv.metrics.tokens_generated

    # -- after the window ---------------------------------------------------

    def counters(self):
        if self.srv is not None:
            eng = self.srv.engine
            self._counters = {
                "kv_high_water_blocks": eng.cache.pool.high_water,
                "kv_num_blocks": eng.cache.num_blocks - 1,
                "max_batch": self.max_batch,
                "paged": bool(eng.paged),
                "kv_quant": bool(eng.kv_quant),
                "weight_quant": eng.weight_quant,
                "pool_dtype": str(eng.cache.k.dtype)}
        return self._counters

    def close(self):
        """Stop the server without waiting for what is still in flight and
        give its pool back, so the reference fits beside the weights."""
        if self.srv is None:
            return
        self.counters()
        srv, self.srv = self.srv, None
        srv.close(drain=False, timeout=30.0)
        srv.engine.cache.k = srv.engine.cache.v = None
        del srv
        gc.collect()

    def host_spans(self, record, spans):
        """What the host was doing, for labelling the device's idle gaps:
        the engine's own prefill and decode-step spans (name, start, end;
        perf_counter s)."""
        return [(s["name"], s["ts"] / 1e6, (s["ts"] + s["dur"]) / 1e6)
                for s in spans
                if s["name"] == "serving.prefill" or "batch" in s.get("attrs", {})]

    def check(self, record, control_bits=None):
        """The served-model comparison of "How correct is decided": a sample
        of the requests the window finished, drawn from the seed, with the
        longest in it; per served token the gap between the reference's
        best logit and its logit of the served token; the widest gap and
        the mean are each held to a limit."""
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
            util.compared("served_gap_mean", float(gaps.mean()),
                          limits["served_gap_mean"]),
            util.compared("tokens_out_of_vocab", bad_ids, 0),
            util.note("sample_requests", len(sample)),
            util.note("sample_served_tokens", int(gaps.size)),
        ]

    def control(self, record):
        """The reference in the program's place with int8 weights: at each
        position of the same prompts and served tokens, the gap of the token
        that the lower precision puts first."""
        return self.check(record, control_bits=self.cell.config["check"][
            "control_weight_bits"])


def sample_finished(done, n, seed):
    """The longest finished request and n-1 others, drawn from the seed."""
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + len(done[i]["served"])))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    picks = [order[0]] + [rest[i] for i in
                          rng.permutation(len(rest))[:max(0, n - 1)]]
    return [done[i] for i in picks]


def build(cell):
    return Server(cell)
