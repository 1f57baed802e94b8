"""The dense GQA decoder family (Llama / Qwen2 / Qwen3): its leaf layout,
its seeded weights, the program's serving tree and ArchConfig for it, and
its plain float32 reference.

A configuration names this module by its `reference` key; the harness
reaches the architecture only through it. It exports:

  arch_config(cfg)     the program's ArchConfig for a configuration file
  LINEARS, linear_shapes(cfg), layer_names(cfg), layer_leaf(...),
  global_leaf(...)     the leaves and their shapes
  layer_values(cfg), global_values(seed, cfg)
                       the weights as float32 values, for the reference
  builder(cfg)         a function of a root key that returns the program's
                       serving tree (`bench.weights.serving_params` jits it)
  served_gaps(seed, cfg, seqs)
                       the comparison that decides `correct`

The reference is the published forward pass, written out in `jax.numpy`
with every matrix product at HIGHEST precision, one layer at a time, with
no cache, kernel or batching: token embedding; per layer a pre-norm
RMSNorm, q/k/v projections (with biases where the configuration has
them), an RMSNorm over each q and k head where the configuration has
`qk_norm` (Qwen3), rotary embedding in the split-half convention, causal
grouped-query softmax attention, the output projection and a residual
add; then a pre-norm SwiGLU MLP and a residual add; a final RMSNorm and
the LM head (the embedding matrix where tied). Norm gains are stored as
the offset from 1.

The reference path (`served_gaps` and the values it draws) imports
nothing of the program under test and draws its own weights from the
seed. `arch_config` and `builder` describe the program's side and import
it inside themselves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import draw, leaf_key, root_key

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)
PAD = 256              # sequences are padded to a multiple of this
NORM_STD = 0.1 / math.sqrt(3.0)     # norm gains 1 + U(-0.1, 0.1)

LINEARS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
           "mlp/gate", "mlp/up", "mlp/down")


# -- the program's side ------------------------------------------------------

def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        arch_id=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=bool(cfg.get("qk_norm")),
        qkv_bias=bool(cfg.get("attention_bias")),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_seq_len=cfg["max_position_embeddings"])


def builder(cfg: dict):
    """The function of a root key that draws the program's serving tree:
    float32 embedding, norms, biases and LM head; each linear weight's
    float16 values nested into its two byte planes by the program's own
    encoder. Layers are drawn one at a time inside the program (lax.map),
    so no float32 copy of a stacked weight ever exists."""
    from repro.core.linear import NestedLinearParams
    from repro.core.nestedfp import NestedTensor, encode

    n_layers = cfg["num_hidden_layers"]
    names = layer_names(cfg)

    def build(key):
        per = jax.lax.map(
            lambda l: {n: (encode(layer_leaf(key, cfg, n, l))
                           if n in LINEARS else layer_leaf(key, cfg, n, l))
                       for n in names},
            jnp.arange(n_layers))

        def linear(name):
            upper, lower = per[name]
            return NestedLinearParams(
                weight=NestedTensor(upper=upper, lower=lower, raw=None),
                bias=per.get(f"{name}/b"))
        attn = {w: linear(f"attn/{w}") for w in ("wq", "wk", "wv", "wo")}
        if cfg.get("qk_norm"):
            attn.update(q_norm=per["attn/q_norm"], k_norm=per["attn/k_norm"])
        layers = {"ln1": per["ln1"], "ln2": per["ln2"], "attn": attn,
                  "mlp": {w: linear(f"mlp/{w}")
                          for w in ("gate", "up", "down")}}
        tree = {"embed": {"tok": global_leaf(key, cfg, "embed")},
                "final_norm": global_leaf(key, cfg, "final_norm"),
                "layers": layers}
        if not cfg["tie_word_embeddings"]:
            tree["lm_head"] = {"w": global_leaf(key, cfg, "lm_head")}
        return tree

    return build


# -- leaves --------------------------------------------------------------------

def linear_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of each linear of a layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv, f = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["intermediate_size"])
    return {"attn/wq": (d, h * hd), "attn/wk": (d, hkv * hd),
            "attn/wv": (d, hkv * hd), "attn/wo": (h * hd, d),
            "mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)}


def layer_names(cfg: dict) -> list[str]:
    names = ["ln1", "ln2", *LINEARS]
    if cfg.get("attention_bias"):
        names += [f"attn/{w}/b" for w in ("wq", "wk", "wv")]
    if cfg.get("qk_norm"):
        names += ["attn/q_norm", "attn/k_norm"]
    return names


def layer_leaf(key, cfg: dict, name: str, layer):
    """One layer's value of a per-layer leaf (`layer` may be traced)."""
    k = jax.random.fold_in(leaf_key(key, name), layer)
    if name in LINEARS:
        kk, n = linear_shapes(cfg)[name]
        return draw(k, (kk, n), kk ** -0.5, jnp.float16)
    if name.endswith("/b"):
        n = linear_shapes(cfg)[name[:-2]][1]
        return draw(k, (n,), 0.02, jnp.float32)
    if name in ("ln1", "ln2"):
        return draw(k, (cfg["hidden_size"],), NORM_STD, jnp.float32)
    if name in ("attn/q_norm", "attn/k_norm"):
        return draw(k, (cfg["head_dim"],), NORM_STD, jnp.float32)
    raise KeyError(name)


def global_leaf(key, cfg: dict, name: str):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = leaf_key(key, name)
    if name == "embed":
        return draw(k, (v, d), 0.02, jnp.float32)
    if name == "lm_head":
        return draw(k, (d, v), d ** -0.5, jnp.float32)
    if name == "final_norm":
        return draw(k, (d,), NORM_STD, jnp.float32)
    raise KeyError(name)


def layer_values(cfg: dict):
    """A jitted function (root key, layer) -> that layer's weights as
    float32 values, for the reference."""
    return jax.jit(lambda key, layer: {
        n: layer_leaf(key, cfg, n, layer).astype(jnp.float32)
        for n in layer_names(cfg)})


def global_values(seed: int, cfg: dict) -> dict[str, jax.Array]:
    """Embedding, final norm and LM head (the embedding again when tied)."""
    def draw_all(key):
        out = {n: global_leaf(key, cfg, n) for n in ("embed", "final_norm")}
        out["lm_head"] = out["embed"].T if cfg["tie_word_embeddings"] \
            else global_leaf(key, cfg, "lm_head")
        return out
    return jax.jit(draw_all)(root_key(seed))


# -- the plain reference ---------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, w, h):
    """One decoder layer over a whole (padded) sequence h: (S, d). Pad
    positions come last, so the causal mask keeps them out of every real
    position."""
    s = h.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)

    def proj(x, name):
        y = jnp.dot(x, w[name], precision=HI)
        return y + w[f"{name}/b"] if f"{name}/b" in w else y

    def heads(x, name, n):
        """Projected q or k heads, normed over head_dim (qk_norm), then
        rotated."""
        y = proj(x, f"attn/w{name}").reshape(s, n, hd)
        if cfg.get("qk_norm"):
            y = _rms(y, w[f"attn/{name}_norm"], eps)
        return _rope(y, pos, cfg["rope_theta"])

    x = _rms(h, w["ln1"], eps)
    q = heads(x, "q", nh)
    k = heads(x, "k", nkv)
    v = proj(x, "attn/wv").reshape(s, nkv, hd)
    g = nh // nkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        causal = pos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(s, nh * hd)
    h = h + jnp.dot(o, w["attn/wo"], precision=HI)
    x = _rms(h, w["ln2"], eps)
    gate = jnp.dot(x, w["mlp/gate"], precision=HI)
    up = jnp.dot(x, w["mlp/up"], precision=HI)
    return h + jnp.dot(jax.nn.silu(gate) * up, w["mlp/down"], precision=HI)


def _logits(cfg, glob, h, positions):
    x = _rms(h[positions], glob["final_norm"], cfg["rms_norm_eps"])
    return jnp.dot(x, glob["lm_head"], precision=HI)


def served_gaps(seed: int, cfg: dict, seqs) -> list[np.ndarray]:
    """For each (prompt, served tokens) in `seqs`, run the reference over
    the prompt and the served tokens and return, for each served token,
    how far its reference logit lies below the reference's best logit at
    that position (0 where the served token is the reference's argmax).
    Every sequence is padded to the engine's capacity (rounded up to
    PAD), so each program has one shape in every run of a cell."""
    layer = jax.jit(lambda w, h: _layer(cfg, w, h))
    logits = jax.jit(lambda g, h, p: _logits(cfg, g, h, p))
    draw_layer = layer_values(cfg)
    key = root_key(seed)
    glob = global_values(seed, cfg)
    s = -(-cfg["engine"]["capacity"] // PAD) * PAD
    hs, plan = [], []
    for prompt, served in seqs:
        toks = list(prompt) + list(served[:-1])
        ids = np.zeros(s, np.int32)
        ids[:len(toks)] = toks
        hs.append(glob["embed"][jnp.asarray(ids)])
        plan.append((np.arange(len(prompt) - 1, len(toks), dtype=np.int32),
                     np.asarray(served, np.int32)))
    for li in range(cfg["num_hidden_layers"]):
        w = draw_layer(key, li)
        hs = [layer(w, h) for h in hs]
        del w
    out = []
    for h, (positions, served) in zip(hs, plan):
        lg = logits(glob, h, jnp.asarray(positions))
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
        out.append(np.asarray(best - got, np.float64))
    return out
