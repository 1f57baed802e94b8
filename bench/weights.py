"""Seeded weights of a dense GQA decoder, made by the benchmark.

Every value is an integer times a power of two, drawn with
`jax.random.randint`, so it is exact in float16 (linear weights) or
float32 (embedding, LM head, norms, biases) and comes out bit for bit the
same however XLA fuses the program that draws it. The harness draws the
whole serving tree on the device in one jitted call (`serving_params`);
the plain reference draws one layer at a time (`layer_values`) from the
same keys, so both see the same numbers and the reference takes nothing
that the program made.

Scales follow the usual initialisation: linear weights uniform with
standard deviation 1/sqrt(fan_in), the embedding 0.02, biases 0.02, and
norm gains 1 + U(-0.1, 0.1) (stored as the offset from 1, the program's
`rms_norm` convention).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

LINEARS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
           "mlp/gate", "mlp/up", "mlp/down")


def root_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _grid(std: float) -> tuple[int, float]:
    """(m, step): U{-m..m} * step has about `std` and m < 2048, so each
    value has at most 11 significant bits (exact in float16)."""
    half_width = std * math.sqrt(3.0)
    e = math.floor(math.log2(2047 / half_width))
    step = 2.0 ** -e
    return int(round(half_width / step)), step


def draw(key, shape, std: float, dtype):
    m, step = _grid(std)
    ints = jax.random.randint(key, shape, -m, m + 1, jnp.int32)
    return ints.astype(dtype) * jnp.asarray(step, dtype)


def linear_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv, f = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["intermediate_size"])
    return {"attn/wq": (d, h * hd), "attn/wk": (d, hkv * hd),
            "attn/wv": (d, hkv * hd), "attn/wo": (h * hd, d),
            "mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)}


def layer_leaf(key, cfg: dict, name: str, layer):
    """One layer's value of a per-layer leaf (`layer` may be traced)."""
    k = jax.random.fold_in(_leaf_key(key, name), layer)
    d = cfg["hidden_size"]
    if name in LINEARS:
        kk, n = linear_shapes(cfg)[name]
        return draw(k, (kk, n), kk ** -0.5, jnp.float16)
    if name.endswith("/b"):
        n = linear_shapes(cfg)[name[:-2]][1]
        return draw(k, (n,), 0.02, jnp.float32)
    if name in ("ln1", "ln2"):
        return draw(k, (d,), 0.1 / math.sqrt(3.0), jnp.float32)
    raise KeyError(name)


def layer_names(cfg: dict) -> list[str]:
    names = ["ln1", "ln2", *LINEARS]
    if cfg.get("attention_bias"):
        names += [f"attn/{w}/b" for w in ("wq", "wk", "wv")]
    return names


def global_leaf(key, cfg: dict, name: str):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = _leaf_key(key, name)
    if name == "embed":
        return draw(k, (v, d), 0.02, jnp.float32)
    if name == "lm_head":
        return draw(k, (d, v), d ** -0.5, jnp.float32)
    if name == "final_norm":
        return draw(k, (d,), 0.1 / math.sqrt(3.0), jnp.float32)
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


def serving_params(seed: int, cfg: dict):
    """The program's serving tree, drawn on the device in one jitted call:
    float32 embedding, norms, biases and LM head; each linear weight's
    float16 values nested into its two byte planes by the program's own
    encoder. Layers are drawn one at a time inside the program (lax.map),
    so no float32 copy of a stacked weight ever exists."""
    return jax.jit(builder(cfg))(root_key(seed))


def builder(cfg: dict):
    """The function of a root key that `serving_params` jits."""
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
        layers = {"ln1": per["ln1"], "ln2": per["ln2"],
                  "attn": {w: linear(f"attn/{w}")
                           for w in ("wq", "wk", "wv", "wo")},
                  "mlp": {w: linear(f"mlp/{w}")
                          for w in ("gate", "up", "down")}}
        tree = {"embed": {"tok": global_leaf(key, cfg, "embed")},
                "final_norm": global_leaf(key, cfg, "final_norm"),
                "layers": layers}
        if not cfg["tie_word_embeddings"]:
            tree["lm_head"] = {"w": global_leaf(key, cfg, "lm_head")}
        return tree

    return build
