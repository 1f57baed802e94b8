"""Plain float32 reference of a dense GQA decoder (Llama / Qwen2 family).

The published forward pass, written out in `jax.numpy` with every matrix
product at HIGHEST precision, one layer at a time, with no cache, kernel
or batching: token embedding; per layer a pre-norm RMSNorm, q/k/v
projections (with biases where the configuration has them), rotary
embedding in the split-half convention, causal grouped-query softmax
attention, the output projection and a residual add; then a pre-norm
SwiGLU MLP and a residual add; a final RMSNorm and the LM head (the
embedding matrix where tied). Norm gains are stored as the offset from 1.

It imports nothing of the program under test and draws its own weights
from the seed (`bench.weights`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)
PAD = 256              # sequences are padded to a multiple of this


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

    x = _rms(h, w["ln1"], eps)
    q = _rope(proj(x, "attn/wq").reshape(s, nh, hd), pos, cfg["rope_theta"])
    k = _rope(proj(x, "attn/wk").reshape(s, nkv, hd), pos, cfg["rope_theta"])
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
    draw = W.layer_values(cfg)
    key = W.root_key(seed)
    glob = W.global_values(seed, cfg)
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
        w = draw(key, li)
        hs = [layer(w, h) for h in hs]
        del w
    out = []
    for h, (positions, served) in zip(hs, plan):
        lg = logits(glob, h, jnp.asarray(positions))
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
        out.append(np.asarray(best - got, np.float64))
    return out
