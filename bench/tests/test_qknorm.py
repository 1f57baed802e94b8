"""Qwen3's q/k RMSNorm in the dense GQA family, on the CPU: the program's
tree carries the norms where the program reads them, the reference's
logits match the program's forward pass, a sound run of a q/k-norm
configuration is correct, and both the FP8 control and a program that
leaves the norms out fail the comparison (so the reference really checks
them). The harness's look for a chip is skipped (`allow_cpu`)."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run as R
from bench import family, weights

DATA = Path(__file__).resolve().parent / "data"
CELL, SEED, SECONDS = "tiny_qknorm.chat", 2**33 + 2718, 2.0
# The program rounds each GEMM's activations to float16 in FP16 mode
# (2^-11 relative); over two layers that moves logits of magnitude ~5 by
# a few thousandths (3.4e-3 measured on the CPU). Leaving the q/k norms
# out moves them by ~1.5.
LOGIT_TOL = 1e-2


def cfg():
    return json.loads((DATA / "tiny_qknorm.json").read_text())


def run(**kw):
    return R.run_once(CELL, SEED, SECONDS, False,
                      bench_json=DATA / "BENCHMARK.json", allow_cpu=True,
                      log=lambda *a: None, **kw)


def test_norms_sit_where_the_program_reads_them():
    from repro.models.convert import serving_param_shapes
    c = cfg()
    fam = family.of(c)
    tree = weights.serving_params(SEED, c)
    want = serving_param_shapes(fam.arch_config(c))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert jax.tree.structure(shapes(tree)) == \
        jax.tree.structure(shapes(want))
    assert jax.tree.leaves(shapes(tree)) == jax.tree.leaves(shapes(want))
    draw = fam.layer_values(c)
    for layer in range(c["num_hidden_layers"]):
        ref = draw(weights.root_key(SEED), layer)
        for name in ("q_norm", "k_norm"):
            np.testing.assert_array_equal(
                tree["layers"]["attn"][name][layer], ref[f"attn/{name}"])
            assert np.any(ref[f"attn/{name}"] != 0)


def _program_logits(tree, acfg, tokens):
    from repro.models import model as M
    from repro.models.layers import Runtime
    rt = Runtime(mode="fp16", backend="ref", dtype=jnp.float32)
    h = M.embed_tokens(rt, tree, acfg, tokens[None])
    h, _, _ = M.backbone(rt, tree, acfg, h, phase="prefill",
                         positions=jnp.arange(len(tokens))[None])
    return np.asarray(M.lm_logits(rt, tree, acfg, h)[0])


def _reference_logits(c, tokens):
    fam = family.of(c)
    glob = fam.global_values(SEED, c)
    ids = np.zeros(c["engine"]["capacity"], np.int32)
    ids[:len(tokens)] = tokens
    h = glob["embed"][jnp.asarray(ids)]
    draw = fam.layer_values(c)
    for layer in range(c["num_hidden_layers"]):
        h = fam._layer(c, draw(weights.root_key(SEED), layer), h)
    return np.asarray(fam._logits(c, glob, h, jnp.arange(len(tokens))))


def test_reference_logits_match_the_program():
    c = cfg()
    tokens = np.asarray([(37 * i + 11) % c["vocab_size"] for i in range(48)],
                        np.int32)
    prog = _program_logits(weights.serving_params(SEED, c),
                           family.of(c).arch_config(c), tokens)
    ref = _reference_logits(c, tokens)
    assert np.abs(prog - ref).max() < LOGIT_TOL
    # the same reference without the norms is far off the program
    assert np.abs(prog - _reference_logits({**c, "qk_norm": False},
                                           tokens)).max() > 10 * LOGIT_TOL


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_fails():
    out = run(mode="fp8")
    assert not out["correct"], out["checks"]


def test_program_without_qk_norm_fails(monkeypatch):
    fam = family.of(cfg())
    real = fam.arch_config
    monkeypatch.setattr(fam, "arch_config", lambda c: dataclasses.replace(
        real(c), qk_norm=False))
    out = run()
    assert not out["correct"], out["checks"]
