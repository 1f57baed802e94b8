#!/usr/bin/env python3
"""Chip smoke test: the serving path end to end on a TPU.

    python3 chip_smoke.py [--seed N]           one chip
    python3 chip_smoke.py --chips 4 [--seed N] the four-chip phase only

One chip: qwen1.5-0.5b at its full published width, weights random from
--seed and nested once, served through the normal entry points (`Engine`
-> `paged_step`) with the NestedFP Pallas GEMMs doing every linear layer.

  * GEMMs: every qwen1.5-0.5b linear shape at M=8 and M=256, Pallas and
    the jnp oracle (`backend="ref"`), against a float64 product of the
    same operands, in FP16 and FP8 mode (GEMM_TOL);
  * logits: first-step logits of an 8 x 128-token prefill through the
    Pallas GEMMs against the jnp oracle on the same chip, in FP16 and
    FP8 mode (LOGIT_TOL);
  * attention: the paged planar decode kernel in both modes, and the
    plain-pool paged decode kernel, against a jnp reference at the
    model's head shapes (ATTN_TOL);
  * serving: 8 requests (128-token prompts, 32 new tokens, 8 slots,
    capacity 512) in forced FP16, in forced FP8 (the same nested weight
    bytes), and in FP16 over a byte-planar KV pool, decode attention in
    the Pallas kernels throughout. Each pass AOT-compiles its decode and
    prefill step programs first, timing the compile and counting the
    `tpu_custom_call`s in them.

Four chips: llama3.1-8b, whose nested linears (about 13 GiB) plus f32
embedding and LM head (about 4 GiB) do not fit one 16 GB chip, built
sharded and served on a ("model",) mesh in FP16 and FP8; a 2-layer
depth cut of it on the mesh and on one chip must give first-step logits
within LOGIT_TOL in both modes and identical served tokens in FP16
(token agreement in FP8 is reported); four one-chip qwen1.5-0.5b
replicas behind `Router` must finish every request with no step error
and no drain.

Each phase prints one JSON line. Any failed phase exits non-zero, and so
does a run that finds no TPU. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BLOCK = 16
SLOTS, CAPACITY = 8, 512
PROMPT, MAX_NEW = 128, 32
# max |got - exact| / max |exact| of one GEMM against a float64 product
# of the same f16 (or e4m3) operands. Exact products with f32
# accumulation over K <= 14336 terms stay far below this; a weight
# rounded to bf16 (2^-9) or an activation re-rounded to f16 (2^-12) would
# not.
GEMM_TOL = 1e-4
# max |a - b| / max |b| over first-step logits of two runs whose GEMMs
# differ only in summation order: Pallas vs the jnp oracle over the full
# 24-layer model, or a mesh vs one chip. Each GEMM pair differs by ~1e-7
# (GEMM_TOL's check), but through the depth that moves activations across
# the rounding boundary of the next GEMM's input: one f16 ulp (2^-11) in
# FP16 mode, one e4m3 step (2^-3) in FP8 mode. Measured growth with depth
# (CPU, interpret kernels, d_model 256): FP16 2e-4 / 5e-4 / 7e-4 and FP8
# 3e-7 / 4e-2 / 7e-2 at 2 / 8 / 24 layers.
LOGIT_TOL = {"fp16": 1e-2, "fp8": 1.5e-1}
# the kernel's f32 dots run at Mosaic's default MXU precision, whose bf16
# operand rounding (2^-9 relative) bounds the score and output error
ATTN_TOL = 1e-2


class PhaseFailed(RuntimeError):
    pass


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def make_requests(cfg, n: int, seed: int, tag: str, max_new: int = MAX_NEW):
    from repro.serving.engine import Request
    rng = np.random.RandomState(seed)
    return [Request(f"{tag}{i}",
                    [int(t) for t in rng.randint(1, cfg.vocab_size, PROMPT)],
                    max_new=max_new)
            for i in range(n)]


def serving_runtime(mode: str, **kw):
    """The Runtime `Engine` builds for its steps (f32 activations,
    per-token FP8 scales)."""
    import jax.numpy as jnp
    from repro.models.layers import Runtime
    return Runtime(mode=mode, dtype=jnp.float32, act_quant="per_token", **kw)


def compile_steps(eng, mode: str) -> tuple[float, int]:
    """AOT-compile an engine's own decode (8 x 1) and prefill (2 x 128)
    step programs, with its params, pool and mesh; returns (seconds,
    tpu_custom_call count in the decode step). The engine's first steps
    then find them in the persistent compilation cache."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from repro.core.compat import mesh_context
    from repro.serving.shard import replicated
    rep = None if eng.mesh is None else replicated(eng.mesh)
    caches = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        eng.caches)
    rt, cfg, mb = eng._rts[mode], eng.cfg, CAPACITY // BLOCK
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)

    def step(p, c, t, tab, qo, kvl):
        return eng._paged_step(rt, p, cfg, t, c, tab, q_offset=qo,
                               kv_len=kvl, block_size=BLOCK)
    secs, calls = 0.0, 0
    for rows, cols in ((SLOTS, 1), (2, PROMPT)):
        t0 = time.perf_counter()
        with (contextlib.nullcontext() if eng.mesh is None
              else mesh_context(eng.mesh)):
            compiled = jax.jit(step, donate_argnums=(1,)).lower(
                eng.params, caches, i32(rows, cols), i32(rows, mb),
                i32(rows), i32(rows)).compile()
        secs += time.perf_counter() - t0
        if cols == 1:
            calls = compiled.as_text().count("tpu_custom_call")
    return secs, calls


def check_gemms(cfg, seed: int, backends=("pallas", "ref")) -> None:
    """Every linear shape of `cfg` at a decode batch (M=8) and a prefill
    chunk (M=256), per GEMM backend, against a float64 host product of
    the same operands: FP16 mode on f16 activations, FP8 mode on per-token
    e4m3 activations (the scales the serving engine uses)."""
    import jax.numpy as jnp
    from repro.core import nestedfp as nf
    from repro.core.quant import quantize_act_per_token
    from repro.kernels import ops
    d, hd = cfg.d_model, cfg.resolved_head_dim
    shapes = sorted({(d, cfg.n_heads * hd), (d, cfg.n_kv_heads * hd),
                     (cfg.n_heads * hd, d), (d, cfg.d_ff), (cfg.d_ff, d)})
    rng = np.random.RandomState(seed)
    worst = {(mode, b): 0.0 for mode in ("fp16", "fp8") for b in backends}
    for k, n in shapes:
        w = (rng.normal(size=(k, n)) * k ** -0.5).astype(np.float16)
        upper, lower = nf.encode(jnp.asarray(w))
        w8 = np.asarray(nf.fp8_dequant(upper), np.float64)
        for m in (8, 256):
            x = rng.normal(size=(m, k)).astype(np.float16)
            xq, scale = quantize_act_per_token(jnp.asarray(x, jnp.float32))
            exact = {"fp16": x.astype(np.float64) @ w.astype(np.float64),
                     "fp8": (np.asarray(xq.astype(jnp.float32), np.float64)
                             @ w8) * np.asarray(scale, np.float64)}
            for b in backends:
                got = {"fp16": ops.matmul_nested_f16(
                           jnp.asarray(x), upper, lower, backend=b),
                       "fp8": ops.matmul_nested_fp8(
                           xq, upper, scale, backend=b)}
                for mode, y in got.items():
                    err = float(np.abs(np.asarray(y, np.float64)
                                       - exact[mode]).max()
                                / np.abs(exact[mode]).max())
                    worst[mode, b] = max(worst[mode, b], err)
    for mode in ("fp16", "fp8"):
        emit(phase=f"gemm_{mode}", shapes=shapes, rows=[8, 256],
             rel_err_vs_float64={b: worst[mode, b] for b in backends},
             tol=GEMM_TOL)
        for b in backends:
            check(worst[mode, b] <= GEMM_TOL,
                  f"{mode} GEMM ({b}): rel err {worst[mode, b]} > {GEMM_TOL}")


def first_logits(cfg, params, reqs, mode: str, backend=None,
                 mesh=None) -> np.ndarray:
    """First-step logits of one prefill of `reqs` (PROMPT tokens each),
    through the GEMM `backend` (None: the platform's), on one chip or,
    with `mesh`, as the engine's sharded step over params committed by
    `shard_serving_params`."""
    import contextlib
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.compat import mesh_context
    from repro.models import model as M
    from repro.serving import shard as SHARD
    rows = len(reqs)
    mb = -(-PROMPT // BLOCK)
    tokens = jnp.asarray([r.tokens for r in reqs], jnp.int32)
    tables = jnp.asarray(1 + np.arange(rows * mb).reshape(rows, mb),
                         jnp.int32)               # block 0 is the trash block
    zero = jnp.zeros((rows,), jnp.int32)
    full = jnp.full((rows,), PROMPT, jnp.int32)
    rt = serving_runtime(mode, backend=backend, mesh=mesh)
    caches = M.init_paged_cache(cfg, rows * mb + 1, BLOCK, mesh=mesh)
    step = M.paged_step if mesh is None \
        else functools.partial(SHARD.sharded_paged_step, mesh)
    with (contextlib.nullcontext() if mesh is None else mesh_context(mesh)):
        out = jax.jit(lambda p, c: step(
            rt, p, cfg, tokens, c, tables, q_offset=zero, kv_len=full,
            block_size=BLOCK, logit_position=full - 1,
            return_logits=True))(params, caches)[0]
    return np.asarray(out)


def compare_logits(phase: str, mode: str, got: dict) -> dict:
    """Two first-step logit arrays that differ only in the summation
    order of their GEMMs must agree within LOGIT_TOL[mode]."""
    (na, a), (nb, b) = got.items()
    err = float(np.abs(a - b).max() / np.abs(b).max())
    row = {"phase": phase, "compared": [na, nb], "rel_err": err,
           "tol": LOGIT_TOL[mode],
           "argmax_agree": float(np.mean(a.argmax(-1) == b.argmax(-1))),
           "finite": bool(np.isfinite(a).all() and np.isfinite(b).all())}
    emit(**row)
    check(row["finite"] and err <= LOGIT_TOL[mode],
          f"{phase}: {na} vs {nb} rel err {err} > {LOGIT_TOL[mode]}")
    return row


def check_logits(cfg, params, reqs, mode: str) -> dict:
    """Pallas vs the jnp oracle on the same chip."""
    return compare_logits(f"logits_{mode}", mode, {
        b: first_logits(cfg, params, reqs, mode, backend=b)
        for b in ("pallas", "ref")})


def check_planar_attention(cfg, seed: int) -> None:
    """Paged planar decode kernel vs a jnp reference (gather the blocks
    in logical order, join the byte planes, masked softmax)."""
    import jax
    import jax.numpy as jnp
    from repro.core.nestedfp import e5m2_view, join_bytes, split_bytes
    from repro.kernels.planar_decode_attention import (
        paged_planar_decode_attention)
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, mb = SLOTS, CAPACITY // BLOCK
    rng = np.random.RandomState(seed)
    nb = b * mb + 1
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, BLOCK, hkv, d)), jnp.float16)
    v = jnp.asarray(rng.normal(size=(nb, BLOCK, hkv, d)), jnp.float16)
    (k_hi, k_lo), (v_hi, v_lo) = split_bytes(k), split_bytes(v)
    tables = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb),
                         jnp.int32)
    lens = jnp.asarray(rng.randint(1, CAPACITY + 1, b), jnp.int32)
    for fp8 in (False, True):
        got = paged_planar_decode_attention(q, k_hi, k_lo, v_hi, v_lo,
                                            tables, lens, fp8=fp8)
        kk = e5m2_view(k_hi) if fp8 else join_bytes(k_hi, k_lo)
        vv = e5m2_view(v_hi) if fp8 else join_bytes(v_hi, v_lo)
        kk = kk.astype(jnp.float32)[tables].reshape(b, mb * BLOCK, hkv, d)
        vv = vv.astype(jnp.float32)[tables].reshape(b, mb * BLOCK, hkv, d)
        qg = q.reshape(b, hkv, h // hkv, d) * d ** -0.5
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhgd,bkhd->bhgk", qg, kk)
            s = jnp.where(jnp.arange(mb * BLOCK) < lens[:, None, None, None],
                          s, -1e30)
            want = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, -1),
                              vv).reshape(b, h, d)
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        mode = "fp8" if fp8 else "fp16"
        emit(phase=f"planar_attention_{mode}", rel_err=err, tol=ATTN_TOL)
        check(np.isfinite(got).all() and err <= ATTN_TOL,
              f"planar attention {mode}: rel err {err} > {ATTN_TOL}")


def token_agreement(a: dict, b: dict) -> float:
    """Share of equal tokens, position by position, of two runs' outputs
    keyed by request id."""
    return float(np.mean([x == y for k in a for x, y in zip(a[k], b[k])]))


def check_paged_attention(cfg, seed: int) -> None:
    """Plain-pool paged decode kernel vs a jnp reference over the same
    pool (gather the blocks in logical order, masked softmax)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode_attention import paged_decode_attention
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, mb = SLOTS, CAPACITY // BLOCK
    rng = np.random.RandomState(seed)
    nb = b * mb + 1
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, BLOCK, hkv * d)), jnp.float16)
    v = jnp.asarray(rng.normal(size=(nb, BLOCK, hkv * d)), jnp.float16)
    tables = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb),
                         jnp.int32)
    lens = jnp.asarray(rng.randint(1, CAPACITY + 1, b), jnp.int32)
    bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.int16)  # noqa: E731
    got = paged_decode_attention(q, bits(k), bits(v), tables, lens)
    kk = k.astype(jnp.float32)[tables].reshape(b, mb * BLOCK, hkv, d)
    vv = v.astype(jnp.float32)[tables].reshape(b, mb * BLOCK, hkv, d)
    qg = q.reshape(b, hkv, h // hkv, d) * d ** -0.5
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kk)
        s = jnp.where(jnp.arange(mb * BLOCK) < lens[:, None, None, None],
                      s, -1e30)
        want = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, -1),
                          vv).reshape(b, h, d)
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.abs(got - want).max() / np.abs(want).max())
    emit(phase="paged_attention", rel_err=err, tol=ATTN_TOL)
    check(np.isfinite(got).all() and err <= ATTN_TOL,
          f"paged attention: rel err {err} > {ATTN_TOL}")


def serve(cfg, params, reqs, phase: str, mode: str, **engine_kw) -> dict:
    """Serve `reqs` through a fresh Engine forced to `mode`, after
    compiling its step programs (`compile_steps`); every request must
    finish with exactly max_new in-vocabulary tokens, and the decode step
    must hold a Pallas kernel."""
    from repro.serving.engine import Engine
    eng = Engine(cfg, params, n_slots=SLOTS, capacity=CAPACITY,
                 block_size=BLOCK, forced_mode=mode, **engine_kw)
    compile_s, calls = compile_steps(eng, mode)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    fin = eng.run()
    run_s = time.perf_counter() - t0
    outs = {r.request_id: list(r.output) for r in fin}
    toks = [t for o in outs.values() for t in o]
    row = {"phase": phase, "mode": mode, "submitted": len(reqs),
           "finished": len(fin), "tokens": len(toks),
           "iterations": eng.iteration, "run_s": run_s,
           "compile_s": compile_s, "tpu_custom_calls": calls,
           "peak_bytes": peak_bytes()}
    check(calls > 0, f"{phase}: no tpu_custom_call in the served step")
    check(len(fin) == len(reqs), f"{phase}: {len(fin)}/{len(reqs)} finished")
    check(all(len(r.output) == r.max_new for r in reqs),
          f"{phase}: a request stopped short of max_new")
    check(all(0 <= t < cfg.vocab_size for t in toks),
          f"{phase}: token outside the vocabulary")
    row["outputs"] = outs
    return row


def one_chip(seed: int) -> None:
    import jax
    from repro.configs import get_arch
    from repro.models.convert import init_serving_params, serving_memory_bytes
    cfg = get_arch("qwen1.5-0.5b")
    t0 = time.perf_counter()
    params = init_serving_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    mem = serving_memory_bytes(params)
    emit(phase="build", arch=cfg.arch_id, n_layers=cfg.n_layers,
         d_model=cfg.d_model, n_heads=cfg.n_heads, d_ff=cfg.d_ff,
         vocab=cfg.vocab_size, nested_bytes=mem["nested_bytes"],
         other_bytes=mem["other_bytes"], build_s=time.perf_counter() - t0)
    reqs = make_requests(cfg, SLOTS, seed, "q")
    check_gemms(cfg, seed)
    for mode in ("fp16", "fp8"):
        check_logits(cfg, params, reqs, mode)
    check_planar_attention(cfg, seed)
    check_paged_attention(cfg, seed)
    served = {}
    compile_total = 0.0
    for phase, mode, planar in (("serve_fp16", "fp16", False),
                                ("serve_fp8", "fp8", False),
                                ("serve_planar_pallas_attn", "fp16", True)):
        row = serve(cfg, params, make_requests(cfg, SLOTS, seed, "q"), phase,
                    mode, kv_planar=planar, attn_backend="pallas")
        served[phase] = row.pop("outputs")
        compile_total += row["compile_s"]
        emit(**row)
        gc.collect()

    emit(phase="agreement",
         fp8_vs_fp16=token_agreement(served["serve_fp8"],
                                     served["serve_fp16"]),
         planar_vs_fp16=token_agreement(served["serve_planar_pallas_attn"],
                                        served["serve_fp16"]),
         compile_s_total=compile_total)


def four_chips(seed: int) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_arch
    from repro.launch.mesh import make_replica_meshes, make_serving_mesh
    from repro.models.convert import init_serving_params, serving_param_shapes
    from repro.serving.router import Router
    from repro.serving.shard import (serving_param_shardings,
                                     shard_serving_params)
    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, "
          f"found {len(jax.devices())}")
    mesh = make_serving_mesh(4)
    key = jax.random.PRNGKey(seed)

    def build(cfg):
        t0 = time.perf_counter()
        shardings = serving_param_shardings(serving_param_shapes(cfg), cfg,
                                            mesh)
        params = jax.block_until_ready(
            init_serving_params(key, cfg, shardings))
        emit(phase=f"build_{cfg.arch_id}_{cfg.n_layers}l",
             build_s=time.perf_counter() - t0, peak_bytes=peak_bytes())
        return params

    cfg = get_arch("llama3.1-8b")
    params = build(cfg)
    for mode in ("fp16", "fp8"):
        row = serve(cfg, params, make_requests(cfg, SLOTS, seed, "L"),
                    f"mesh4_{cfg.arch_id}_{mode}", mode, mesh=mesh)
        row.pop("outputs")
        emit(**row, n_layers=cfg.n_layers, chips=4)
    del params
    gc.collect()

    cut = dataclasses.replace(cfg, n_layers=2)
    p_mesh = shard_serving_params(build(cut), cut, mesh)
    p_one = jax.device_put(p_mesh, SingleDeviceSharding(jax.devices()[0]))
    for mode in ("fp16", "fp8"):
        reqs = make_requests(cut, SLOTS, seed, "C")
        compare_logits(f"depth_cut_logits_{mode}", mode, {
            "mesh4": first_logits(cut, p_mesh, reqs, mode, mesh=mesh),
            "chip1": first_logits(cut, p_one, reqs, mode)})
        outs = {}
        for where, p, m in (("mesh4", p_mesh, mesh), ("chip1", p_one, None)):
            row = serve(cut, p, make_requests(cut, SLOTS, seed, "C"),
                        f"depth_cut_{where}_{mode}", mode, mesh=m)
            outs[where] = row.pop("outputs")
            emit(**row, n_layers=cut.n_layers)
        same = outs["mesh4"] == outs["chip1"]
        emit(phase=f"depth_cut_{mode}", tokens_identical=same,
             token_agreement=token_agreement(outs["mesh4"], outs["chip1"]))
        # Row-parallel GEMMs (o, down) sum their four partial products in
        # another order than one chip does. FP16 rounding of the next
        # GEMM's input absorbs that; an e4m3 step (2^-3) can flip on it
        # and move greedy tokens, so FP8 is held to the logit bound.
        check(same or mode == "fp8",
              f"depth cut {mode}: mesh tokens differ from one chip")
    del p_mesh, p_one
    gc.collect()

    qcfg = get_arch("qwen1.5-0.5b")
    qparams = init_serving_params(key, qcfg)
    router = Router.build(qcfg, qparams, 4,
                          meshes=make_replica_meshes(4, 1),
                          engine_kwargs=dict(n_slots=SLOTS,
                                             capacity=CAPACITY,
                                             block_size=BLOCK))
    reqs = make_requests(qcfg, 4 * SLOTS, seed, "R", max_new=16)
    for r in reqs:
        router.submit(r)
    t0 = time.perf_counter()
    fin = router.run()
    st = router.stats()
    emit(phase="router_4x1_qwen", submitted=len(reqs), finished=len(fin),
         tokens=sum(len(r.output) for r in fin),
         run_s=time.perf_counter() - t0, steps=st["steps"],
         step_errors=st["step_errors"], failovers=st["failovers"],
         rebuilds=st["rebuilds"], lost=st["lost"], shed=st["shed"],
         replicas=st["replicas"], peak_bytes=peak_bytes())
    check(len(fin) == len(reqs) and st["lost"] == 0 and st["shed"] == 0,
          "router: not every request finished")
    check(st["step_errors"] == 0 and st["failovers"] == 0
          and st["rebuilds"] == 0, "router: a replica step failed or drained")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); this check runs only on a chip",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    emit(phase="start", compile_cache=enable_compile_cache(),
         jax=jax.__version__, chips=args.chips)
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit(phase="done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
