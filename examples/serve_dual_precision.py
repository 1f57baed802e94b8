"""Dual-precision serving demo (deliverable b): a bursty request stream
through the continuous-batching engine with the SLO controller flipping
between FP16 and FP8 per iteration — the paper's core serving story.

`--arch` selects any engine-served architecture: every family routes
through the same paged scheduling path via its cache descriptor — GQA
K/V blocks (qwen3-8b, the default), MLA latent blocks
(deepseek-v3-671b), hybrid shared-attn blocks + slot-resident SSM state
(zamba2-2.7b), pure SSM (mamba2-2.7b), or sliding-window GQA
(gemma3-1b: local-layer blocks are window-slide reclaimed
mid-generation while global-layer blocks stay pinned).

Run: PYTHONPATH=src python examples/serve_dual_precision.py \
         [--arch deepseek-v3-671b]
"""
import argparse

import numpy as np
import jax

from repro.configs import ARCHS
from repro.core.policy import DualPrecisionController, SLOConfig
from repro.models import model as M
from repro.models.convert import to_serving, serving_memory_bytes
from repro.serving.engine import Engine, Request

ap = argparse.ArgumentParser(
    formatter_class=argparse.RawDescriptionHelpFormatter,
    epilog="""\
one-dispatch engine steps
  Every engine iteration costs O(1) jitted dispatches however many
  sequences are prefilling or decoding: all planned prompt chunks fuse
  into ONE batched ragged paged_step (per-row q_offset/kv_len/
  logit_position carry the raggedness), block tables are DEVICE-resident
  (incremental jitted scatters on allocate/slide/COW instead of a full
  re-upload per step), and greedy sampling is fused into the step so
  decode pulls (B,) int32 token ids — not (B, vocab) logits — with one
  host sync at the end of the step. `benchmarks/bench_kernel_overhead.py`
  reports this as the engine_dispatch/* rows (prefill_dispatches_per_step
  == 1, table_h2d_bytes_per_decode_step << full table), consolidated
  into BENCH_results.json by `python -m benchmarks.run`.

--attn-backend selection
  (unset) the platform picks: pallas on a TPU, ref elsewhere
  ref     pure-jnp block-table gather attention (every family)
  pallas  single-token decode over a GQA pool runs in a block-table
          scalar-prefetch Pallas kernel that reads only each row's live
          blocks (kernels/paged_decode_attention.py; with --kv-planar,
          kernels/planar_decode_attention.py, whose fp8 mode DMAs ONLY
          the hi planes); gemma3 sliding windows ride a traced per-layer
          window operand. Prefill chunks and MLA keep the ref gather
          path. Interpret-mode (slow, exact) off-TPU.
""")
ap.add_argument("--arch", default="qwen3-8b", choices=sorted(ARCHS),
                help="architecture (reduced variant); any decoder-only "
                     "family serves through the paged engine")
ap.add_argument("--attn-backend", choices=["ref", "pallas"],
                help="paged decode attention backend (see epilog)")
ap.add_argument("--kv-planar", action="store_true",
                help="store GQA KV as NestedKV byte planes (fp8 decode "
                     "reads half the cache bytes)")
args = ap.parse_args()

cfg = ARCHS[args.arch].reduced()
params = M.init_params(jax.random.PRNGKey(0), cfg)
sparams = to_serving(params)
mem = serving_memory_bytes(sparams)
desc = M.cache_descriptor(cfg)
print(f"model: {cfg.arch_id}, serving bytes {mem['total_bytes']/2**20:.1f} MiB")
print(f"cache descriptor: {desc.kind}, {desc.bytes_per_token} paged B/token, "
      f"{desc.bytes_per_slot} slot-resident B/seq")

# a controller calibrated so a full batch trips the SLO guard
ctrl = DualPrecisionController(SLOConfig(tpot_ms=33.3, hysteresis_steps=3),
                               fp16_ms_per_token=0.8, fp8_ms_per_token=0.4,
                               fixed_overhead_ms=2.0)
eng = Engine(cfg, sparams, n_slots=8, capacity=128, controller=ctrl,
             attn_backend=args.attn_backend, kv_planar=args.kv_planar)

rng = np.random.RandomState(1)
# every request opens with the same system prompt — on prefix-cacheable
# descriptors (gqa/mla) the COW prefix cache shares those KV blocks
# across the whole burst (one prefill, N readers); recurrent descriptors
# recompute them (slot-resident state cannot be shared)
system_prompt = list(rng.randint(1, 500, 32))
# light phase: 3 requests; burst: 12 at once; light again
for i in range(3):
    eng.submit(Request(f"light{i}",
                       system_prompt + list(rng.randint(1, 500, 12)),
                       max_new=6))
eng.run(max_iters=40)
for i in range(12):
    eng.submit(Request(f"burst{i}",
                       system_prompt + list(rng.randint(1, 500, 48)),
                       max_new=8))
eng.run(max_iters=400)

hist = ctrl.history
print(f"iterations: {len(hist)}, fp16 fraction: {ctrl.fp16_time_fraction():.2f}")
print("mode trace:", "".join("H" if m == "fp16" else "8" for m in hist))
assert "fp8" in hist and "fp16" in hist, "controller must use both modes"
ps = eng.prefix_cache_stats()
print(f"prefix cache: hit rate {ps['hit_rate']:.2f}, "
      f"blocks saved {ps['blocks_saved']}, cow forks {ps['cow_forks']}")
windowed = any(g.window for g in desc.groups)
if desc.prefix_cacheable and not windowed:
    assert ps["blocks_saved"] > 0, "shared system prompt never hit the cache"
if windowed:
    # sliding-window archs: once a holder decodes past the window, the
    # shared prefix's local-layer lookback blocks are slide-freed (and
    # evicted from the index — matching them would be illegal), so the
    # reuse story here is mid-generation block reclamation instead
    print(f"sliding window: {eng.stats['window_reclaimed_blocks']} "
          f"local-layer blocks reclaimed mid-generation")
    assert eng.stats["window_reclaimed_blocks"] > 0, \
        "long decode never slid a local block"
st = eng.stats
steps = max(eng.iteration, 1)
print(f"dispatch accounting over {steps} steps: "
      f"{st['prefill_dispatches']/steps:.2f} prefill + "
      f"{st['decode_dispatches']/steps:.2f} decode + "
      f"{st['aux_dispatches']/steps:.2f} aux dispatches/step, "
      f"{(st['h2d_bytes'] + eng.blocks.table_h2d_bytes)/steps:.0f} "
      f"h2d B/step")
print("finished requests:", len(eng.finished))
