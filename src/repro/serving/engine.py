"""Continuous-batching serving engine with per-iteration dual precision.

ORCA-style iteration-level scheduling on a BLOCK-PAGED KV cache: each
engine step (a) schedules prompt-prefill CHUNKS up to a bounded token
budget — interleaved with decode so a long queued prompt no longer
stalls every active decode's TPOT — and (b) advances all active slots by
one token (batched decode). Admission is driven by free KV blocks rather
than free slots; when decode growth exhausts the pool, the youngest
sequence is preempted (blocks released, request requeued for recompute).
The DualPrecisionController picks FP16 or FP8 per iteration; because
NestedFP serves both precisions from the same weight buffers the switch
costs nothing — the engine simply dispatches to the other pre-compiled
executable (paper §5.3 "per-iteration precision switching"), and the
measured wall time of every step feeds the controller's p90 tracker.

EVERY decoder-only family runs the paged path — there is ONE scheduling
path. Cache layouts are per-family descriptors (kvcache.py
`CacheDescriptor`): GQA K/V planes (incl. the byte-planar NestedKV
layout on paged blocks), MLA `c_kv`+`k_rope` latent planes (absorbed
latent attention over gathered blocks), and hybrid/ssm descriptors that
pair paged shared-attention planes with slot-resident Mamba2 state
(claimed per-slot via SlotManager in lockstep with the block tables and
zeroed at (re-)admission). Because MLA latent and hybrid shared-attn
blocks live in the same pool, the controller's `free_block_frac` FP8
trigger sees deepseek/zamba-class memory pressure too. The legacy
fixed-slot scheduling path (`_admit_legacy`/`_decode_legacy`) is
retired.

Recurrent families (ssm/hybrid) prefill with EXACT-length chunks (pad
tokens would be absorbed into the state) and disable prefix caching (a
cached KV prefix cannot stand in for slot-resident SSM state); batched
decode masks state writes on inactive rows.

Sliding-window archs (gemma3's 5:1 local:global layout) serve with one
block table PER WINDOW GROUP: local-layer blocks that slide fully out
of every future query's window are freed back to the pool mid-
generation (`BlockManager.slide_window`, invoked on every ensure) while
global-layer blocks stay pinned, so `free_block_frac` — and with it the
controller's memory-pressure FP8 trigger and the admission watermark —
reflects HONEST headroom instead of phantom pressure from dead
local-layer KV. Prefix matching is group-aware: global groups match the
full from-root chain, local groups only need (and only attach) the
blocks covering the resume position's lookback window.
`window_reclaim=False` keeps the group split but never slides — the
every-block-resident baseline the tests compare against.

Copy-on-write prefix caching (gqa/mla, on by default): at admission
the engine matches the longest cached full-block prefix of the request's
token stream (kvcache.py chain-hash index), attaches those blocks with
zero recompute, and starts chunked prefill at the matched offset —
always recomputing at least the final prompt token so the first-token
logit is produced. Before any chunk or decode write lands, shared
write-target blocks are COW-forked (`cow_for_write`) and their bytes
copied in the physical pool by one jitted block-copy; retire/preempt
decref blocks instead of freeing them, parking reusable prefixes in an
LRU pool that is reclaimed before preemption ever triggers. The paged
attention read path gathers keys through the block table in logical
order, so shared physical blocks are transparent to `paged_step` and the
planar decode kernel alike. `prefix_cache_stats()` reports hit-rate and
blocks saved.

N-gram speculative decoding (opt-in via `speculate=`): each decode row
may carry up to K drafted tokens proposed by a host-side suffix n-gram
match over the request's OWN token history (serving/speculate.py — no
draft model, no extra dispatch). The batched decode then runs as one
ragged C=K+1 `paged_step` chunk with per-column greedy argmax
(`sample_all=True`), and the longest accepted draft prefix is selected
ON DEVICE next to the fused sampling — the end-of-step sync pulls a
single packed `[ids | n_accepted]` array, so speculation adds zero host
syncs. Rejected draft positions are rolled back by pure block
bookkeeping (`BlockManager.truncate`: rejected writes only ever land in
COW-exclusive unregistered tail blocks, so garbage beyond the accepted
length is masked by kv_len and overwritten before it could become
valid), and the per-row draft length adapts to the measured acceptance
rate (`core.policy.AdaptiveKController` on the same `StepObservation`
stream the precision controller reads). Drafting is opportunistic and
NEVER preempts: draft extensions are clamped to `max_coverable` and
given back (truncate) if their COW fork cannot complete. Greedy outputs
are BIT-IDENTICAL with speculation on or off — drafts only decide how
many tokens one dispatch confirms, never which tokens. Recurrent
descriptors reject speculation (slot-resident SSM state cannot roll
back).

Greedy sampling; attention-family chunk lengths are bucketed and jit
caches key on (mode, bucket) with positions and slot index passed as
traced arguments, so distinct prompt lengths share one executable per
bucket (recurrent families compile per exact chunk length instead).

One-dispatch steps (host-orchestration overhead)
------------------------------------------------
The per-step host work is O(1) jitted dispatches and O(changed bytes)
host→device traffic, independent of how many sequences are prefilling
or decoding:

* ALL of a step's planned prompt chunks run as ONE batched ragged
  `paged_step` dispatch (attention-family descriptors): chunk rows are
  right-padded to a shared bucket, row count is bucketed to a power of
  two, and per-row `q_offset`/`kv_len`/`logit_position` carry the
  raggedness — executables key on (mode, rows-bucket, chunk-bucket),
  i.e. the total-chunk bucket. Disabled pad rows (kv_len=0) write to
  the trash block. Recurrent descriptors keep per-chunk dispatches
  (exact-length chunks + single-slot state routing).
* Block tables live on DEVICE (`BlockManager.device_tables()`): each
  dispatch reads the persistent mirror, and allocate/ensure/slide/COW
  mutations flush as one small jitted scatter instead of re-uploading
  the (G, n_slots, MB) array every step.
* Sampling is fused into the jitted step (`paged_step` returns argmax
  token ids), so decode pulls (B,) int32s back — not (B, vocab) floats
  — and the step's device results are synced ONCE at the end
  (`_finalize_step`); no `np.asarray` on live device values mid-step.
  A prefill that completes mid-step hands its on-device first token to
  the same step's decode through a tiny jitted overlay, never a sync.
* Caches are donated to every step dispatch, so XLA updates pools in
  place rather than copying them per step.

`stats` counts `prefill_dispatches`/`decode_dispatches`/
`aux_dispatches` and `h2d_bytes`; `benchmarks/bench_kernel_overhead.py`
turns them into the `engine_dispatch/*` rows the CI smoke asserts.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.compat import mesh_context
from repro.core.policy import (AdaptiveKController, DualPrecisionController,
                               RestorePolicy, SpeculationConfig,
                               StepObservation)
from repro.models import model as M
from repro.models.layers import Runtime
from repro.serving import shard as SHARD
from repro.serving.kvcache import (TRASH_BLOCK, BlockManager, HostPool,
                                   SlotManager)
from repro.serving.speculate import NgramProposer


@dataclasses.dataclass
class Request:
    request_id: str
    tokens: list[int]
    max_new: int
    arrival_s: float = 0.0
    # generation stops the step AFTER one of these ids is emitted (the
    # stop token itself is kept in `output`, EOS-style); an accepted
    # speculative run is cut at the first stop token mid-run
    stop_tokens: tuple[int, ...] = ()
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    first_token_s: float | None = None
    finished_s: float | None = None
    token_times: list[float] = dataclasses.field(default_factory=list)
    modes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Prefill:
    """In-flight chunked prefill. seq_tokens is the full token stream to
    re-establish in the cache — prompt plus any output generated before a
    preemption (greedy decoding makes the recompute continuation exact)."""
    req: Request
    seq_tokens: list[int]
    done: int = 0


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


# placeholder for a token whose value still lives on device; patched by
# `_finalize_step`'s single end-of-step sync before anything reads it
_PENDING = -1


class Engine:
    def __init__(self, cfg: ArchConfig, serving_params, *, n_slots: int,
                 capacity: int, controller: DualPrecisionController | None = None,
                 forced_mode: str | None = None, backend: str | None = None,
                 attn_backend: str = "ref",
                 kv_planar: bool = False,
                 clock: Callable[[], float] = time.monotonic,
                 block_size: int = 16,
                 n_blocks: int | None = None, chunk_tokens: int = 256,
                 prefix_cache: bool = True, window_reclaim: bool = True,
                 debug_invariants: bool = False, mesh=None,
                 speculate: SpeculationConfig | bool | None = None,
                 host_offload: bool = True,
                 host_bytes: int | None = None,
                 restore_policy: RestorePolicy | None = None,
                 persist_dir: str | None = None,
                 fault_hook: Callable[["Engine"], None] | None = None):
        # mesh (launch.mesh.make_serving_mesh): drive an N-chip
        # tensor-parallel mesh as ONE logical device — weights and the
        # paged pool are committed to sharded layouts here (serving/
        # shard.py axis table) and every step stays a single pjit
        # dispatch whose partitioning GSPMD derives from them. None
        # preserves single-device serving byte-for-byte.
        self.cfg = cfg
        self.mesh = mesh
        self.params = serving_params if mesh is None \
            else SHARD.shard_serving_params(serving_params, cfg, mesh)
        self.controller = controller
        self.forced_mode = forced_mode
        self.clock = clock
        self.n_slots = n_slots
        self.capacity = capacity
        self.chunk_tokens = chunk_tokens
        # opt-in runtime sanitizer (Engine(debug_invariants=True) or
        # NFP_DEBUG=1): audit the BlockManager's refcount/free-list/
        # table-mirror invariants after every step instead of only where
        # a test remembers to call check_invariants()
        self.debug_invariants = debug_invariants \
            or os.environ.get("NFP_DEBUG") == "1"
        self.kv_planar = kv_planar and cfg.cache_kind == "gqa"
        # raises NotImplementedError for enc-dec — engine serves
        # decoder-only archs (enc-dec is covered by dry-run + benchmarks)
        self.desc = M.cache_descriptor(cfg, planar=self.kv_planar)
        # recurrent state can't be re-attached from cached KV blocks
        prefix_cache = prefix_cache and self.desc.prefix_cacheable
        # pad tokens are invisible to attention (causal mask + trash
        # block) but would be absorbed into SSM state: recurrent
        # families prefill with exact-length chunks instead of buckets
        self._pad_chunks = not self.desc.slot_planes
        # n-gram speculative decoding (module docstring): True picks the
        # default SpeculationConfig; rejected-draft rollback is pure
        # block bookkeeping, which slot-resident recurrent state cannot
        # provide — advancing an SSM recurrence is irreversible
        if speculate:
            if self.desc.slot_planes:
                raise ValueError(
                    "speculative decoding requires rolling back rejected "
                    "positions; slot-resident recurrent state (ssm/hybrid "
                    "descriptors) cannot be truncated")
            self._spec = speculate if isinstance(speculate, SpeculationConfig) \
                else SpeculationConfig()
            self._proposer = NgramProposer(self._spec)
            self._spec_k = AdaptiveKController(self._spec)
        else:
            self._spec = None
            self._proposer = None
            self._spec_k = None
        self._spec_cache: dict[tuple[str, int], Any] = {}
        self._last_spec = (0, 0)     # (drafted, accepted) of the last step
        self.queue: collections.deque[Request] = collections.deque()
        self.active: dict[int, Request] = {}
        self.prefilling: dict[int, _Prefill] = {}
        self.finished: list[Request] = []
        self.lens = np.zeros(n_slots, np.int32)
        self.stats = {"preemptions": 0, "chunks": 0, "chunk_tokens": 0,
                      "peak_block_util": 0.0, "window_reclaimed_blocks": 0,
                      # one-dispatch accounting (bench_kernel_overhead
                      # engine_dispatch/* rows): jitted calls per phase
                      # plus host->device bytes for step inputs (block
                      # tables are counted by BlockManager separately)
                      "prefill_dispatches": 0, "decode_dispatches": 0,
                      "aux_dispatches": 0, "h2d_bytes": 0,
                      # speculative decoding (spec_stats() / bench
                      # spec/* rows): decode_rows counts row-dispatches,
                      # decode_tokens the tokens they emitted — their
                      # ratio is tokens-accepted-per-dispatch (1.0
                      # without speculation, >1 iff drafts accepted)
                      "spec_dispatches": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "decode_rows": 0,
                      "decode_tokens": 0,
                      # tiered KV (tiered_stats()): blocks/bytes spilled
                      # to the host tier, restored through the scatter
                      # path, lazily lo-plane-completed, admissions that
                      # fell back to recompute under the SLO guard, and
                      # the run() iteration-cap satellite counter — all
                      # host-side bookkeeping, so mesh-size-invariant
                      "spilled_blocks": 0, "spilled_bytes": 0,
                      "restored_blocks": 0, "restored_bytes": 0,
                      "lo_lazy_blocks": 0, "lo_lazy_bytes": 0,
                      "restore_fallbacks": 0, "iters_exhausted": 0,
                      # host-tier entries whose checksum failed at
                      # restore-drain time: the owning rows were
                      # preempted back to recompute (never served
                      # corrupt KV, never crashed)
                      "corrupt_fallbacks": 0}
        self._last_step_ms: float | None = None
        # failure-injection seam (serving/faults.py): called at the very
        # top of _step_inner, BEFORE any state mutates — an InjectedFault
        # raised here leaves the engine drainable. Stall faults add
        # virtual milliseconds to the step instead of raising:
        # inject_stall_ms is consumed into _last_step_ms (so the
        # dual-precision controller sees the slowdown) and surfaced to
        # the router as last_stall_ms.
        self.fault_hook = fault_hook
        self.inject_stall_ms = 0.0
        self.last_stall_ms = 0.0
        self.last_mode: str | None = None
        # backend=None lets the platform pick the GEMMs
        # (ops.default_backend): the NestedFP Pallas kernels on TPU, the
        # jnp oracle on CPU.
        # attn_backend="pallas" serves planar GQA decode through the
        # block-table scalar-prefetch kernel (layers.attention "paged");
        # anything it cannot serve falls back to the ref gather path.
        # act_quant="per_token": fp8 generation must be batch-invariant
        # under continuous batching (and speculative verification chunks)
        # — per-tensor dynamic scales would couple co-batched tokens'
        # rounding (Runtime docstring).
        self._rts = {m: Runtime(mode=m, backend=backend, dtype=jnp.float32,
                                act_quant="per_token",
                                attn_backend=None if attn_backend == "ref"
                                else attn_backend, mesh=mesh)
                     for m in ("fp16", "fp8")}
        self.block_size = block_size
        mbs = -(-capacity // block_size)
        # per-layer-group window metadata: sliding-window archs (gemma3)
        # keep one block table per group — each group allocates from its
        # own id space over the same pool array (a layer only touches
        # its group's rows of a block), so local-layer blocks can be
        # slide-freed mid-generation while global-layer blocks stay
        # pinned, at zero extra pool bytes; window_reclaim=False keeps
        # the group split but never slides (the
        # every-block-resident-forever baseline)
        gw = self.desc.group_windows
        if not window_reclaim:
            gw = (None,) * len(gw)
        if n_blocks is None:
            n_blocks = n_slots * mbs         # dense-equivalent pool by default
        # tiered KV (kvcache.py HostPool): spill LRU-evicted prefix
        # blocks to a host pool instead of discarding them, restore
        # matched blocks through the scatter-upload path under the
        # RestorePolicy SLO guard, and (persist_dir) serialize index +
        # host pool across engine restarts. Only prefix-cacheable paged
        # families participate — recurrent state cannot be re-attached.
        self._host_tier = bool(host_offload and prefix_cache
                               and self.desc.paged
                               and not self.desc.slot_planes)
        self._restore_policy = restore_policy or RestorePolicy()
        self.persist_dir = persist_dir
        self.blocks = BlockManager(n_slots, block_size, n_blocks, mbs,
                                   prefix_cache=prefix_cache,
                                   group_windows=gw,
                                   mirror_sharding=None if mesh is None
                                   else SHARD.replicated(mesh),
                                   host_pool=HostPool(host_bytes)
                                   if self._host_tier else None)
        # slot-resident state side (hybrid/ssm descriptors): SlotManager
        # tracks per-slot occupancy in lockstep with the block tables
        self.slot_state = SlotManager(n_slots, capacity) \
            if self.desc.slot_planes else None
        self.caches = M.init_paged_cache(
            cfg, self.blocks.n_total_blocks, block_size, n_slots=n_slots,
            planar=self.kv_planar, mesh=mesh)
        # the step entry point: identical call signature either way, so
        # the dispatch sites below never branch on the mesh. Sharded
        # mode routes through serving/shard.sharded_paged_step (a
        # repro-lint hot root), which pins the tiny control operands
        # replicated and leaves pool/weight partitioning to GSPMD.
        self._paged_step = M.paged_step if mesh is None \
            else functools.partial(SHARD.sharded_paged_step, mesh)
        # one compile per window group: src/dst are traced scalars into
        # the block axis; donating the cache lets XLA update the one
        # block in place instead of materializing a whole-pool copy per
        # COW fork. Only paged-plane subtrees are touched —
        # slot-resident state ("ssm") has a slot axis, not a block
        # axis. With per-group block id spaces a fork must copy ONLY
        # the group's layer rows: the same physical id may be live in
        # the other group with unrelated content.
        def _make_copy(layers):
            if layers is None:               # single group: all layers
                cp = lambda a, s, d: a.at[:, d].set(a[:, s])
            else:
                li = jnp.asarray(layers, jnp.int32)
                cp = lambda a, s, d: a.at[li, d].set(a[li, s])
            return jax.jit(
                lambda c, s, d: {
                    k: (jax.tree.map(lambda a: cp(a, s, d), sub)
                        if k in ("attn", "shared") else sub)
                    for k, sub in c.items()},
                donate_argnums=(0,))
        if self.desc.groups:
            self._copy_block = {gi: _make_copy(g.layers)
                                for gi, g in enumerate(self.desc.groups)}
        else:
            self._copy_block = {0: _make_copy(None)}
        # tiered-KV executables: per window group, ONE jitted pool
        # gather (spill capture: d2h of K evicted blocks' plane bytes)
        # and ONE jitted pool scatter per plane set (restore upload —
        # the same dirty-scatter discipline the block tables use). Block
        # counts are padded to a power of two (gather pads repeat the
        # last id; scatter pads aim at the trash block — both
        # idempotent), so a handful of executables serve every drain.
        # Planar (NestedKV) pools split the plane set: fp8 hi planes
        # upload eagerly at restore, lo planes lazily on the first
        # FP16-mode touch — half the restore h2d while serving fp8.
        if self._host_tier:
            pool_key = "shared" if self.desc.kind == "hybrid" else "attn"
            names = tuple(p.name for p in self.desc.planes)
            self._lo_planes = tuple(n for n in names if n.endswith("_lo")) \
                if self.kv_planar else ()
            self._hi_planes = tuple(n for n in names
                                    if n not in self._lo_planes)

            def _make_tier(layers):
                if layers is None:
                    sel = lambda a, ids: a[:, ids]
                    put = lambda a, ids, v: a.at[:, ids].set(v)
                else:
                    li = jnp.asarray(layers, jnp.int32)
                    sel = lambda a, ids: a[li[:, None], ids[None, :]]
                    put = lambda a, ids, v: \
                        a.at[li[:, None], ids[None, :]].set(v)
                gather = jax.jit(lambda c, ids: {
                    p: sel(a, ids) for p, a in c[pool_key].items()})

                def make_scatter(plane_names):
                    pn = tuple(plane_names)

                    def f(c, ids, vals):
                        sub = dict(c[pool_key])
                        for p in pn:
                            sub[p] = put(sub[p], ids, vals[p])
                        out = dict(c)
                        out[pool_key] = sub
                        return out
                    return jax.jit(f, donate_argnums=(0,))
                return gather, make_scatter
            glayers = [g.layers for g in self.desc.groups] \
                if self.desc.groups else [None]
            self._spill_gather, self._scatter_hi, self._scatter_lo = {}, {}, {}
            self._eager_block_bytes, self._lo_block_bytes = {}, {}
            by_name = {p.name: p for p in self.desc.planes}
            for gi, lys in enumerate(glayers):
                gather, make_scatter = _make_tier(lys)
                self._spill_gather[gi] = gather
                self._scatter_hi[gi] = make_scatter(self._hi_planes)
                if self._lo_planes:
                    self._scatter_lo[gi] = make_scatter(self._lo_planes)
                nl = len(lys) if lys is not None else self.desc.planes[0].n_layers

                def pbytes(pl):
                    return sum(int(nl * block_size
                                   * np.prod(by_name[p].token_shape,
                                             dtype=np.int64)
                                   * np.dtype(by_name[p].dtype).itemsize)
                               for p in pl)
                self._eager_block_bytes[gi] = pbytes(self._hi_planes)
                self._lo_block_bytes[gi] = pbytes(self._lo_planes)
        if self.slot_state is not None:
            # zero one slot's recurrent state at (re-)admission
            self._zero_slot = jax.jit(
                lambda c, i: {
                    k: (jax.tree.map(lambda a: a.at[:, i].set(0), sub)
                        if k == "ssm" else sub)
                    for k, sub in c.items()},
                donate_argnums=(0,))
        # batched decode: greedy sampling fused into the step (returns
        # (n_slots,) int32 ids, not (B, vocab) logits); caches donated so
        # pools update in place
        self._decode = {
            m: jax.jit(lambda p, c, t, tab, qo, kvl, _m=m:
                       self._paged_step(
                self._rts[_m], p, cfg, t, c, tab, q_offset=qo,
                kv_len=kvl, block_size=block_size), donate_argnums=(1,))
            for m in ("fp16", "fp8")}
        self._chunk_cache: dict[tuple[str, int], Any] = {}
        self._fused_cache: dict[tuple[str, int, int], Any] = {}
        # scatter a completing prefill's on-device first token into the
        # same step's decode inputs (no host sync on the seam)
        self._overlay = jax.jit(lambda t, s, ids, r: t.at[s, 0].set(ids[r]))
        self.iteration = 0
        if self._host_tier and persist_dir:
            self._load_prefix_store(persist_dir)

    # -- public API -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue one request, validating it up front: a malformed
        request must fail HERE with a clear error, not steps later as a
        scheduling failure deep inside `_plan_chunks`/`try_allocate`."""
        if not req.tokens:
            raise ValueError(f"request {req.request_id}: empty prompt")
        if req.max_new <= 0:
            raise ValueError(
                f"request {req.request_id}: max_new={req.max_new} must be "
                f"positive — a request that may emit nothing can never "
                f"retire")
        total = len(req.tokens) + req.max_new
        if total > self.capacity:
            raise ValueError(
                f"request {req.request_id}: prompt ({len(req.tokens)}) + "
                f"max_new ({req.max_new}) = {total} exceeds per-sequence "
                f"capacity {self.capacity}")
        bm = self.blocks
        if any(bm._group_need(total, w) > bm.n_blocks
               for w in bm.group_windows):
            raise ValueError(
                f"request {req.request_id}: needs more KV blocks than a "
                f"whole group pool holds ({bm.n_blocks}) — the pool can "
                f"never cover it")
        self.queue.append(req)

    def drain_requests(self) -> list[Request]:
        """Evacuate every in-flight request (admission order, then
        queue order), releasing all KV blocks and slots — the router's
        failover export. Outputs are sanitized (a trailing `_PENDING`
        placeholder from an interrupted step is dropped along with its
        timing/mode entries) so a survivor can resubmit each request
        as-is: re-prefilling prompt + emitted-so-far continues greedy
        generation exactly (`_plan_chunks` replay invariant)."""
        order = sorted(set(self.active) | set(self.prefilling),
                       key=lambda i: self.blocks.seqs[i].admitted)
        out: list[Request] = []
        for idx in order:
            if idx in self.active:
                out.append(self.active.pop(idx))
            else:
                out.append(self.prefilling.pop(idx).req)
            self.blocks.release(idx)
            if self.slot_state is not None:
                self.slot_state.release(idx)
            self.lens[idx] = 0
        out.extend(self.queue)
        self.queue.clear()
        for req in out:
            while req.output and req.output[-1] == _PENDING:
                req.output.pop()
                if req.token_times:
                    req.token_times.pop()
                if req.modes:
                    req.modes.pop()
            if not req.output:
                req.first_token_s = None     # the dropped placeholder was
                                             # the "first token"
        return out

    def run(self, max_iters: int = 10_000,
            allow_partial: bool = False) -> list[Request]:
        """Step until every submitted request finishes. Hitting
        `max_iters` with work still queued/active is an ERROR unless
        `allow_partial=True` — a silently-truncated run used to let
        benches report a partially-served trace as complete. Either way
        `stats["iters_exhausted"]` records how many requests were left
        unserved when the cap hit."""
        while (self.queue or self.active or self.prefilling) \
                and self.iteration < max_iters:
            self.step()
        leftover = len(self.queue) + len(self.active) + len(self.prefilling)
        if leftover:
            self.stats["iters_exhausted"] = leftover
            if not allow_partial:
                raise RuntimeError(
                    f"run(max_iters={max_iters}) exhausted its iteration "
                    f"cap with {leftover} requests unfinished; pass "
                    f"allow_partial=True to accept a partially-served "
                    f"trace")
        return self.finished

    def block_utilization(self) -> float:
        return self.blocks.utilization()

    def prefix_cache_stats(self) -> dict:
        """Prefix-cache effectiveness: hit rate over prompt tokens looked
        up at admission, blocks saved by sharing, COW forks, LRU churn
        (all-zero for recurrent descriptors, which disable the cache)."""
        ps = self.blocks.prefix_stats
        denom = ps["lookup_tokens"]
        return {"hit_rate": ps["hit_tokens"] / denom if denom else 0.0,
                "hit_tokens": ps["hit_tokens"],
                "blocks_saved": ps["blocks_shared"],
                "cached_blocks": self.blocks.n_cached_blocks(),
                "cow_forks": ps["cow_forks"],
                "evictions": ps["evictions"]}

    def spec_stats(self) -> dict:
        """Speculation effectiveness. `tokens_accepted_per_dispatch` is
        the per-row mean tokens confirmed by one decode dispatch: exactly
        1.0 without speculation, > 1 iff drafts were accepted. All ratios
        guard their denominators — a trace that never decoded (or never
        drafted) reports 0.0, it does not raise."""
        s = self.stats
        return {"enabled": self._spec is not None,
                "spec_dispatches": s["spec_dispatches"],
                "drafted": s["spec_drafted"],
                "accepted": s["spec_accepted"],
                "acceptance_rate": s["spec_accepted"] / s["spec_drafted"]
                if s["spec_drafted"] else 0.0,
                "tokens_accepted_per_dispatch":
                s["decode_tokens"] / s["decode_rows"]
                if s["decode_rows"] else 0.0,
                "k": self._spec_k.k if self._spec_k else 0}

    @property
    def restore_policy(self) -> RestorePolicy:
        """The live SLO guard on the tiered-KV restore path — swappable
        at runtime (the router's DegradePolicy tightens it on survivors
        while the fleet runs short-handed, and restores it after)."""
        return self._restore_policy

    @restore_policy.setter
    def restore_policy(self, policy: RestorePolicy) -> None:
        self._restore_policy = policy

    # -- tiered KV: spill / restore / persist ---------------------------------
    def tiered_stats(self) -> dict:
        """Host-tier effectiveness: blocks spilled (d2h captures),
        restored (scatter uploads), lazily lo-completed, admissions the
        SLO guard bounced to recompute, and current tier occupancy."""
        s, bm = self.stats, self.blocks
        host = bm.host
        return {"enabled": self._host_tier,
                "host_blocks": len(host) if host is not None else 0,
                "host_bytes": host.bytes if host is not None else 0,
                "spilled_blocks": s["spilled_blocks"],
                "spilled_bytes": s["spilled_bytes"],
                "restored_blocks": s["restored_blocks"],
                "restored_bytes": s["restored_bytes"],
                "lo_lazy_blocks": s["lo_lazy_blocks"],
                "lo_lazy_bytes": s["lo_lazy_bytes"],
                "restore_fallbacks": s["restore_fallbacks"],
                "host_hit_blocks": bm.prefix_stats["host_hit_blocks"],
                "queued_restores": len(bm.restore_jobs)}

    def _tier_dev(self, a: np.ndarray):
        """Device placement for tiny host-built spill/restore operands
        (block ids, stacked plane values): replicated under a mesh so
        GSPMD never tries to partition control data."""
        if self.mesh is None:
            return jnp.asarray(a)
        return SHARD.put_replicated(self.mesh, a)

    def _capture_blocks(self, jobs: list[tuple[int, int, int]]) -> None:
        """Copy (group, block, hash) pool bytes into the host tier: one
        jitted per-group gather (ids padded to a power of two by
        repeating the last id — idempotent), then a single batched d2h
        pull per group. Used by `_flush_spills` (eviction/preemption
        spills) and `save_prefix_store` (non-evicting index mirror)."""
        bm = self.blocks
        by_g: dict[int, list[tuple[int, int]]] = {}
        for g, b, h in jobs:
            by_g.setdefault(g, []).append((b, h))
        for g, items in sorted(by_g.items()):
            kb = _bucket(len(items), 1)
            ids = np.full(kb, items[-1][0], np.int32)
            for i, (b, _h) in enumerate(items):
                ids[i] = b
            out = self._spill_gather[g](self.caches, self._tier_dev(ids))
            # nfp: ignore[NFP001] tiered-KV spill capture: batched d2h of evicted cold blocks, an aux transfer that never sits on the step's argmax sync
            planes = jax.device_get(out)
            for i, (_b, h) in enumerate(items):
                entry = {p: np.ascontiguousarray(a[:, i])
                         for p, a in planes.items()}
                bm.store_spill(g, h, entry)
                self.stats["spilled_blocks"] += 1
                self.stats["spilled_bytes"] += sum(
                    a.nbytes for a in entry.values())
            self.stats["aux_dispatches"] += 1

    def _flush_spills(self) -> None:
        """Capture every queued evicted-block spill to the host tier.
        MUST run before any cache-writing dispatch: the evicted block
        ids are already reallocated, so their bytes are intact only
        until the next write lands. No-op when nothing is queued."""
        if not self._host_tier:
            return
        jobs = self.blocks.take_spills()
        if jobs:
            self._capture_blocks(jobs)

    def _tier_upload(self, g: int, items: list[tuple[int, int]],
                     names: tuple[str, ...]) -> int:
        """Scatter host-tier bytes for `names` planes of [(block, hash)]
        `items` into group g's pool rows (one jitted donated scatter —
        the same upload path the device table mirror uses). Pad slots
        aim at the trash block. Returns bytes shipped."""
        bm = self.blocks
        kb = _bucket(len(items), 1)
        ids = np.full(kb, TRASH_BLOCK, np.int32)
        vals: dict[str, np.ndarray] = {}
        nbytes = 0
        for i, (b, h) in enumerate(items):
            ids[i] = b
            entry = bm.host.get((g, h))
            for p in names:
                a = entry[p]
                if p not in vals:
                    vals[p] = np.zeros((a.shape[0], kb) + a.shape[1:],
                                       a.dtype)
                vals[p][:, i] = a
                nbytes += a.nbytes
        self.caches = (self._scatter_hi if names == self._hi_planes
                       else self._scatter_lo)[g](
            self.caches, self._tier_dev(ids),
            {p: self._tier_dev(v) for p, v in vals.items()})
        self.stats["aux_dispatches"] += 1
        return nbytes

    def _restore_queued_bytes(self) -> int:
        """Eager (hi-plane) bytes waiting in the restore queue — the
        backlog the RestorePolicy's admission gate reads."""
        return sum(self._eager_block_bytes[g]
                   for g, _b, _h, _t in self.blocks.restore_jobs)

    def _host_admit(self) -> bool:
        """May this admission match host-tier blocks? The SLO guard
        bounces the match to plain recompute when the restore backlog
        would blow TPOT (`stats["restore_fallbacks"]`)."""
        if not self._host_tier:
            return False
        bm = self.blocks
        if not (len(bm.host) or bm._spill_pending):
            return True                      # nothing to restore anyway
        if self._restore_policy.admit(self._restore_queued_bytes()):
            return True
        self.stats["restore_fallbacks"] += 1
        return False

    def _drain_restores(self) -> None:
        """Upload queued host-tier restores at the top of the step,
        bounded by the RestorePolicy's per-step byte grant (always at
        least one block, so gated rows make progress — the guard shapes
        latency, it cannot deadlock). Spill captures run first: a
        restore may target an entry whose bytes are still queued for
        capture."""
        bm = self.blocks
        if not self._host_tier or not bm.restore_jobs:
            return
        self._flush_spills()
        budget = self._restore_policy.grant(self._restore_queued_bytes())
        taken: dict[int, list[tuple[int, int]]] = {}
        spent = 0
        while bm.restore_jobs:
            g, b, h, t = bm.restore_jobs[0]
            if not bm.claim_restore(g, b, h, t):
                bm.restore_jobs.popleft()    # voided by release/preempt
                continue
            if not bm.host_ok(g, h):
                # checksum mismatch: never scatter these bytes — preempt
                # the owners back to recompute and drop the entry
                bm.restore_jobs.popleft()
                self._corrupt_fallback(g, b, h)
                continue
            cost = self._eager_block_bytes[g]
            if spent and spent + cost > budget:
                break
            bm.restore_jobs.popleft()
            taken.setdefault(g, []).append((b, h))
            spent += cost
        lazy = bool(self._lo_planes)
        for g, items in sorted(taken.items()):
            nbytes = self._tier_upload(g, items, self._hi_planes)
            for b, h in items:
                bm.finish_restore(g, b, h, lo_pending=lazy)
            self.stats["restored_blocks"] += len(items)
            self.stats["restored_bytes"] += nbytes

    def _corrupt_fallback(self, g: int, b: int, h: int) -> None:
        """A claimed restore's host bytes failed their checksum: preempt
        every row holding the destination block (requeued rows re-prefill
        prompt + emitted-so-far — the replay invariant makes the
        recompute continuation exact), then drop the poisoned entry so
        future matches recompute too. Counted, never raised, and never
        a wrong token: the garbage bytes are never scattered."""
        bm = self.blocks
        for idx in bm.rows_holding(g, b):
            self._preempt(idx)
        if (g, h) in bm.host and not bm.host.pinned((g, h)):
            bm.host.discard((g, h))
        self.stats["corrupt_fallbacks"] += 1

    def _sweep_corrupt_lo(self) -> None:
        """Integrity-sweep deferred lo-plane sources at the top of the
        step — BEFORE planning, where preemption is safe. A corrupt
        entry's block is purged (its device hi planes may be fine, but
        fp16 would join garbage lo bytes), its owner rows recompute, and
        the entry is dropped; the mid-step lo-upload sites may then
        trust whatever they drain."""
        bm = self.blocks
        if not (self._host_tier and self._lo_planes and bm._lo_pending):
            return
        for (g, b), h in list(bm._lo_pending.items()):
            if bm.host.verify((g, h)):
                continue
            del bm._lo_pending[(g, b)]
            bm.host.unpin((g, h))
            for idx in bm.rows_holding(g, b):
                self._preempt(idx)
            bm.purge_block(g, b)
            if not bm.host.pinned((g, h)):
                bm.host.discard((g, h))
            self.stats["corrupt_fallbacks"] += 1

    def _upload_lo(self, triples: list[tuple[int, int, int]]) -> None:
        """Complete deferred lo planes for (group, block, hash) triples
        (host-entry pins transfer here and are released after the
        upload)."""
        if not triples:
            return
        bm = self.blocks
        self._flush_spills()
        by_g: dict[int, list[tuple[int, int]]] = {}
        for g, b, h in triples:
            by_g.setdefault(g, []).append((b, h))
        for g, items in sorted(by_g.items()):
            nbytes = self._tier_upload(g, items, self._lo_planes)
            for _b, h in items:
                bm.host.unpin((g, h))
            self.stats["lo_lazy_blocks"] += len(items)
            self.stats["lo_lazy_bytes"] += nbytes

    def _ensure_lo(self, mode: str) -> None:
        """FP16 joins hi+lo planes everywhere, so the first FP16-mode
        step after a planar restore must land every deferred lo plane
        before it dispatches."""
        if mode == "fp16" and self._host_tier and self._lo_planes:
            self._upload_lo(self.blocks.take_lo_pending())

    def _store_meta(self) -> dict:
        """Layout fingerprint of the persisted prefix store: a store is
        only loadable into an engine whose chain hashes AND pool plane
        shapes mean the same thing."""
        return {"version": 1, "arch_id": self.cfg.arch_id,
                "kind": self.desc.kind, "planar": bool(self.kv_planar),
                "block_size": self.block_size,
                "group_windows": [w if w is None else int(w)
                                  for w in self.blocks.group_windows],
                "planes": {p.name: [list(p.token_shape), p.dtype]
                           for p in self.desc.planes}}

    def save_prefix_store(self, path: str | None = None) -> int:
        """Mirror the ENTIRE prefix index into the host tier (a
        non-evicting batched capture) and serialize it — chain-hash keys
        plus block bytes — to `path` (default `persist_dir`). Because
        chain hashes are stable blake2b content digests, a fresh
        `Engine(persist_dir=...)` in another process re-admits these
        prefixes without recomputing them. Returns entries written."""
        path = path or self.persist_dir
        if not self._host_tier or not path:
            raise ValueError("save_prefix_store needs host_offload and a "
                             "persist_dir/path")
        with (contextlib.nullcontext() if self.mesh is None
              else mesh_context(self.mesh)):
            self._flush_spills()
            self._capture_blocks(self.blocks.mirror_jobs())
        os.makedirs(path, exist_ok=True)
        arrs = {f"{g}|{h}|{p}": a
                for (g, h), planes in self.blocks.host.entries.items()
                for p, a in planes.items()}
        np.savez(os.path.join(path, "prefix_store.npz"), **arrs)
        with open(os.path.join(path, "prefix_store.json"), "w") as f:
            json.dump(self._store_meta(), f)
        return len(self.blocks.host)

    def _load_prefix_store(self, path: str) -> int:
        """Load a persisted prefix store into the host tier (engine
        construction). A missing store or a layout-fingerprint mismatch
        loads nothing — stale bytes must never be joined with a
        different block size, plane layout, or window split."""
        meta_p = os.path.join(path, "prefix_store.json")
        npz_p = os.path.join(path, "prefix_store.npz")
        if not (os.path.exists(meta_p) and os.path.exists(npz_p)):
            return 0
        with open(meta_p) as f:
            if json.load(f) != self._store_meta():
                return 0
        entries: dict[tuple[int, int], dict[str, np.ndarray]] = {}
        with np.load(npz_p) as data:
            for key in data.files:
                g, h, p = key.split("|", 2)
                entries.setdefault((int(g), int(h)), {})[p] = data[key]
        for key, planes in entries.items():
            self.blocks.host.put(key, planes, loaded=True)
        return len(entries)

    # -- mode selection -------------------------------------------------------
    def _mode(self, decode_tokens: int, prefill_tokens: int,
              free_block_frac: float | None = None) -> str:
        if self.forced_mode:
            return self.forced_mode
        if self.controller is None:
            return "fp16"
        obs = StepObservation(batch_tokens=max(decode_tokens, 1),
                              queue_depth=len(self.queue),
                              measured_step_ms=self._last_step_ms,
                              prefill_tokens=prefill_tokens,
                              free_block_frac=free_block_frac)
        return self.controller.decide(obs)

    # -- step -----------------------------------------------------------------
    def step(self) -> None:
        """One engine iteration: O(1) jitted dispatches regardless of how
        many sequences are prefilling or decoding (attention families —
        recurrent descriptors dispatch per chunk), with the step's device
        results synced to host exactly once at the end.

        Under a serving mesh the dispatch/h2d counters in `stats` keep
        counting LOGICAL steps: every jitted call below is one pjit
        program spanning all shards, so `prefill_dispatches` et al. and
        `h2d_bytes` are mesh-size-invariant (asserted by the dispatch
        tests) — replication fan-out is XLA's job, not a per-shard loop
        here."""
        with (contextlib.nullcontext() if self.mesh is None
              else mesh_context(self.mesh)):
            # the ambient mesh lets shard_hint constraints inside the
            # model stack (mla absorbed-q pinning et al.) take effect;
            # all committed-operand partitioning works without it
            self._step_inner()

    def _step_inner(self) -> None:
        if self.fault_hook is not None:
            # containment point: nothing has mutated yet, so a raise
            # here (InjectedFault or a real defect surfaced by the
            # harness) leaves the engine fully drainable
            self.fault_hook(self)
        self.iteration += 1
        t0 = self.clock()
        # land queued host-tier restores first (SLO-bounded): rows whose
        # blocks finish restoring here become schedulable this very step
        self._sweep_corrupt_lo()
        self._drain_restores()
        plan = self._plan_chunks()
        mode = self._mode(len(self.active),
                          sum(take for _, _, take in plan),
                          free_block_frac=self.blocks.free_block_frac())
        # planar pools restore hi planes eagerly, lo lazily: the first
        # FP16-mode step joins hi+lo, so deferred lo bytes land NOW
        self._ensure_lo(mode)
        # pending: (req, output index, device ids, row, slot) patched —
        # and EOS-checked — at the end-of-step sync; fresh: (slot,
        # device ids, row) prefills that completed this step and decode
        # below with a device-held token
        pending: list[tuple[Request, int, Any, int, int]] = []
        fresh: list[tuple[int, Any, int]] = []
        chunk_ids = self._run_chunks(mode, plan, pending, fresh)
        decode_ids, drafts = self._decode_paged(mode, chunk_ids, fresh)
        self._finalize_step(mode, pending, decode_ids, drafts)
        self._sample_peak()
        # wall time of this step feeds the controller's p90 tracker on the
        # NEXT decision (measured-latency fallback to FP8, paper §3.2);
        # injected stalls ride on top so the controller reacts to them
        self.last_mode = mode
        self.last_stall_ms, self.inject_stall_ms = self.inject_stall_ms, 0.0
        self._last_step_ms = (self.clock() - t0) * 1e3 + self.last_stall_ms
        if self.debug_invariants:
            # outside the measured step window, so the controller's p90
            # and the bench rows stay honest under NFP_DEBUG=1
            self.blocks.check_invariants()

    # =========================================================================
    # paged path: chunked prefill + block-table decode
    # =========================================================================
    def _ensure_take(self, idx: int, start: int, want: int) -> int:
        """Largest chunk <= want coverable by already-owned + free blocks
        across every window group (sliding dead local blocks back into
        the pool first)."""
        bm = self.blocks
        take = bm.max_coverable(idx, start, want)
        if take <= 0 or not bm.ensure(idx, start + take):
            return 0
        return take

    def _plan_chunks(self) -> list[tuple[int, int, int]]:
        """Schedule this step's prefill work: continue in-flight prefills
        (oldest first), then admit queued requests while the chunk-token
        budget, a slot, and enough free blocks for their WHOLE prompt are
        available (the admission watermark — decode growth may still
        preempt, but admissions never immediately thrash)."""
        plan: list[tuple[int, int, int]] = []
        budget = self.chunk_tokens
        order = sorted(self.prefilling,
                       key=lambda i: self.blocks.seqs[i].admitted)
        for idx in order:
            if budget <= 0:
                break
            if self.blocks.row_unrestored(idx):
                continue    # host-tier restore in flight: reads would
                            # see garbage; _drain_restores ungates it
            st = self.prefilling[idx]
            want = min(len(st.seq_tokens) - st.done, budget)
            take = self._ensure_take(idx, st.done, want)
            if take > 0:
                plan.append((idx, st.done, take))
                budget -= take
        while budget > 0 and self.queue:
            req = self.queue[0]
            seq_tokens = req.tokens + req.output
            idx = self.blocks.try_allocate(
                req.request_id, len(seq_tokens),
                req.max_new - len(req.output),
                cached_blocks=self.blocks.prefix_admit_discount(seq_tokens))
            if idx is None:
                break
            self.queue.popleft()
            if self.slot_state is not None:
                # slot-resident state side: claim the same slot index and
                # zero its recurrent state (recompute after preemption
                # must restart the recurrence from scratch)
                self.slot_state.claim(idx, req.request_id, len(seq_tokens),
                                      req.max_new - len(req.output))
                self.caches = self._zero_slot(self.caches, jnp.int32(idx))
                self.stats["aux_dispatches"] += 1
            # longest cached full-block prefix is shared (incref, zero
            # recompute); prefill starts at the matched offset but always
            # recomputes >= 1 token so the first-token logit is produced
            # (cow_for_write forks the tail block if that write would
            # land in a shared one)
            matched = self.blocks.attach_prefix(
                idx, seq_tokens, allow_host=self._host_admit())
            start = min(matched, len(seq_tokens) - 1)
            self.blocks.set_length(idx, start)
            st = _Prefill(req, seq_tokens, done=start)
            self.prefilling[idx] = st
            if self.blocks.row_unrestored(idx):
                continue    # attached host-tier blocks: the first chunk
                            # waits for their restore uploads to land
            take = self._ensure_take(
                idx, start, min(len(seq_tokens) - start, budget))
            if take > 0:
                plan.append((idx, start, take))
                budget -= take
        return plan

    def _h2d(self, a: np.ndarray):
        """Host->device upload with byte accounting (engine_dispatch/*
        bench rows report bytes per step/token)."""
        self.stats["h2d_bytes"] += a.nbytes
        return jnp.asarray(a)

    def _chunk_fn(self, mode: str, bucket: int):
        """Single-row prefill chunk executable (recurrent descriptors —
        attention families batch through `_fused_fn` instead). The
        traced `slot` routes the chunk's state read/write to one state
        row; the row's block table is sliced from the device-resident
        (G, n_slots, MB) array by a traced slot index, so jit caches
        still key on (mode, bucket) alone."""
        key = (mode, bucket)
        if key not in self._chunk_cache:
            rt, cfg, bs = self._rts[mode], self.cfg, self.block_size
            slotted = self.slot_state is not None

            def fn(p, caches, tokens, tables, row, q_offset, kv_len,
                   logit_pos, slot):
                table = jax.lax.dynamic_slice_in_dim(tables, row, 1, axis=1)
                return self._paged_step(rt, p, cfg, tokens, caches, table,
                                    q_offset=q_offset, kv_len=kv_len,
                                    block_size=bs, logit_position=logit_pos,
                                    slot=slot if slotted else None)
            self._chunk_cache[key] = jax.jit(fn, donate_argnums=(1,))
        return self._chunk_cache[key]

    def _fused_fn(self, mode: str, rows_bucket: int, chunk_bucket: int):
        """Batched ragged prefill executable: every planned chunk of a
        step runs as one dispatch. Rows are independent single-sequence
        chunks (per-row q_offset/kv_len/logit_position carry the
        raggedness; kv_len=0 disables pad rows); each row's block table
        is gathered from the device-resident array by a traced slot
        vector, so the jit cache keys on (mode, rows-bucket,
        chunk-bucket) — the total-chunk bucket — alone."""
        key = (mode, rows_bucket, chunk_bucket)
        if key not in self._fused_cache:
            rt, cfg, bs = self._rts[mode], self.cfg, self.block_size

            def fn(p, caches, tokens, tables, rows, q_offset, kv_len,
                   logit_pos):
                tab = jnp.take(tables, rows, axis=1)     # (G, R, MB)
                return self._paged_step(rt, p, cfg, tokens, caches, tab,
                                    q_offset=q_offset, kv_len=kv_len,
                                    block_size=bs, logit_position=logit_pos)
            self._fused_cache[key] = jax.jit(fn, donate_argnums=(1,))
        return self._fused_cache[key]

    def _spec_fn(self, mode: str, cb: int):
        """Speculative verification executable: the batched decode as a
        ragged C=cb chunk (column 0 the pending token, columns 1..K the
        drafts, pad columns masked by per-row kv_len), per-column greedy
        argmax (`sample_all`), and the longest-accepted-prefix selection
        FUSED next to it — draft j survives iff it matches the argmax
        after position j-1 AND every earlier draft survived (the
        cumprod). Returns ONE packed (B, cb+1) int32 array `[ids |
        n_accepted]` so the end-of-step sync stays a single pull; the jit
        cache keys on (mode, draft-bucket) via `_bucket`, exactly like
        the prefill executables."""
        key = (mode, cb)
        if key not in self._spec_cache:
            rt, cfg, bs = self._rts[mode], self.cfg, self.block_size

            def fn(p, caches, toks, tables, qo, kvl, dlen):
                ids, new_caches = self._paged_step(
                    rt, p, cfg, toks, caches, tables, q_offset=qo,
                    kv_len=kvl, block_size=bs, sample_all=True)
                # ids[:, j] = greedy successor of position qo+j; draft
                # toks[:, j] (the input at position qo+j) is confirmed
                # iff it equals ids[:, j-1]; dlen masks pad columns
                m = (ids[:, :-1] == toks[:, 1:]) \
                    & (jnp.arange(1, cb)[None, :] <= dlen[:, None])
                n_acc = jnp.cumprod(m.astype(jnp.int32), axis=1).sum(axis=1)
                return jnp.concatenate(
                    [ids, n_acc[:, None]], axis=1), new_caches
            self._spec_cache[key] = jax.jit(fn, donate_argnums=(1,))
        return self._spec_cache[key]

    def _apply_cow(self, triples: list[tuple[int, int, int]]) -> None:
        """Materialize COW forks: copy each forked block's bytes — the
        owning group's layer rows only — in the physical pool (one
        jitted scatter per group, src/dst traced)."""
        if self._host_tier and triples:
            # copies are cache writes: capture queued spills first, and
            # complete any fork SOURCE's deferred lo planes — the copy
            # clones all planes, so a lo-pending src would hand the dst
            # stale lo bytes with no lo_pending record of its own
            self._flush_spills()
            self._upload_lo(self.blocks.take_lo_pending_for(
                [(g, src) for g, src, _dst in triples]))
        for g, src, dst in triples:
            self.caches = self._copy_block[g](
                self.caches, jnp.int32(src), jnp.int32(dst))
            self.stats["aux_dispatches"] += 1

    def _cow_or_preempt(self, idx: int, start: int, end: int) -> bool:
        """Fork shared blocks covering the write range [start, end);
        preempt youngest sequences while the pool is too exhausted to
        fork. False when `idx` itself got preempted."""
        pairs = self.blocks.cow_for_write(idx, start, end)
        while pairs is None:
            victim = self.blocks.youngest()
            if victim is None:
                raise RuntimeError("KV pool exhausted with nothing "
                                   "preemptible")
            self._preempt(victim)
            if idx not in self.prefilling and idx not in self.active:
                return False                 # preempted ourselves
            pairs = self.blocks.cow_for_write(idx, start, end)
        self._apply_cow(pairs)
        return True

    def _sample_peak(self) -> None:
        self.stats["peak_block_util"] = max(
            self.stats["peak_block_util"], self.blocks.utilization())
        self.stats["window_reclaimed_blocks"] = \
            self.blocks.window_freed_blocks

    def _run_chunks(self, mode: str, plan, pending, fresh):
        """Execute this step's planned prompt chunks. Attention-family
        descriptors fuse EVERY chunk into one batched ragged dispatch;
        recurrent descriptors dispatch per chunk (exact-length chunks,
        single-slot state routing). Returns the device array of sampled
        ids for the fused batch (None otherwise); completing rows are
        recorded in `pending`/`fresh` for the end-of-step sync."""
        if self._pad_chunks:
            return self._run_chunks_fused(mode, plan, pending, fresh)
        for idx, start, take in plan:
            # a COW-fork failure inside an earlier chunk may have
            # preempted a later plan entry — skip stale entries
            if idx in self.prefilling:
                self._run_chunk(mode, idx, start, take, pending, fresh)
        return None

    def _run_chunks_fused(self, mode: str, plan, pending, fresh):
        """ONE jitted ragged `paged_step` covers the whole chunk budget:
        rows bucketed to a power of two, chunk lengths to the max take's
        bucket; pad rows are disabled via kv_len=0 and pad columns are
        masked as before, so the fused batch is bit-identical to the
        per-chunk dispatches it replaces."""
        entries = []
        for idx, start, take in plan:
            if idx not in self.prefilling:
                continue                     # preempted by an earlier COW
            if not self._cow_or_preempt(idx, start, start + take):
                continue
            entries.append((idx, start, take))
        # a later COW fork may have preempted an earlier surviving entry
        entries = [e for e in entries if e[0] in self.prefilling]
        if not entries:
            return None
        rb = _bucket(len(entries), 1)
        cb = _bucket(max(take for _, _, take in entries))
        tokens = np.zeros((rb, cb), np.int32)
        rows = np.zeros(rb, np.int32)        # pad rows alias slot 0:
        qo = np.zeros(rb, np.int32)          # kv_len=0 masks their reads
        kvl = np.zeros(rb, np.int32)         # and trashes their writes
        lp = np.zeros(rb, np.int32)
        for r, (idx, start, take) in enumerate(entries):
            st = self.prefilling[idx]
            tokens[r, :take] = st.seq_tokens[start: start + take]
            rows[r] = idx
            qo[r] = start
            kvl[r] = start + take
            lp[r] = take - 1
        if self._host_tier:
            # the fused dispatch writes the pool: queued spill captures
            # go first, and any lo-pending block the write ranges touch
            # (the resume-boundary rewrite can land in a restored block
            # the row owns exclusively) completes its lo planes NOW —
            # a later whole-block lo scatter would clobber fresh bytes
            self._flush_spills()
            touched = [p for idx, start, take in entries
                       for p in self.blocks.lo_pending_in_range(
                           idx, start, start + take)]
            self._upload_lo(self.blocks.take_lo_pending_for(touched))
        ids, self.caches = self._fused_fn(mode, rb, cb)(
            self.params, self.caches, self._h2d(tokens),
            self.blocks.device_tables(), self._h2d(rows), self._h2d(qo),
            self._h2d(kvl), self._h2d(lp))
        self.stats["prefill_dispatches"] += 1
        for idx, start, take in entries:
            self._commit_chunk(idx, start, take)
        # sample pool pressure BEFORE _finish_chunk can retire+release
        # blocks — prefill-heavy steps used to under-report the peak
        self._sample_peak()
        for r, (idx, start, take) in enumerate(entries):
            self._finish_chunk(mode, idx, ids, r, pending, fresh)
        return ids

    def _run_chunk(self, mode: str, idx: int, start: int, take: int,
                   pending, fresh) -> None:
        """Recurrent-descriptor chunk: one dispatch per chunk (pads
        would be absorbed into the SSM state, so rows cannot share a
        bucketed batch)."""
        st = self.prefilling[idx]
        if not self._cow_or_preempt(idx, start, start + take):
            return
        bucket = take                        # exact-length, no padding
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = st.seq_tokens[start: start + take]
        ids, self.caches = self._chunk_fn(mode, bucket)(
            self.params, self.caches, self._h2d(toks),
            self.blocks.device_tables(), jnp.int32(idx),
            self._h2d(np.asarray([start], np.int32)),
            self._h2d(np.asarray([start + take], np.int32)),
            self._h2d(np.asarray([take - 1], np.int32)), jnp.int32(idx))
        self.stats["prefill_dispatches"] += 1
        self._commit_chunk(idx, start, take)
        self._sample_peak()                  # pre-retire, as above
        self._finish_chunk(mode, idx, ids, 0, pending, fresh)

    def _commit_chunk(self, idx: int, start: int, take: int) -> None:
        st = self.prefilling[idx]
        st.done = start + take
        self.blocks.commit(idx, st.done, st.seq_tokens)
        self.stats["chunks"] += 1
        self.stats["chunk_tokens"] += take

    def _finish_chunk(self, mode: str, idx: int, ids, row: int,
                      pending, fresh) -> None:
        """Promote a prefill whose final chunk just ran to active. Its
        first generated token is still ON DEVICE (`ids[row]`): the
        output slot is patched at the end-of-step sync, and the same
        step's decode receives it through the jitted overlay."""
        st = self.prefilling[idx]
        if st.done < len(st.seq_tokens):
            return
        req = st.req
        req.output.append(_PENDING)
        pending.append((req, len(req.output) - 1, ids, row, idx))
        now = self.clock()
        if req.first_token_s is None:
            req.first_token_s = now
        req.token_times.append(now)
        req.modes.append(mode)
        self.lens[idx] = len(st.seq_tokens)
        self.active[idx] = req
        del self.prefilling[idx]
        self._maybe_retire(idx, now)
        if idx in self.active:
            fresh.append((idx, ids, row))

    def _preempt(self, victim: int) -> None:
        """vLLM-style recompute preemption: drop the victim's blocks and
        requeue its request at the FRONT of the queue; on re-admission it
        prefills prompt+generated-so-far and continues exactly."""
        self.stats["preemptions"] += 1
        if victim in self.active:
            req = self.active.pop(victim)
        else:
            req = self.prefilling.pop(victim).req
        self.blocks.release(victim)
        if self.slot_state is not None:
            self.slot_state.release(victim)
        self.lens[victim] = 0
        self.queue.appendleft(req)

    def _retire(self, idx: int, now: float) -> None:
        req = self.active.pop(idx)
        req.finished_s = now
        self.finished.append(req)
        self.blocks.release(idx)
        if self.slot_state is not None:
            self.slot_state.release(idx)
        self.lens[idx] = 0

    def _maybe_retire(self, idx: int, now: float) -> None:
        req = self.active[idx]
        # NOTE length >= capacity (not length+1): position `length` is the
        # next write target, so a row is live while length < capacity —
        # the old `+1` retired sequences one writable position early.
        # Stop-token retirement reads the LAST emitted token only: the
        # speculative multi-token path already cuts its emission at the
        # first stop token, so output[-1] is the one place EOS can live
        # (_PENDING placeholders are not yet tokens and never match).
        eos = bool(req.stop_tokens) and bool(req.output) \
            and req.output[-1] != _PENDING \
            and req.output[-1] in req.stop_tokens
        if eos or len(req.output) >= req.max_new \
                or self.lens[idx] >= self.capacity:
            self._retire(idx, now)

    def _draft(self) -> dict[int, list[int]]:
        """Propose n-gram drafts per active row and secure KV coverage
        for their writes at positions L+1..L+K. Drafting NEVER preempts:
        the draft is clamped to what the pool can cover without evicting
        anyone (`max_coverable`), and if the COW fork for the extension
        cannot complete the extension is given back (`truncate`) and the
        row runs as a plain one-token decode. Rows whose pending input
        token still lives on device (fresh prefills) cannot be matched
        against and draft nothing this step."""
        k = self._spec_k.decide(StepObservation(
            batch_tokens=max(len(self.active), 1),
            queue_depth=len(self.queue),
            measured_step_ms=self._last_step_ms,
            spec_drafted=self._last_spec[0],
            spec_accepted=self._last_spec[1]))
        drafts: dict[int, list[int]] = {}
        bm = self.blocks
        for idx, req in self.active.items():
            if req.output[-1] == _PENDING:
                continue
            L = int(self.lens[idx])
            # position L's write and this step's guaranteed token are
            # already budgeted — clamp drafts to what's left of the
            # output budget and the cache capacity beyond them
            budget = min(k, req.max_new - len(req.output) - 1,
                         self.capacity - L - 1)
            if budget <= 0:
                continue
            d = self._proposer.propose(req.tokens + req.output, budget)
            if d:
                d = d[:bm.max_coverable(idx, L + 1, len(d))]
            if not d:
                continue
            ok = bm.ensure(idx, L + 1 + len(d))
            assert ok, idx           # max_coverable guarantees coverage
            pairs = bm.cow_for_write(idx, L + 1, L + 1 + len(d))
            if pairs is None:
                bm.truncate(idx, L + 1)
                continue
            self._apply_cow(pairs)
            drafts[idx] = d
        return drafts

    def _decode_paged(self, mode: str, chunk_ids, fresh):
        """Dispatch the batched decode; returns (device ids, drafts) —
        ids None when nothing is active, drafts None for a plain
        one-token step. With speculation enabled and at least one row
        drafting, the decode runs through `_spec_fn` as a ragged C=K+1
        chunk instead (same single dispatch, packed [ids | n_accepted]
        result). Host bookkeeping for the decoded tokens happens in
        `_finalize_step` after the single end-of-step sync."""
        # grow each active row's block table to cover the incoming write
        # at position lens[idx] and COW-fork it if shared; preempt
        # youngest sequences on exhaustion
        for idx in sorted(self.active):
            while idx in self.active:
                if self.blocks.ensure(idx, int(self.lens[idx]) + 1):
                    if self._cow_or_preempt(idx, int(self.lens[idx]),
                                            int(self.lens[idx]) + 1):
                        break
                    continue                 # preempted (maybe ourselves)
                victim = self.blocks.youngest()
                if victim is None:
                    raise RuntimeError("KV pool exhausted with nothing "
                                       "preemptible")
                self._preempt(victim)
        self._sample_peak()                  # allocation peak, pre-retire
        if not self.active:
            return None, None
        drafts = self._draft() if self._spec is not None else {}
        kmax = max(map(len, drafts.values()), default=0)
        # no row drafted: dispatch the plain C=1 executable — identical
        # to speculation-off (under attn_backend="pallas" it keeps the
        # single-query decode kernel, which the C>1 chunk cannot use)
        cb = _bucket(kmax + 1, 1) if kmax else 1
        tokens = np.zeros((self.n_slots, cb), np.int32)
        q_off = np.zeros(self.n_slots, np.int32)
        kvl = np.zeros(self.n_slots, np.int32)   # 0 disables inactive rows
        dlen = np.zeros(self.n_slots, np.int32)
        for idx, req in self.active.items():
            if req.output[-1] != _PENDING:
                tokens[idx, 0] = req.output[-1]
            d = drafts.get(idx)
            if d:
                tokens[idx, 1:1 + len(d)] = d
                dlen[idx] = len(d)
            q_off[idx] = self.lens[idx]
            kvl[idx] = self.lens[idx] + 1 + dlen[idx]
        toks = self._h2d(tokens)
        fresh = [(s, a, r) for s, a, r in fresh if s in self.active]
        if fresh and chunk_ids is not None:
            # fused path: every completing prefill's first token lives in
            # ONE device array — overlay them all with a single jitted
            # scatter instead of syncing mid-step
            slots = np.asarray([s for s, _, _ in fresh], np.int32)
            rows = np.asarray([r for _, _, r in fresh], np.int32)
            toks = self._overlay(toks, self._h2d(slots), chunk_ids,
                                 self._h2d(rows))
            self.stats["aux_dispatches"] += 1
        elif fresh:
            # recurrent path: per-chunk ids arrays, one overlay each
            for s, a, r in fresh:
                toks = self._overlay(
                    toks, self._h2d(np.asarray([s], np.int32)), a,
                    self._h2d(np.asarray([r], np.int32)))
                self.stats["aux_dispatches"] += 1
        # decode writes the pool: capture queued spills (ensure() may
        # have evicted LRU prefix blocks above) before the write lands.
        # No lo guard here — decode/draft writes only ever land in
        # partially-filled or COW-exclusive tail blocks, never in a
        # restored (full, registered) block.
        self._flush_spills()
        if kmax:
            ids, self.caches = self._spec_fn(mode, cb)(
                self.params, self.caches, toks, self.blocks.device_tables(),
                self._h2d(q_off), self._h2d(kvl), self._h2d(dlen))
            self.stats["decode_dispatches"] += 1
            self.stats["spec_dispatches"] += 1
            return ids, drafts
        ids, self.caches = self._decode[mode](
            self.params, self.caches, toks, self.blocks.device_tables(),
            self._h2d(q_off), self._h2d(kvl))
        self.stats["decode_dispatches"] += 1
        return ids, None

    # nfp: sync-point
    def _finalize_step(self, mode: str, pending, decode_ids,
                       drafts=None) -> None:
        """The step's ONLY device->host sync: pull the sampled token ids
        (a few int32s, not logits), patch pending prefill outputs, then
        run decode bookkeeping — commit() must hash REAL token values,
        so it happens strictly after the patch.

        A patched pending token that is a stop token retires its row
        HERE, before decode bookkeeping: the row's same-step decode
        result is discarded (its position-L write went to an exclusive
        unregistered tail block, so releasing is clean) — previously a
        first-token EOS decoded on to max_new.

        Speculative steps (`drafts` non-None) emit per row the accepted
        draft prefix plus the model's next token — `[ids | n_acc]`
        packed by `_spec_fn` — cut at the first stop token and the
        max_new budget; `BlockManager.truncate` gives back the blocks
        covering rejected positions, and one commit() both registers any
        newly-filled blocks (a multi-token emission can fill several)
        and advances the length. The LAST emitted token is never in the
        cache — it is the next step's input, exactly as in plain
        decode."""
        nxt = None if decode_ids is None else np.asarray(decode_ids)
        now = self.clock()
        for req, pos, ids, row, idx in pending:
            req.output[pos] = int(np.asarray(ids)[row])
            if req.output[pos] in req.stop_tokens \
                    and self.active.get(idx) is req:
                self._retire(idx, now)
        if nxt is None:
            return
        if drafts is None:
            for idx, req in list(self.active.items()):
                self.lens[idx] += 1
                n = int(self.lens[idx])
                if n % self.block_size == 0:
                    # tail block just filled: register it in the prefix
                    # index (generated content is reusable too — replays
                    # after preemption and shared multi-turn history)
                    self.blocks.commit(idx, n,
                                       (req.tokens + req.output)[:n])
                else:
                    self.blocks.set_length(idx, n)
                req.output.append(int(nxt[idx]))
                req.token_times.append(now)
                req.modes.append(mode)
                self.stats["decode_rows"] += 1
                self.stats["decode_tokens"] += 1
                self._maybe_retire(idx, now)
            if self._spec is not None:
                self._last_spec = (0, 0)
            return
        drafted_total = accepted_total = 0
        for idx, req in list(self.active.items()):
            d = drafts.get(idx, ())
            n_acc = int(nxt[idx, -1]) if d else 0
            out = [int(t) for t in nxt[idx, :n_acc + 1]]
            drafted_total += len(d)
            accepted_total += n_acc
            # EOS stops an accepted run MID-RUN: everything after the
            # first stop token is discarded (never emitted), and the
            # output budget bounds the emission the same way
            for j, t in enumerate(out):
                if t in req.stop_tokens:
                    out = out[:j + 1]
                    break
            out = out[:req.max_new - len(req.output)]
            new_n = int(self.lens[idx]) + len(out)
            # rollback: drop the blocks covering rejected positions
            # (their writes landed in COW-exclusive unregistered blocks;
            # what survives inside the kept tail block beyond new_n is
            # masked by kv_len and overwritten before it can be read)
            self.blocks.truncate(idx, new_n)
            self.blocks.commit(idx, new_n,
                               (req.tokens + req.output + out)[:new_n])
            self.lens[idx] = new_n
            req.output.extend(out)
            req.token_times.extend([now] * len(out))
            req.modes.extend([mode] * len(out))
            self.stats["decode_rows"] += 1
            self.stats["decode_tokens"] += len(out)
            self._maybe_retire(idx, now)
        self.stats["spec_drafted"] += drafted_total
        self.stats["spec_accepted"] += accepted_total
        self._last_spec = (drafted_total, accepted_total)

