"""KV-cache managers + per-family cache descriptors for the engine.

Every serving-relevant architecture is described by a `CacheDescriptor`:
a set of token-granular PAGED planes (block-pooled, managed by
`BlockManager`) plus, for recurrent families, SLOT-RESIDENT planes
(fixed per-sequence state with no token axis, tracked by `SlotManager`).
The four descriptor kinds:

* `gqa`    — K/V pairs per layer (optionally byte-planar NestedKV);
             all planes paged.
* `mla`    — DeepSeek latent planes `c_kv` + `k_rope` per layer;
             all planes paged (576 f16 values/token for deepseek-v3).
* `hybrid` — zamba2-class: the shared-attention K/V planes are paged
             (one logical layer per application group) while the
             Mamba2 conv + SSD state is slot-resident.
* `ssm`    — pure Mamba2: slot-resident state only; block tables
             degenerate to token-length accounting.

`BlockManager` is the paged side (the paper's §3.3 serving story: KV
memory bounds the admissible batch, so reserving `capacity` tokens per
slot wastes exactly the HBM that NestedFP's zero-overhead weights
reclaim). Physical KV lives in a pool of fixed-size token blocks; each
sequence owns an ordered block table and grows one block at a time.
Admission is driven by free blocks, not free slots, and when blocks run
out the youngest sequence is preempted (blocks released, request
recomputed later — vLLM-style recompute preemption). Because MLA latent
and hybrid shared-attention blocks live in the same pool abstraction,
the controller's `free_block_frac` memory-pressure trigger sees
deepseek/zamba-class sequences exactly like GQA ones.

`SlotManager` is the slot-resident side of the `hybrid`/`ssm`
descriptors: one state slot per sequence, claimed in lockstep with the
BlockManager slot index (`claim`), zeroed at (re-)admission. The legacy
fixed-slot ENGINE path that used it for whole KV caches is retired —
every family now schedules through the paged path.

Physical block 0 is reserved as a trash block: jit'd steps always write
a full (possibly padded) chunk, and pad/inactive-row writes are pointed
at block 0 so they can never clobber live cache state.

Copy-on-write prefix caching (`prefix_cache=True`)
--------------------------------------------------
Every physical block carries a REFCOUNT, and every FULL block whose
content has been committed is registered in a content-hash index keyed
by a prefix chain hash `h_i = hash((h_{i-1}, tokens_of_block_i))` — a
block's identity is the whole token prefix up to and including it, so
identical (system-prompt / few-shot / replayed-after-preemption)
prefixes map to identical chains. At admission `attach_prefix` walks a
new sequence's chain and shares the longest run of cached blocks
(incref, zero recompute, zero new HBM). Sharing is read-only: the engine
always recomputes at least the last prompt token so the first-token
logit exists, and `cow_for_write` forks any write-target block whose
refcount exceeds one (allocate, copy bytes, decref the shared original)
before the write lands — writers can never clobber a neighbour's prefix.

Releasing a sequence (retire OR preempt) decrefs its blocks; registered
blocks whose refcount hits zero are parked in an LRU pool of
unreferenced-but-cached blocks instead of the free list. The allocator
reclaims LRU blocks (evicting their index entries) only after the free
list runs dry, so cached prefixes survive exactly as long as the pool
has headroom and reclaim always happens BEFORE preemption would: a
sequence is only ever preempted for blocks that live sequences hold.

Block identity is token-based, not byte-based: under the dual-precision
controller a reused block may have been written in either precision —
interchangeable by construction in NestedFP's serving model (both modes
read the same nested buffers). Forced-mode runs are bit-exact.

Sliding-window layer groups (gemma3-style local attention)
----------------------------------------------------------
Descriptors whose architecture interleaves LOCAL (sliding-window) and
GLOBAL attention layers (gemma3's 5:1 pattern) carry `LayerGroup`
metadata splitting the paged planes' layer axis into window groups.
`BlockManager` then keeps ONE block table PER GROUP per sequence, and
each group allocates from its OWN id space over the same physical pool
array: a layer only ever reads/writes its own group's rows of a block,
so block id `b` can be live in the global group and the local group
simultaneously without touching the same bytes — no pool doubling, and
no permanently-dead other-group rows inside an allocated block. A
windowed group's blocks are **slide-freed** the moment they fall fully
out of every future query's window — `slide_window` (invoked on every
`ensure`) decrefs dead local blocks, returns exclusively-held ones
straight to the group's free list, and points the table hole back at
the trash block. Global-group blocks stay pinned for the sequence's
whole life, so `free_block_frac` (the MINIMUM headroom across groups —
the binding constraint) reports the HONEST pressure the dual-precision
controller acts on instead of phantom pressure from dead local-layer
KV.

Slide-freed blocks are evicted from the prefix index at slide time, so
they can never be prefix-matched for local groups; blocks a live
neighbour still shares stay matchable (their content is intact). Prefix
matching itself is GROUP-AWARE (`_match_plan`): a resumable offset `m`
requires the global groups' full [0, m) chain AND, per windowed group,
only the cached blocks covering the resume position's lookback window
[q0 - window + 1, m*bs) — freshly attached sequences therefore start
with their local groups already slid to that point.

Device-resident block tables (`device_tables`)
----------------------------------------------
The engine dispatches one jitted step per iteration; re-uploading the
whole (G, n_slots, MB) table array from host every step would put an
O(table) host→device transfer on the per-step critical path even though
a typical step changes only a handful of entries (one `ensure` append
per growing row, the odd COW fork or window slide). `BlockManager`
therefore keeps a DEVICE mirror of the host table array: every table
mutation is recorded in a dirty set, and `device_tables()` flushes the
accumulated (group, slot, j) -> block updates with ONE small jitted
scatter (update count bucketed to a power of two so the scatter
executable is reused; the old device buffer is donated so the update is
in place, never a pool-sized copy). Steady-state decode uploads a few
dozen bytes per step instead of the full table. The host array stays
the source of truth for all allocator logic and tests.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# cache descriptors (per-family layouts; factory: models/model.py
# `cache_descriptor(cfg)`)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlaneSpec:
    """One token-granular cache plane, paged into fixed-size blocks.

    A pool leaf is shaped (n_layers, n_total_blocks, block_size,
    *token_shape); `token_shape` is the per-token feature shape (plain
    GQA K/V, f16 bits as int16: (Hkv*Hd,); NestedKV byte planes:
    (Hkv, Hd); MLA c_kv: (kv_lora_rank,))."""
    name: str
    n_layers: int
    token_shape: tuple[int, ...]
    dtype: str                          # numpy dtype name

    @property
    def bytes_per_token(self) -> int:
        return int(self.n_layers * np.prod(self.token_shape, dtype=np.int64)
                   * np.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class SlotPlaneSpec:
    """Slot-resident (non-paged) state: one fixed-shape entry per
    sequence slot, no token axis. A pool leaf is shaped
    (shape[0], n_slots, *shape[1:]) — batch rides axis 1, matching the
    layer-stacked cache convention."""
    name: str
    shape: tuple[int, ...]              # per-slot shape incl. layer dim
    dtype: str

    @property
    def bytes_per_slot(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)
                   * np.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """One attention-window group of the paged planes' layer axis.

    gemma3-style configs split into a "global" group (window None — keys
    live for the whole sequence) and a "local" group (sliding window in
    tokens — keys die once they fall out of every future query's
    lookback). Each group gets its OWN per-sequence block table in
    `BlockManager`, which is what makes local blocks reclaimable while
    global blocks stay pinned."""
    name: str
    window: int | None                  # tokens; None = full attention
    layers: tuple[int, ...]             # indices into the planes' layer axis


@dataclasses.dataclass(frozen=True)
class CacheDescriptor:
    """Per-family cache layout: which planes are paged (BlockManager)
    and which are slot-resident (SlotManager). `prefix_cacheable` is
    False for recurrent families — a cached KV prefix cannot stand in
    for slot-resident SSM state, so sharing blocks would skip state
    recomputation. `groups` (empty = one implicit global group) carries
    the per-layer-group window metadata for sliding-window archs."""
    kind: str                           # "gqa" | "mla" | "hybrid" | "ssm"
    planes: tuple[PlaneSpec, ...] = ()
    slot_planes: tuple[SlotPlaneSpec, ...] = ()
    prefix_cacheable: bool = True
    groups: tuple[LayerGroup, ...] = ()

    @property
    def group_windows(self) -> tuple[int | None, ...]:
        """Per-group sliding window (None = global); the BlockManager's
        `group_windows` argument. Single implicit global group when the
        descriptor carries no explicit layer groups."""
        if not self.groups:
            return (None,)
        return tuple(g.window for g in self.groups)

    def layer_group_map(self, n_layers: int) -> np.ndarray:
        """(n_layers,) int32 map from plane layer index to group index
        (all zeros for the implicit single global group)."""
        out = np.zeros(n_layers, np.int32)
        if self.groups:
            seen: set[int] = set()
            for gi, g in enumerate(self.groups):
                for li in g.layers:
                    assert 0 <= li < n_layers and li not in seen, (gi, li)
                    seen.add(li)
                    out[li] = gi
            assert len(seen) == n_layers, "layer groups must cover the stack"
        return out

    @property
    def paged(self) -> bool:
        return bool(self.planes)

    @property
    def bytes_per_token(self) -> int:
        """Paged-plane bytes per cached token (0 for pure SSM)."""
        return sum(p.bytes_per_token for p in self.planes)

    def bytes_per_block(self, block_size: int) -> int:
        return self.bytes_per_token * block_size

    @property
    def bytes_per_slot(self) -> int:
        """Slot-resident state bytes per sequence (0 for gqa/mla)."""
        return sum(p.bytes_per_slot for p in self.slot_planes)


@dataclasses.dataclass
class Slot:
    request_id: str | None = None
    length: int = 0
    max_new: int = 0
    generated: int = 0

    @property
    def free(self) -> bool:
        return self.request_id is None


class SlotManager:
    def __init__(self, n_slots: int, capacity: int):
        self.n_slots = n_slots
        self.capacity = capacity
        self.slots = [Slot() for _ in range(n_slots)]

    def try_allocate(self, request_id: str, prompt_len: int,
                     max_new: int) -> int | None:
        if prompt_len + max_new > self.capacity:
            raise ValueError(
                f"request {request_id}: {prompt_len}+{max_new} exceeds "
                f"slot capacity {self.capacity}")
        for i, s in enumerate(self.slots):
            if s.free:
                self.slots[i] = Slot(request_id, prompt_len, max_new, 0)
                return i
        return None

    def claim(self, idx: int, request_id: str, prompt_len: int,
              max_new: int) -> None:
        """Claim a SPECIFIC slot — used by the engine to keep the
        slot-resident state side of a hybrid/ssm descriptor in lockstep
        with the BlockManager's slot assignment."""
        assert self.slots[idx].free, f"slot {idx} already claimed"
        self.slots[idx] = Slot(request_id, prompt_len, max_new, 0)

    def release(self, idx: int) -> None:
        self.slots[idx] = Slot()

    def active(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if not s.free]

    def n_free(self) -> int:
        return sum(1 for s in self.slots if s.free)

    def utilization(self) -> float:
        used = sum(s.length for s in self.slots if not s.free)
        return used / (self.n_slots * self.capacity)


TRASH_BLOCK = 0


@functools.partial(jax.jit, donate_argnums=(0,))
def _table_scatter(tables, idx, val):
    """Apply K incremental (group, slot, j) -> block updates to the
    device table mirror in place (donated)."""
    return tables.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(val)


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _chain_hash(parent: int, tokens: tuple[int, ...]) -> int:
    """Stable content digest of one block's prefix chain link.

    Python's builtin `hash()` is salted per process (PYTHONHASHSEED), so
    chain hashes built from it could never be compared across engine
    processes or serialized with the host-tier prefix store — two
    restarts of the same engine would disagree on every key. blake2b
    over the parent digest + the block's token bytes is deterministic
    everywhere, and 64 bits keeps the index keys cheap ints."""
    h = hashlib.blake2b(int(parent).to_bytes(8, "little", signed=True),
                        digest_size=8)
    for t in tokens:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return int.from_bytes(h.digest(), "little", signed=True)


_ROOT_HASH = int.from_bytes(
    hashlib.blake2b(b"prefix-root", digest_size=8).digest(),
    "little", signed=True)


class HostPool:
    """Second KV tier: pinned host memory holding evicted prefix blocks.

    Entries are keyed by (group, chain hash) — the same stable identity
    the device-side prefix index uses — and each holds one block's pool
    bytes per plane, shaped (group_layers, block_size, *token_shape) as
    numpy arrays. The tier is INCLUSIVE: restoring an entry to the
    device keeps the host copy, so a restored-then-re-evicted block
    (registered blocks are immutable under COW) never needs a second
    d2h capture, and lazily-restored lo planes always have a source.

    `max_bytes` bounds the tier with drop-oldest LRU eviction; entries
    some device block still depends on (a queued restore, or a pending
    lazy lo-plane upload) are PINNED and skipped by the eviction scan.
    """

    def __init__(self, max_bytes: int | None = None):
        self.entries: collections.OrderedDict[
            tuple[int, int], dict[str, np.ndarray]] = collections.OrderedDict()
        self.max_bytes = max_bytes
        self.bytes = 0
        self._pins: collections.Counter = collections.Counter()
        self._sums: dict[tuple[int, int], bytes] = {}
        self._corrupt: set[tuple[int, int]] = set()
        self.stats = {"spilled_blocks": 0, "spilled_bytes": 0,
                      "restored_blocks": 0, "restored_bytes": 0,
                      "dropped_blocks": 0, "loaded_blocks": 0,
                      "corrupt_blocks": 0}

    def __contains__(self, key: tuple[int, int]) -> bool:
        return key in self.entries

    def __len__(self) -> int:
        return len(self.entries)

    @staticmethod
    def entry_bytes(planes: dict[str, np.ndarray]) -> int:
        return sum(a.nbytes for a in planes.values())

    def pin(self, key: tuple[int, int]) -> None:
        assert key in self.entries, key
        self._pins[key] += 1

    def unpin(self, key: tuple[int, int]) -> None:
        assert self._pins[key] > 0, key
        self._pins[key] -= 1
        if self._pins[key] == 0:
            del self._pins[key]

    def pinned(self, key: tuple[int, int]) -> bool:
        return self._pins.get(key, 0) > 0

    @staticmethod
    def checksum(planes: dict[str, np.ndarray]) -> bytes:
        """blake2b integrity digest over a block's plane bytes (names
        sorted so the digest is layout-order independent)."""
        h = hashlib.blake2b(digest_size=16)
        for name in sorted(planes):
            h.update(name.encode())
            h.update(np.ascontiguousarray(planes[name]).tobytes())
        return h.digest()

    def put(self, key: tuple[int, int], planes: dict[str, np.ndarray],
            loaded: bool = False) -> None:
        """Insert (or refresh) one block's bytes; `loaded` marks entries
        deserialized from a persisted store rather than spilled live."""
        if key in self.entries:
            self.bytes -= self.entry_bytes(self.entries.pop(key))
        self.entries[key] = planes
        self._sums[key] = self.checksum(planes)
        self._corrupt.discard(key)
        nb = self.entry_bytes(planes)
        self.bytes += nb
        if loaded:
            self.stats["loaded_blocks"] += 1
        else:
            self.stats["spilled_blocks"] += 1
            self.stats["spilled_bytes"] += nb
        self._shrink()

    def get(self, key: tuple[int, int]) -> dict[str, np.ndarray]:
        self.entries.move_to_end(key)        # LRU touch
        return self.entries[key]

    def verify(self, key: tuple[int, int]) -> bool:
        """Re-derive the entry's checksum and compare with the one
        recorded at `put`. A mismatch (bit rot, torn write, injected
        corruption) is remembered and counted exactly once — callers
        treat the entry as absent and fall back to recompute, never
        restoring garbage KV."""
        if key in self._corrupt:
            return False
        if self._sums.get(key) == self.checksum(self.entries[key]):
            return True
        self._corrupt.add(key)
        self.stats["corrupt_blocks"] += 1
        return False

    def discard(self, key: tuple[int, int]) -> None:
        """Drop one entry outright (corrupt payloads; must be unpinned)."""
        assert not self.pinned(key), key
        self.bytes -= self.entry_bytes(self.entries.pop(key))
        self._sums.pop(key, None)
        self._corrupt.discard(key)

    def _shrink(self) -> None:
        if self.max_bytes is None:
            return
        while self.bytes > self.max_bytes:
            victim = next((k for k in self.entries if not self.pinned(k)),
                          None)
            if victim is None:
                return                       # everything left is pinned
            self.bytes -= self.entry_bytes(self.entries.pop(victim))
            self._sums.pop(victim, None)
            self._corrupt.discard(victim)
            self.stats["dropped_blocks"] += 1


@dataclasses.dataclass
class _Group:
    """One window group's view of a sequence: physical block ids by
    logical index (TRASH_BLOCK = slide-freed hole), the chain hashes of
    the committed/matched full-block prefix, and how many leading
    logical blocks the window has slid past."""
    blocks: list[int] = dataclasses.field(default_factory=list)
    hashes: list[int] = dataclasses.field(default_factory=list)
    slid: int = 0


@dataclasses.dataclass
class _Seq:
    request_id: str
    groups: list[_Group]       # one block table per window group
    length: int = 0            # tokens committed to the cache
    admitted: int = 0          # admission counter (largest == youngest)

    # group-0 views: the only group for non-windowed descriptors (and
    # the GLOBAL group for windowed ones) — keeps single-group callers
    # and tests reading seq.blocks/seq.hashes working unchanged
    @property
    def blocks(self) -> list[int]:
        return self.groups[0].blocks

    @property
    def hashes(self) -> list[int]:
        return self.groups[0].hashes


class BlockManager:
    """Free-list allocator of fixed-size KV blocks with per-sequence
    block tables, per-block refcounts, and (optionally) copy-on-write
    prefix caching (see module docstring for the COW design).

    `n_blocks` counts USABLE blocks; physical block 0 (trash) is extra,
    so pools must be allocated with `n_total_blocks` blocks. Unassigned
    block-table entries point at the trash block — reads through them
    are masked by per-row lengths, writes land in garbage space.

    A persistent `(n_slots, max_blocks_per_seq)` int32 table array is
    maintained incrementally by ensure/attach/fork/release — `tables()`
    is O(1) per decode step instead of a full Python rebuild.
    """

    def __init__(self, n_slots: int, block_size: int, n_blocks: int,
                 max_blocks_per_seq: int, prefix_cache: bool = False,
                 group_windows: tuple[int | None, ...] = (None,),
                 mirror_sharding=None, host_pool: HostPool | None = None):
        assert block_size > 0 and n_blocks > 0
        assert group_windows and all(w is None or w > 0 for w in group_windows)
        self.n_slots = n_slots
        self.block_size = block_size
        self.n_blocks = n_blocks
        self.max_blocks_per_seq = max_blocks_per_seq
        self.prefix_cache = prefix_cache
        self.group_windows = tuple(group_windows)
        self.n_groups = len(self.group_windows)
        # PER-GROUP id spaces over one shared pool array: a layer only
        # touches its own group's rows of a block, so the same id can be
        # live in several groups without byte overlap. pop() hands out
        # low block ids first (deterministic layouts in tests).
        self._free = [list(range(n_blocks, 0, -1))
                      for _ in range(self.n_groups)]
        self.seqs: list[_Seq | None] = [None] * n_slots
        self._admissions = 0
        self._ref = [[0] * (n_blocks + 1)            # per-group refcounts
                     for _ in range(self.n_groups)]
        self._index: dict[tuple[int, int], int] = {}
        # (group, chain hash) -> block id; a block's content is only
        # valid for its group's layers
        self._hash_of: dict[tuple[int, int], int] = {}
        # registered (group, block) -> chain hash
        self._lru: list[collections.OrderedDict[int, None]] = [
            collections.OrderedDict() for _ in range(self.n_groups)]
        # per-group unreferenced-but-cached blocks, least recent first
        self._tables = np.full((self.n_groups, n_slots, max_blocks_per_seq),
                               TRASH_BLOCK, np.int32)
        # device mirror of _tables: created on first device_tables() call,
        # then maintained by small jitted scatters of the dirty set.
        # mirror_sharding (a replicated NamedSharding under a serving
        # mesh) commits the first upload onto every shard; the donated
        # scatter then keeps that placement, so per-step flushes stay
        # ONE logical dispatch of O(dirty) entries — never a per-shard
        # re-upload of the table
        self._dev_tables = None
        self.mirror_sharding = mirror_sharding
        self._dirty: dict[tuple[int, int, int], int] = {}
        self.table_h2d_bytes = 0         # bytes shipped host->device
        self.table_flushes = 0           # incremental scatter dispatches
        self.table_updates = 0           # table entries actually flushed
        self.prefix_stats = {"queries": 0, "lookup_tokens": 0,
                             "hit_tokens": 0, "blocks_shared": 0,
                             "cow_forks": 0, "evictions": 0,
                             "host_hit_blocks": 0}
        self.window_freed_blocks = 0     # blocks returned by window slides
        # ---- tiered KV (HostPool docstring) ----------------------------
        # host: the second tier; None disables spilling entirely.
        # _spill_queue: (group, block, hash) of LRU-evicted registered
        #   blocks whose bytes must be captured to the host tier BEFORE
        #   the next cache-writing dispatch lands (the evicted id is
        #   already reallocated — its bytes are intact only until then).
        # restore_jobs: (group, dst block, hash, ticket) uploads the
        #   engine drains through the scatter path under the SLO guard.
        # _unrestored: (group, block) -> ticket for device blocks whose
        #   bytes have NOT arrived yet; rows holding one are gated out
        #   of chunk scheduling, and a stale ticket voids the job.
        # _lo_pending: (group, block) -> hash for planar blocks whose
        #   fp8 hi planes were restored eagerly but whose lo planes wait
        #   for the first FP16-mode touch (host entry stays pinned).
        self.host = host_pool if prefix_cache else None
        self._spill_queue: list[tuple[int, int, int]] = []
        self._spill_pending: set[tuple[int, int]] = set()
        self.restore_jobs: collections.deque[tuple[int, int, int, int]] = \
            collections.deque()
        self._unrestored: dict[tuple[int, int], int] = {}
        self._lo_pending: dict[tuple[int, int], int] = {}
        self._ticket = 0

    # -- pool-level views ------------------------------------------------------
    @property
    def n_total_blocks(self) -> int:
        return self.n_blocks + 1                     # + trash block 0

    @property
    def capacity(self) -> int:
        """Max tokens a single sequence can hold."""
        return self.max_blocks_per_seq * self.block_size

    def free_blocks(self, group: int) -> int:
        """Allocatable blocks in one group's id space: truly free +
        reclaimable LRU-cached."""
        return len(self._free[group]) + len(self._lru[group])

    def n_free_blocks(self) -> int:
        """Allocatable blocks of the TIGHTEST group — the binding
        constraint on any allocation (every group must be able to cover
        a new block). Identical to the per-pool count for non-windowed
        (single-group) managers."""
        return min(self.free_blocks(g) for g in range(self.n_groups))

    def n_cached_blocks(self) -> int:
        """Unreferenced blocks kept warm in the prefix cache (summed
        over groups)."""
        return sum(len(l) for l in self._lru)

    def n_free_slots(self) -> int:
        return sum(1 for s in self.seqs if s is None)

    def blocks_in_use(self) -> int:
        """Group-blocks referenced by live sequences, summed over
        groups (shared blocks count once per group)."""
        return sum(self.n_blocks - self.free_blocks(g)
                   for g in range(self.n_groups))

    def utilization(self) -> float:
        return self.blocks_in_use() / (self.n_blocks * self.n_groups)

    def free_block_frac(self) -> float:
        """Allocatable fraction of the TIGHTEST group's pool — the
        MorphServe-style memory-pressure signal fed to the
        dual-precision controller. With window reclamation the local
        group keeps returning dead blocks, so this reflects honest
        headroom rather than phantom pressure."""
        return self.n_free_blocks() / self.n_blocks

    def table(self, idx: int, group: int = 0):
        """(max_blocks_per_seq,) int32 block table for one slot and
        window group; holes point at the trash block. A view into the
        persistent table — valid until the next
        ensure/attach/fork/slide/release on this slot."""
        return self._tables[group, idx]

    def tables(self):
        """(n_slots, max_blocks_per_seq) persistent int32 table array
        for group 0 — the only group for non-windowed descriptors
        (maintained incrementally; do not mutate). Windowed managers
        should use `group_tables()`."""
        return self._tables[0]

    def group_tables(self):
        """(n_groups, n_slots, max_blocks_per_seq) persistent int32
        table array — one table per window group, all maintained
        incrementally (do not mutate). `paged_step` gathers each
        layer's KV through its group's table."""
        return self._tables

    def _set_table(self, g: int, idx: int, j: int, b: int) -> None:
        """Single point of mutation for table entries: updates the host
        array and records the entry in the device mirror's dirty set."""
        if self._tables[g, idx, j] != b:
            self._tables[g, idx, j] = b
            if self._dev_tables is not None:
                self._dirty[(g, idx, j)] = int(b)

    def device_tables(self):
        """(n_groups, n_slots, max_blocks_per_seq) int32 DEVICE-resident
        table array. The first call uploads the full host array; every
        later call flushes only the entries mutated since the previous
        flush, as one jitted scatter whose update count is bucketed to a
        power of two (padding repeats the last update, which is
        idempotent) so a handful of executables serve every step. The
        returned array is the engine's per-step `block_tables` argument
        — identical in content to `group_tables()`, with h2d traffic
        proportional to the CHANGE, not the table."""
        if self._dev_tables is None:
            if self.mirror_sharding is not None:
                self._dev_tables = jax.device_put(self._tables,
                                                  self.mirror_sharding)
            else:
                self._dev_tables = jnp.asarray(self._tables)
            self.table_h2d_bytes += self._tables.nbytes
            self.table_flushes += 1
            return self._dev_tables
        if self._dirty:
            k = len(self._dirty)
            kb = _pow2(k)
            idx = np.empty((kb, 3), np.int32)
            val = np.empty((kb,), np.int32)
            for i, ((g, s, j), b) in enumerate(self._dirty.items()):
                idx[i] = (g, s, j)
                val[i] = b
            idx[k:] = idx[k - 1]
            val[k:] = val[k - 1]
            self._dev_tables = _table_scatter(
                self._dev_tables, jnp.asarray(idx), jnp.asarray(val))
            self.table_h2d_bytes += idx.nbytes + val.nbytes
            self.table_flushes += 1
            self.table_updates += k
            self._dirty.clear()
        return self._dev_tables

    # -- allocation core -------------------------------------------------------
    def _alloc_block(self, g: int) -> int | None:
        """Pop a free block from group g's id space; when the free list
        is dry, reclaim the least-recently-used cached block (evicting
        its index entry) — cached prefixes are always sacrificed before
        preemption is."""
        if self._free[g]:
            return self._free[g].pop()
        if self._lru[g]:
            b, _ = self._lru[g].popitem(last=False)
            h = self._hash_of.pop((g, b))
            del self._index[(g, h)]
            self.prefix_stats["evictions"] += 1
            if self.host is not None:
                # spill instead of discard: queue a d2h capture of the
                # block's bytes (drained by the engine before the next
                # cache-writing dispatch). Blocks already mirrored in
                # the host tier — including lazily-pending lo planes,
                # whose DEVICE lo bytes are garbage — skip the capture:
                # the tier is inclusive, the host copy is the truth.
                lo = self._lo_pending.pop((g, b), None)
                if lo is not None:
                    self.host.unpin((g, lo))
                if (g, h) in self.host or (g, h) in self._spill_pending:
                    pass
                else:
                    self._spill_queue.append((g, b, h))
                    self._spill_pending.add((g, h))
            return b
        return None

    def _release_block(self, g: int, b: int) -> None:
        """Decref; park registered zero-ref blocks in the group's LRU
        cache, return unregistered ones to the group's free list. A
        zero-ref block whose restore never completed holds garbage
        bytes — it is deregistered and FREED (its restore job is voided
        by the ticket check), never parked as matchable content."""
        self._ref[g][b] -= 1
        assert self._ref[g][b] >= 0, f"refcount underflow on block {g}/{b}"
        if self._ref[g][b] == 0:
            if (g, b) in self._unrestored:
                self._forget_restore(g, b)
                h = self._hash_of.pop((g, b), None)
                if h is not None:
                    del self._index[(g, h)]
                self._free[g].append(b)
            elif (g, b) in self._hash_of:
                self._lru[g][b] = None       # most-recent end
            else:
                self._free[g].append(b)

    # -- sequence lifecycle ----------------------------------------------------
    def _group_need(self, seq_len: int, window: int | None) -> int:
        """Blocks one group must hold live for a `seq_len`-token
        sequence: the full logical coverage for global groups, only the
        lookback-window span for windowed ones (everything earlier is
        slide-freed by the time the sequence reaches that length)."""
        nb = -(-max(seq_len, 1) // self.block_size)
        if not window:
            return nb
        q0 = max(seq_len - 1, 0)
        return nb - max(0, (q0 - window + 1) // self.block_size)

    def try_allocate(self, request_id: str, seq_len: int, max_new: int,
                     cached_blocks=0) -> int | None:
        """Claim a slot for a sequence (no blocks yet — `ensure` grows
        them chunk by chunk). None when no slot is free or when the
        first chunk could not possibly be admitted (some window group
        has fewer free blocks than the whole prompt needs in ITS id
        space — the admission watermark that keeps preemption for
        decode-time growth, not thrashing admissions). `cached_blocks`
        discounts prefix-cache hits from that watermark — an int
        (applied to every group) or a per-group sequence as returned by
        `prefix_admit_discount`: matched blocks cost nothing to
        re-establish."""
        if seq_len + max_new > self.capacity:
            raise ValueError(
                f"request {request_id}: {seq_len}+{max_new} exceeds paged "
                f"capacity {self.capacity}")
        if any(self._group_need(seq_len + max_new, w) > self.n_blocks
               for w in self.group_windows):
            raise ValueError(
                f"request {request_id}: needs more blocks than a whole "
                f"group pool holds ({self.n_blocks}) — would "
                f"preempt-thrash forever")
        if isinstance(cached_blocks, int):
            cached_blocks = (cached_blocks,) * self.n_groups
        if any(self._group_need(seq_len, w) - c > self.free_blocks(g)
               for g, (w, c) in enumerate(zip(self.group_windows,
                                              cached_blocks))):
            return None
        for i, s in enumerate(self.seqs):
            if s is None:
                self._admissions += 1
                self.seqs[i] = _Seq(
                    request_id, [_Group() for _ in self.group_windows],
                    0, self._admissions)
                return i
        return None

    def slide_window(self, idx: int) -> int:
        """Free every windowed-group block that has slid fully out of
        the lookback window of all FUTURE queries (the next query sits
        at `seq.length`, so positions <= length - window are dead).
        Exclusively-held dead blocks go straight back to the free list
        — and are EVICTED from the prefix index, so a slide-freed block
        can never be prefix-matched for a local group again; blocks a
        neighbour still shares are merely decref'd (their content is
        intact for that holder). Returns the number of blocks freed.
        Invoked by `ensure`/`max_coverable` so reclamation happens
        before any allocation decision."""
        seq = self.seqs[idx]
        assert seq is not None, idx
        freed = 0
        for gi, (g, w) in enumerate(zip(seq.groups, self.group_windows)):
            if not w:
                continue
            sp = min(max(0, (seq.length - w + 1) // self.block_size),
                     len(g.blocks))
            for j in range(g.slid, sp):
                b = g.blocks[j]
                if b == TRASH_BLOCK:
                    continue
                self._ref[gi][b] -= 1
                assert self._ref[gi][b] >= 0, \
                    f"refcount underflow on block {gi}/{b}"
                if self._ref[gi][b] == 0:
                    if (gi, b) in self._unrestored:
                        self._forget_restore(gi, b)
                    lo = self._lo_pending.pop((gi, b), None)
                    if lo is not None:
                        self.host.unpin((gi, lo))
                    h = self._hash_of.pop((gi, b), None)
                    if h is not None:
                        del self._index[(gi, h)]
                    self._free[gi].append(b)
                    freed += 1
                g.blocks[j] = TRASH_BLOCK
                self._set_table(gi, idx, j, TRASH_BLOCK)
            g.slid = max(g.slid, sp)
        self.window_freed_blocks += freed
        return freed

    def ensure(self, idx: int, n_tokens: int) -> bool:
        """Grow slot `idx`'s block tables (every window group) to cover
        positions [0, n_tokens), sliding windowed groups first so dead
        local blocks fund the growth. All-or-nothing; False when the
        free list (including reclaimable cached blocks) runs dry
        (caller preempts or defers)."""
        seq = self.seqs[idx]
        assert seq is not None, idx
        self.slide_window(idx)
        nb = -(-n_tokens // self.block_size)
        if all(len(g.blocks) >= nb for g in seq.groups):
            return True
        if n_tokens > self.capacity or any(
                max(0, nb - len(g.blocks)) > self.free_blocks(gi)
                for gi, g in enumerate(seq.groups)):
            return False
        for gi, g in enumerate(seq.groups):
            while len(g.blocks) < nb:
                b = self._alloc_block(gi)
                assert b is not None      # guarded by free_blocks above
                self._ref[gi][b] = 1
                self._set_table(gi, idx, len(g.blocks), b)
                g.blocks.append(b)
        return True

    def max_coverable(self, idx: int, start: int, want: int) -> int:
        """Largest take <= want such that `ensure(idx, start + take)`
        will succeed right now (window slides applied first): the
        engine's chunk planner asks this instead of reimplementing
        per-group block accounting."""
        seq = self.seqs[idx]
        assert seq is not None, idx
        self.slide_window(idx)
        avail = [self.free_blocks(gi) + len(g.blocks)
                 for gi, g in enumerate(seq.groups)]
        upper = min(start + want, self.capacity)
        take = 0
        # feasibility only changes at block boundaries: walk block counts
        # (<= max_blocks_per_seq iterations), not tokens
        bs = self.block_size
        for nb in range(-(-(start + 1) // bs), -(-upper // bs) + 1):
            if any(nb > a for a in avail):
                break
            take = min(nb * bs, upper) - start
        return take

    def set_length(self, idx: int, n_tokens: int) -> None:
        seq = self.seqs[idx]
        assert seq is not None and n_tokens <= len(seq.blocks) * self.block_size
        seq.length = n_tokens

    def truncate(self, idx: int, n_tokens: int) -> int:
        """Un-write the sequence's tail back to `n_tokens` — the
        speculative-decoding rollback: verification writes K+1 positions
        optimistically, and the rejected suffix must hand its block-table
        coverage back.

        Every block whose logical span lies entirely at/after `n_tokens`
        is dropped from every window group through the normal release
        machinery: shared blocks survive for their other holders
        (decref), registered exclusively-held blocks park in the LRU
        prefix cache (their content is fully committed and still
        attachable), unregistered ones return to the group's free list.
        No cache bytes are touched — reads beyond `seq.length` are
        masked by `kv_len`, and the next write at a kept position simply
        lands over the garbage.

        The committed-hash chain is cut back to the full blocks still
        covered, and a kept tail block that the cut partially
        invalidates is EVICTED from the prefix index: future writes will
        land below its registered content, and a registered block's
        bytes must never change (writers into shared blocks still
        COW-fork as usual). Slide-freed leading holes are never
        resurrected — truncation only ever shortens tables, and the
        slide point is clamped to the new block count. Returns the
        number of group-blocks dropped."""
        seq = self.seqs[idx]
        assert seq is not None and n_tokens >= 0, idx
        bs = self.block_size
        nb = -(-n_tokens // bs)              # blocks still covered
        nfull = n_tokens // bs               # ... of which fully valid
        dropped = 0
        for gi, g in enumerate(seq.groups):
            while len(g.blocks) > nb:
                j = len(g.blocks) - 1
                b = g.blocks.pop()
                if b != TRASH_BLOCK:         # below-slide holes stay holes
                    self._release_block(gi, b)
                    self._set_table(gi, idx, j, TRASH_BLOCK)
                    dropped += 1
            if len(g.hashes) > nfull:
                del g.hashes[nfull:]
                if nfull < nb:
                    # the kept tail block was committed full but is now
                    # partially un-written: evict its index entry before
                    # any future write can diverge from the registered
                    # content (the physical bytes are still intact for
                    # every current sharer — their writes COW-fork)
                    b = g.blocks[nb - 1]
                    h = self._hash_of.pop((gi, b), None)
                    if h is not None:
                        del self._index[(gi, h)]
                        self.prefix_stats["evictions"] += 1
                    lo = self._lo_pending.pop((gi, b), None)
                    if lo is not None:
                        self.host.unpin((gi, lo))
            g.slid = min(g.slid, nb)
        seq.length = min(seq.length, n_tokens)
        return dropped

    def release(self, idx: int) -> None:
        """Decref (not free) every block the sequence holds in any
        group — shared blocks survive for their other holders,
        registered blocks go to the LRU cache."""
        seq = self.seqs[idx]
        if seq is None:
            return
        for gi, g in enumerate(seq.groups):
            for b in reversed(g.blocks):
                if b != TRASH_BLOCK:
                    self._release_block(gi, b)
            # entries beyond len(g.blocks) and below the slide point are
            # already trash by invariant
            for j, b in enumerate(g.blocks):
                if b != TRASH_BLOCK:
                    self._set_table(gi, idx, j, TRASH_BLOCK)
        self.seqs[idx] = None

    def youngest(self) -> int | None:
        """Slot of the most recently admitted live sequence (the
        preemption victim), or None when nothing is live."""
        live = [(s.admitted, i) for i, s in enumerate(self.seqs)
                if s is not None]
        return max(live)[1] if live else None

    # -- prefix caching --------------------------------------------------------
    def _match_plan(self, tokens, allow_host: bool = False
                    ) -> tuple[int, list[tuple[int, list[int | None]]],
                               list[int]]:
        """Group-aware longest servable cached prefix of `tokens`.

        Returns (matched tokens m, per-group (j_lo, block ids for
        logical blocks [j_lo, m/bs)), chain hashes of the matched full
        blocks). A prefill resuming at q0 = min(m, len(tokens)-1) — the
        engine always recomputes >= 1 token — needs, per group, every
        cached block covering positions [q0 - window + 1, m); global
        groups (window None) need the whole from-root run [0, m).
        Slide-freed blocks were evicted from the index, so they can
        never be matched for a local group here.

        With `allow_host`, hashes absent from the device index but
        present in the host tier (or queued for capture — the engine
        always captures before it uploads) are servable too: their plan
        entries are None, and `attach_prefix` allocates fresh device
        blocks + restore jobs for them."""
        bs = self.block_size
        empty = [(0, []) for _ in self.group_windows]
        if not self.prefix_cache:
            return 0, empty, []
        host = self.host if allow_host else None

        def servable(gi: int, h: int) -> bool:
            if (gi, h) in self._index:
                return True
            if host is None:
                return False
            # spill-pending hashes are still device bytes (captured
            # before upload), so only true host entries need the
            # integrity check
            return ((gi, h) in self._spill_pending
                    or self.host_ok(gi, h))

        hashes: list[int] = []
        parent = _ROOT_HASH
        for i in range(min(len(tokens) // bs, self.max_blocks_per_seq)):
            h = _chain_hash(parent, tuple(tokens[i * bs: (i + 1) * bs]))
            hashes.append(h)
            parent = h
        m = len(hashes)
        for gi, w in enumerate(self.group_windows):
            if w:
                continue
            run = 0
            for h in hashes:
                if not servable(gi, h):
                    break
                run += 1
            m = min(m, run)
        while m > 0:
            q0 = min(m * bs, len(tokens) - 1)
            plan: list[tuple[int, list[int | None]]] | None = []
            # when a windowed group is missing block j, every candidate
            # m' in (j, m) still needs j (j_lo shrinks with m), so the
            # next viable candidate is m' = j — one jump per missing
            # block keeps the whole search O(max_blocks_per_seq)
            next_m = m - 1
            for gi, w in enumerate(self.group_windows):
                j_lo = 0 if not w else max(0, q0 - w + 1) // bs
                blks: list[int | None] = []
                for j in range(j_lo, m):
                    b = self._index.get((gi, hashes[j]))
                    if b is None and not (host is not None
                                          and servable(gi, hashes[j])):
                        plan = None
                        next_m = min(next_m, j)
                        break
                    blks.append(b)
                if plan is None:
                    break
                plan.append((j_lo, blks))
            if plan is not None:
                return m * bs, plan, hashes[:m]
            m = next_m
        return 0, empty, []

    def lookup_prefix(self, tokens, allow_host: bool = False) -> int:
        """Matched-prefix length in tokens (no side effects) — the
        largest offset a prefill could resume at with every window
        group's needed blocks cached (on device, or — with `allow_host`
        — restorable from the host tier)."""
        return self._match_plan(tokens, allow_host)[0]

    def prefix_admit_discount(self, tokens) -> tuple[int, ...]:
        """Per-group blocks the admission watermark may discount for
        `tokens`: matched blocks held LIVE by other sequences (sharing
        them costs nothing). Matched blocks parked in a group's LRU pool
        are already counted by `free_blocks()`, so discounting them too
        would double-count. Feed the result straight to
        `try_allocate(cached_blocks=...)`."""
        if not self.prefix_cache:
            return (0,) * self.n_groups
        _, plan, _ = self._match_plan(tokens)
        return tuple(sum(1 for b in blks
                         if b is not None and self._ref[gi][b] > 0)
                     for gi, (_, blks) in enumerate(plan))

    def attach_prefix(self, idx: int, tokens, allow_host: bool = False
                      ) -> int:
        """Share the longest cached servable prefix of `tokens` into
        freshly-allocated slot `idx` (incref each matched block, pull
        zero-ref ones out of the LRU pool). Windowed groups attach only
        the blocks covering the resume position's lookback window and
        start pre-slid below it. Returns the matched token count; the
        caller starts prefill at that offset (recomputing at least one
        token — `cow_for_write` forks the tail block if that recompute
        lands in a shared one).

        With `allow_host`, prefix blocks living only in the host tier
        are re-admitted: a fresh device block is allocated and
        registered for each, a restore job is queued for the engine's
        scatter-upload drain, and the block is marked unrestored (rows
        holding one are gated out of chunk scheduling until the bytes
        arrive). If the free pool cannot cover the host hits, the match
        falls back to device-resident blocks only."""
        seq = self.seqs[idx]
        assert seq is not None and not any(g.blocks for g in seq.groups), \
            "attach before ensure"
        if not self.prefix_cache:
            return 0
        m_tokens, plan, hashes = self._match_plan(tokens, allow_host)
        if allow_host:
            # all-or-nothing feasibility for the host hits: the fresh
            # blocks they need must come from the free list + LRU pool
            # MINUS the plan's own device-matched LRU residents (about
            # to be pulled out and increfed, so not allocatable)
            for gi, (_, blks) in enumerate(plan):
                need = sum(1 for b in blks if b is None)
                lru_held = sum(1 for b in blks
                               if b is not None and self._ref[gi][b] == 0)
                if need > self.free_blocks(gi) - lru_held:
                    m_tokens, plan, hashes = self._match_plan(tokens, False)
                    break
        shared = restored = 0
        # pass 1: incref every device-matched block FIRST, so the host
        # hits' allocations below can never reclaim a plan block out of
        # the LRU pool
        for gi, (g, (j_lo, blks)) in enumerate(zip(seq.groups, plan)):
            g.blocks = [TRASH_BLOCK] * j_lo + list(blks)
            g.hashes = list(hashes)
            g.slid = j_lo
            for j, b in enumerate(blks, start=j_lo):
                if b is None:
                    continue
                if self._ref[gi][b] == 0:
                    del self._lru[gi][b]
                self._ref[gi][b] += 1
                self._set_table(gi, idx, j, b)
            shared += len(blks)
        # pass 2: allocate + queue a restore for each host hit
        for gi, (g, (j_lo, blks)) in enumerate(zip(seq.groups, plan)):
            for j, src in enumerate(blks, start=j_lo):
                if src is not None:
                    continue
                b = self._alloc_block(gi)
                assert b is not None, "host-hit feasibility pre-checked"
                h = hashes[j]
                self._ref[gi][b] = 1
                self._index[(gi, h)] = b
                self._hash_of[(gi, b)] = h
                g.blocks[j] = b
                self._set_table(gi, idx, j, b)
                self._ticket += 1
                self._unrestored[(gi, b)] = (self._ticket, h)
                self.restore_jobs.append((gi, b, h, self._ticket))
                if (gi, h) in self.host:
                    self.host.pin((gi, h))   # spill-pending entries are
                else:                        # pinned at capture time
                    assert (gi, h) in self._spill_pending, (gi, h)
                restored += 1
        seq.length = m_tokens
        st = self.prefix_stats
        st["queries"] += 1
        st["lookup_tokens"] += len(tokens)
        st["hit_tokens"] += m_tokens
        st["blocks_shared"] += shared
        st["host_hit_blocks"] += restored
        return m_tokens

    def cow_for_write(self, idx: int, start: int, end: int
                      ) -> list[tuple[int, int, int]] | None:
        """Copy-on-write fork of every shared block that the token write
        range [start, end) touches, in every window group: allocate a
        private replacement in that group's id space, decref the shared
        original, and return (group, src, dst) triples whose cache
        bytes — the GROUP'S layer rows only — the CALLER must copy
        before writing. Returns None when a fork cannot be allocated
        (some group's pool truly exhausted — caller preempts). Blocks
        must already be ensured over the range; slide-freed holes need
        no fork (their writes land in the trash block)."""
        seq = self.seqs[idx]
        assert seq is not None and end <= len(seq.blocks) * self.block_size
        span = range(start // self.block_size, -(-end // self.block_size))
        # all-or-nothing: check every fork is allocatable BEFORE mutating,
        # so a failure never strands completed forks whose (src, dst)
        # pairs the caller would lose (bytes never copied -> stale reads)
        for gi, g in enumerate(seq.groups):
            if sum(1 for bi in span
                   if g.blocks[bi] != TRASH_BLOCK
                   and self._ref[gi][g.blocks[bi]] > 1) \
                    > self.free_blocks(gi):
                return None
        triples: list[tuple[int, int, int]] = []
        for gi, g in enumerate(seq.groups):
            for bi in span:
                src = g.blocks[bi]
                if src == TRASH_BLOCK or self._ref[gi][src] <= 1:
                    continue
                dst = self._alloc_block(gi)
                assert dst is not None        # guarded above
                self._ref[gi][dst] = 1
                self._release_block(gi, src)
                g.blocks[bi] = dst
                self._set_table(gi, idx, bi, dst)
                triples.append((gi, src, dst))
                self.prefix_stats["cow_forks"] += 1
        return triples

    def commit(self, idx: int, n_tokens: int, tokens) -> None:
        """Record that positions [0, n_tokens) now hold the KV of
        `tokens[:n_tokens]`, and register every newly-FULL block in the
        per-group content-hash index so later sequences can share it
        (slide-freed holes extend the hash chain but register nothing).
        `tokens` must be the sequence's full committed token stream."""
        self.set_length(idx, n_tokens)
        if not self.prefix_cache:
            return
        seq = self.seqs[idx]
        bs = self.block_size
        for gi, g in enumerate(seq.groups):
            parent = g.hashes[-1] if g.hashes else _ROOT_HASH
            for bi in range(len(g.hashes), n_tokens // bs):
                h = _chain_hash(parent, tuple(tokens[bi * bs: (bi + 1) * bs]))
                b = g.blocks[bi]
                if b != TRASH_BLOCK and (gi, h) not in self._index \
                        and (gi, b) not in self._hash_of:
                    self._index[(gi, h)] = b
                    self._hash_of[(gi, b)] = h
                g.hashes.append(h)
                parent = h

    # -- tiered KV: host offload + restore ------------------------------------
    def _forget_restore(self, g: int, b: int) -> None:
        """Void block (g, b)'s pending restore: drop the ticket (the
        queued job dies at claim time) and release its host-entry pin.
        A job against a still-spill-pending entry never took a pin (pins
        are applied at capture, `store_spill`), so there is nothing to
        release in that case."""
        _, h = self._unrestored.pop((g, b))
        if (g, h) in self.host and self.host.pinned((g, h)):
            self.host.unpin((g, h))

    def host_ok(self, g: int, h: int) -> bool:
        """Is host entry (g, h) present AND integrity-clean? A checksum
        mismatch drops the entry (when unpinned; pinned copies are left
        for the restore drain to handle) so later matches recompute
        instead of restoring garbage."""
        key = (g, h)
        if self.host is None or key not in self.host:
            return False
        if self.host.verify(key):
            return True
        if not self.host.pinned(key):
            self.host.discard(key)
        return False

    def rows_holding(self, g: int, b: int) -> list[int]:
        """Slot indices whose block table references physical block
        (g, b) — the owners a corrupt-restore fallback must preempt."""
        return [idx for idx, s in enumerate(self.seqs)
                if s is not None and b in s.groups[g].blocks]

    def purge_block(self, g: int, b: int) -> None:
        """Evict a zero-ref block outright — deregister its content and
        return it to the free list (corrupt-fallback path: the block's
        bytes must never be prefix-matched again)."""
        assert self._ref[g][b] == 0, f"purge of live block {g}/{b}"
        self._lru[g].pop(b, None)
        h = self._hash_of.pop((g, b), None)
        if h is not None:
            del self._index[(g, h)]
        if b not in self._free[g]:
            self._free[g].append(b)

    def take_spills(self) -> list[tuple[int, int, int]]:
        """Drain the (group, block, hash) capture queue. The caller
        (engine `_flush_spills`) must gather + device_get these blocks'
        pool bytes and hand them to `store_spill` BEFORE any
        cache-writing dispatch — the evicted ids are already back in
        circulation and their bytes survive only until the next write
        lands."""
        out, self._spill_queue = self._spill_queue, []
        self._spill_pending.clear()
        return out

    def store_spill(self, g: int, h: int, planes: dict) -> None:
        """Deposit one captured block in the host tier and apply the
        pins any already-queued restore jobs deferred (a job created
        while its entry was still spill-pending could not pin it)."""
        self.host.put((g, h), planes)
        pins = sum(1 for (gi, _b), (_t, hh) in self._unrestored.items()
                   if gi == g and hh == h)
        for _ in range(pins):
            self.host.pin((g, h))

    def claim_restore(self, g: int, b: int, h: int, ticket: int) -> bool:
        """True iff a drained restore job is still wanted: the dst block
        is still attached and the ticket is current (a release/preempt
        of the holder voids the job — the block id may since have been
        reallocated for something else entirely)."""
        return self._unrestored.get((g, b)) == (ticket, h)

    def finish_restore(self, g: int, b: int, h: int,
                       lo_pending: bool = False) -> None:
        """Mark block (g, b) device-resident again. `lo_pending`
        (planar pools) records that only the fp8 hi planes were
        uploaded: the host entry stays pinned as the lazy lo-plane
        source until the first FP16-mode touch."""
        del self._unrestored[(g, b)]
        if lo_pending:
            self._lo_pending[(g, b)] = h     # inherits the job's pin
        else:
            self.host.unpin((g, h))

    def row_unrestored(self, idx: int) -> bool:
        """Does slot `idx` hold any block whose restore has not landed?
        The engine gates such rows out of chunk scheduling — a prefill
        reading them would see garbage."""
        seq = self.seqs[idx]
        if seq is None or not self._unrestored:
            return False
        return any((gi, b) in self._unrestored
                   for gi, g in enumerate(seq.groups) for b in g.blocks)

    def take_lo_pending(self) -> list[tuple[int, int, int]]:
        """Drain ALL lazily-deferred lo-plane uploads as (group, block,
        hash) — the engine's first FP16-mode dispatch must be preceded
        by these bytes. Host-entry pins transfer to the caller, which
        unpins after the upload."""
        out = [(g, b, h) for (g, b), h in self._lo_pending.items()]
        self._lo_pending.clear()
        return out

    def take_lo_pending_for(self, pairs) -> list[tuple[int, int, int]]:
        """Drain the lo-plane uploads for specific (group, block) pairs
        — the write-range guard: a write into a lo-pending block must
        not race a later whole-block lo scatter (the scatter would
        clobber the fresh lo bytes with the stale host copy)."""
        out = []
        for g, b in pairs:
            h = self._lo_pending.pop((g, b), None)
            if h is not None:
                out.append((g, b, h))
        return out

    def lo_pending_in_range(self, idx: int, start: int, end: int
                            ) -> list[tuple[int, int]]:
        """(group, block) pairs with deferred lo planes that the token
        write range [start, end) on slot `idx` touches."""
        if not self._lo_pending:
            return []
        seq = self.seqs[idx]
        span = range(start // self.block_size, -(-end // self.block_size))
        return [(gi, g.blocks[bi]) for gi, g in enumerate(seq.groups)
                for bi in span if bi < len(g.blocks)
                and (gi, g.blocks[bi]) in self._lo_pending]

    def mirror_jobs(self) -> list[tuple[int, int, int]]:
        """(group, block, hash) of every registered device block NOT yet
        mirrored in the host tier — `save_prefix_store` captures these
        (without evicting anything) so the serialized store covers the
        whole prefix index. Unrestored blocks hold garbage and are
        skipped (their content is already hosted by definition)."""
        if self.host is None:
            return []
        return [(g, b, h) for (g, h), b in self._index.items()
                if (g, h) not in self.host
                and (g, h) not in self._spill_pending
                and (g, b) not in self._unrestored]

    # -- invariant audit (tests) ----------------------------------------------
    def check_invariants(self) -> None:
        ref = [[0] * (self.n_blocks + 1) for _ in range(self.n_groups)]
        for s in self.seqs:
            if s is None:
                continue
            for gi, g in enumerate(s.groups):
                for b in g.blocks:
                    if b != TRASH_BLOCK:
                        ref[gi][b] += 1
        assert ref == self._ref, (ref, self._ref)
        for gi in range(self.n_groups):
            free, lru = set(self._free[gi]), set(self._lru[gi])
            assert not (free & lru), f"group {gi} block both free and cached"
            for b in range(1, self.n_blocks + 1):
                if self._ref[gi][b] == 0:
                    assert (b in free) ^ (b in lru), \
                        f"zero-ref block {gi}/{b} neither free nor " \
                        f"cached (or both)"
                else:
                    assert b not in free and b not in lru, \
                        f"live block {gi}/{b} on the free/cached list"
        assert set(self._hash_of) == {(g, b) for (g, _h), b
                                      in self._index.items()}
        for (gi, h), b in self._index.items():
            assert self._hash_of[(gi, b)] == h
            assert b not in self._free[gi], \
                f"indexed block {gi}/{b} on the free list"
        for i, s in enumerate(self.seqs):
            for gi in range(self.n_groups):
                row = np.full(self.max_blocks_per_seq, TRASH_BLOCK, np.int32)
                if s is not None:
                    gl = s.groups[gi].blocks
                    row[: len(gl)] = gl
                assert (self._tables[gi, i] == row).all(), \
                    f"stale table row (group {gi}, slot {i})"
            if s is None:
                continue
            for g, w in zip(s.groups, self.group_windows):
                if not w:
                    assert g.slid == 0, "global group slid"
                assert all(b == TRASH_BLOCK for b in g.blocks[: g.slid]), \
                    "live block below the slide point"
                assert all(b != TRASH_BLOCK for b in g.blocks[g.slid:]), \
                    "hole above the slide point"
        # tiered-KV: unrestored blocks are live and registered-or-voided,
        # spill-pending entries are not yet hosted, lo-pending blocks are
        # live or LRU-parked with a hosted (and pinned) source, and the
        # host tier's pin/byte accounting is exact
        for (g, b), (_t, h) in self._unrestored.items():
            assert self._ref[g][b] > 0, f"unrestored block {g}/{b} unheld"
            assert b not in self._free[g] and b not in self._lru[g]
        qhashes = {(g, h) for g, _b, h in self._spill_queue}
        assert qhashes == self._spill_pending, \
            (qhashes, self._spill_pending)
        if self.host is not None:
            for g, h in self._spill_pending:
                assert (g, h) not in self.host, \
                    f"spill queued for already-hosted entry {g}/{h}"
            for (g, b), h in self._lo_pending.items():
                assert (g, h) in self.host, f"lo-pending {g}/{b} unsourced"
                assert self.host.pinned((g, h)), f"lo source {g}/{h} unpinned"
                assert self._ref[g][b] > 0 or b in self._lru[g], \
                    f"lo-pending block {g}/{b} neither live nor cached"
            want_pins: collections.Counter = collections.Counter()
            for (g, _b), h in self._lo_pending.items():
                want_pins[(g, h)] += 1
            for (g, _b), (_t, h) in self._unrestored.items():
                if (g, h) in self.host:
                    want_pins[(g, h)] += 1
            assert want_pins == self.host._pins, \
                (dict(want_pins), dict(self.host._pins))
            assert self.host.bytes == sum(
                self.host.entry_bytes(p) for p in self.host.entries.values())
            assert set(self.host._sums) == set(self.host.entries), \
                "host checksum map out of sync with entries"
        if self._dev_tables is not None:
            # read-only check: overlay the pending dirty entries on the
            # mirror instead of flushing (device_tables() would mutate
            # the very h2d counters the bench rows report)
            # nfp: ignore[NFP001] opt-in debug sanitizer: auditing the device mirror IS the sync
            mirror = np.asarray(self._dev_tables).copy()
            for (g, s, j), b in self._dirty.items():
                mirror[g, s, j] = b
            assert (mirror == self._tables).all(), \
                "device table mirror diverged from the host tables"
