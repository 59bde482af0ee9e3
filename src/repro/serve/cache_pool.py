"""Paged, mode-switchable augmented KV pool — the serving-layer analogue of
the paper's on-demand capacity.

The pool manages fixed-size pages (``cfg.amc.page_size`` tokens × all
layers × K+V) that each live in one of two modes:

  Normal     1 logical bit per physical bit: bf16 rows in the ``kn``/``vn``
             arena (the 6T static mode).
  Augmented  capacity_factor > 1: int4/int8-packed rows + per-token scales
             in the ``kp``/``vp``/``ks``/``vs`` arena (the 8T/7T dynamic
             mode) — written through the existing `quantize_pack_kv` path.

One BYTE BUDGET models the physical array (the paper's SRAM macro): a
Normal page charges `page_bytes_normal` against it, an Augmented page only
`page_bytes_aug` (~3.6x less for int4+scales). Under memory pressure the
pool *augments* cold pages — move them to the packed plane, release the
byte difference — so more sequences can be admitted instead of rejected.
The two arenas are the staging areas for the two electrical configurations
of the same budgeted cells; `live_bytes <= budget_bytes` is the invariant
the allocator enforces.

Augmented pages are DYNAMIC: each carries a `core.retention.RefreshPolicy`
stamped on every write; after `retention_steps` decode steps the page
expires and the refresh scheduler must re-materialize it (restamp + traffic
accounting) or promote it back to Normal. `refresh_due()` lists expired
pages; the serving scheduler drains that list interleaved with decode.

Host-side metadata (numpy page tables, free lists, stamps) drives
device-side arenas (jax arrays, donated through the jitted decode step).
`device_tables()` emits the scalar-prefetch operands of the paged
attention kernel, including the HOLD-PREVIOUS gather indices that let the
mode-mismatched arena skip its DMA.

`PagedKVPool` implements the `serve.state_store.StateStore` interface
(alloc / free / gather-tables / augment / promote / refresh / bytes) — it
is the attention-KV member of the per-family store registry. With
``prefix_tokens > 0`` the page table grows a second band of rows
(``max_batch`` .. ``2*max_batch``) holding each slot's STATIC-LENGTH
prefix pages — the encoder-decoder cross-attention KV, written once at
admission and read with a fixed length every decode step (the paper's
static plane; cold by construction, so these are the first pages the
pressure policy augments).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import faults as F
from repro.core.retention import RefreshPolicy
from repro.kernels import ops as K
from repro.models import layers as L

POOL_MODES = ("normal-only", "augment-on-pressure", "always-augmented")


def to_device(x) -> jax.Array:
    """Host array -> device array through a private copy.

    The host-to-device transfer may read its source after the call has
    returned, so the serving stack never hands it its live host
    bookkeeping (positions, page tables, slot modes): those are mutated
    right after a dispatch, and a mutation racing the transfer changes
    what the device step reads."""
    return jnp.asarray(np.array(x, copy=True))


def resolve_pool_mode(cfg: ModelConfig) -> str:
    """Validated `cfg.amc.resolved_pool_mode` (auto follows kv_mode)."""
    mode = cfg.amc.resolved_pool_mode
    if mode not in POOL_MODES:
        raise ValueError(f"unknown pool_mode {mode!r}")
    return mode


def aug_bits_for(cfg: ModelConfig) -> int:
    """Augmented-plane width of this model's pool (cfg.amc.aug_bits)."""
    return cfg.amc.aug_bits


@dataclasses.dataclass(frozen=True)
class PageGeometry:
    """Static shape/byte facts of one pool instance."""
    n_layers: int
    kv_heads: int
    head_dim: int
    page_size: int
    aug_bits: int

    @property
    def d_store(self) -> int:
        return self.head_dim // 2 if self.aug_bits == 4 else self.head_dim

    @property
    def page_bytes_normal(self) -> int:
        # K + V, all layers, bf16
        return 2 * self.n_layers * self.kv_heads * self.page_size \
            * self.head_dim * 2

    @property
    def page_bytes_aug(self) -> int:
        # K + V packed rows + bf16 per-(token, head) scales
        return 2 * self.n_layers * self.kv_heads * self.page_size \
            * (self.d_store + 2)

    @property
    def capacity_factor(self) -> float:
        return self.page_bytes_normal / self.page_bytes_aug


class PagedKVPool:
    """See module docstring. `max_batch` bounds the running-batch width
    (rows of the page table); capacity in tokens is budget-bound, not
    row-bound."""

    def __init__(self, cfg: ModelConfig, *, max_batch: int, max_seq: int,
                 pages_normal: Optional[int] = None,
                 pages_packed: Optional[int] = None,
                 budget_bytes: Optional[int] = None,
                 retention_steps: Optional[int] = None,
                 prefix_tokens: int = 0, n_layers: Optional[int] = None):
        a = cfg.amc
        self.cfg = cfg
        self.pool_mode = resolve_pool_mode(cfg)
        self.geom = PageGeometry(cfg.n_layers if n_layers is None
                                 else n_layers, cfg.n_kv_heads, cfg.hd,
                                 a.page_size, aug_bits_for(cfg))
        self.max_batch = max_batch
        self.max_pages = -(-max_seq // a.page_size)          # ceil
        self.prefix_tokens = prefix_tokens
        self.prefix_pages = -(-prefix_tokens // a.page_size) \
            if prefix_tokens else 0
        self.retention_steps = (a.retention_steps if retention_steps is None
                                else retention_steps)
        B, maxP = max_batch, self.max_pages
        pbn, pba = self.geom.page_bytes_normal, self.geom.page_bytes_aug
        # per-row page cost: decode band + (optional) static prefix band
        row_pages = maxP + self.prefix_pages
        # default arena sizing: legacy-equivalent capacity (every row can
        # reach max_seq tokens in any mode the policy may choose)
        if pages_normal is None:
            pages_normal = 0 if self.pool_mode == "always-augmented" \
                else B * row_pages
        if pages_packed is None:
            pages_packed = 0 if self.pool_mode == "normal-only" \
                else B * row_pages
        self.pages_normal, self.pages_packed = pages_normal, pages_packed
        self.budget_bytes = (B * row_pages * pbn if budget_bytes is None
                             else budget_bytes)
        seq_cost = row_pages * (pbn if self.pool_mode == "normal-only"
                                else pba)
        if self.budget_bytes < seq_cost:
            raise ValueError(
                f"budget_bytes={self.budget_bytes} cannot hold one full "
                f"sequence ({seq_cost} B in the pool's cheapest mode)")
        self.live_bytes = 0

        # device arenas — physical page 0 of each is the write-dump page
        # (masked-off scatter rows land there), so usable pages start at 1
        g = self.geom
        Nn, Np = pages_normal + 1, pages_packed + 1
        Lg, KV, P = g.n_layers, g.kv_heads, g.page_size
        self.arenas = {
            "kn": jnp.zeros((Lg, Nn, KV, P, g.head_dim), jnp.bfloat16),
            "vn": jnp.zeros((Lg, Nn, KV, P, g.head_dim), jnp.bfloat16),
            "kp": jnp.zeros((Lg, Np, KV, P, g.d_store),
                            jnp.uint8 if g.aug_bits == 4 else jnp.int8),
            "vp": jnp.zeros((Lg, Np, KV, P, g.d_store),
                            jnp.uint8 if g.aug_bits == 4 else jnp.int8),
            "ks": jnp.zeros((Lg, Np, KV, P), jnp.bfloat16),
            "vs": jnp.zeros((Lg, Np, KV, P), jnp.bfloat16),
        }

        # host page tables (numpy; mirrored to device per dispatch).
        # Rows [0, B) are the decode band; with prefix_tokens > 0 rows
        # [B, 2B) are each slot's static prefix band (table width covers
        # the wider of the two bands). With prefix_cache > 0 a SHARE band
        # of `share_entries` rows follows: each row anchors one cached
        # prompt prefix whose pages are refcount-shared into decode rows
        # (serve/prefix.py owns the hash index; the pool owns the pages).
        n_rows = 2 * B if self.prefix_pages else B
        self.share_entries = int(getattr(a, "prefix_cache", 0))
        self._share_base = n_rows
        n_rows += self.share_entries
        tw = max(maxP, self.prefix_pages)
        self.table_width = tw
        self.page_table = np.zeros((n_rows, tw), np.int32)
        self.page_mode = np.zeros((n_rows, tw), np.int32)  # 0 normal, 1 aug
        self.allocated = np.zeros((n_rows, tw), bool)
        self.last_write = np.full((n_rows, tw), -1, np.int64)
        self.free_normal = list(range(Nn - 1, 0, -1))    # pop() -> low first
        self.free_packed = list(range(Np - 1, 0, -1))
        self.policies: dict[tuple[int, int], RefreshPolicy] = {}
        self._tables_cache: Optional[dict] = None   # invalidated on any
                                                    # page-table mutation
        self.stats = {
            "augment_events": 0, "promote_events": 0, "refreshes": 0,
            "refresh_bytes": 0, "augment_bytes": 0,
            "maintenance_dispatches": 0, "alloc_failures": 0,
            "peak_live_bytes": 0, "retracted_pages": 0,
            "faults_injected": 0, "faults_detected": 0, "faults_masked": 0,
            "refresh_misses": 0, "integrity_checks": 0, "pinned_normal": 0,
            "pages_decommissioned": 0,
            "cow_events": 0, "cow_bytes": 0, "prefix_demotions": 0,
            "prefix_evictions": 0,
        }
        # physical-page reference counts, keyed (mode, phys): every
        # allocated page carries one; shared-prefix aliases raise it.
        # live_bytes charges each PHYSICAL page once — aliases are free.
        self._refcount: dict[tuple[int, int], int] = {}
        self._prefix_index = None   # serve/prefix.py PrefixIndex (optional)
        # retention-fault machinery (core/faults.py) — inert until a
        # FaultModel is attached; all dicts stay empty at fault_rate=0
        self._fm: Optional[F.FaultModel] = None
        self._integrity = False
        self._fault_tag = ""
        self._words: dict[tuple[int, int], int] = {}   # integrity words
        self._dirty: set[tuple[int, int]] = set()      # rewritten since flush
        self._pending: set[tuple[int, int]] = set()    # injected, unscanned
        self._masters: dict[tuple[int, int], tuple] = {}  # static-band copies
        self._offenders: dict[str, int] = {}           # by physical unit id
        self._decommission: set[int] = set()           # weak packed pages
        self._obs = None        # EngineObs facade (attach_obs) — optional
        self._live_by_mode = [0, 0]   # live pages per mode, kept
        # incrementally so the per-step mode-mix sample is O(1)

    # -- byte accounting ------------------------------------------------------

    def _cost(self, mode: int) -> int:
        return self.geom.page_bytes_normal if mode == 0 \
            else self.geom.page_bytes_aug

    def free_page_count(self, mode: int) -> int:
        return len(self.free_normal if mode == 0 else self.free_packed)

    def can_admit_tokens(self, n_tokens: int) -> bool:
        """Admission check: could `n_tokens` more tokens be stored right
        now, augmenting cold pages if the policy allows? Counts the
        static prefix band's pages on top of the prompt's own, and the
        IDLE shared-prefix pages (cached entries no live request maps)
        as reclaimable headroom — the allocator evicts those entries at
        refcount 0 before failing."""
        pages = -(-n_tokens // self.geom.page_size) + self.prefix_pages
        idle_n, idle_a = self._prefix_idle_counts()
        free_b = (self.budget_bytes - self.live_bytes
                  + idle_n * self._cost(0) + idle_a * self._cost(1))
        free0 = self.free_page_count(0) + idle_n
        free1 = self.free_page_count(1) + idle_a
        if self.pool_mode == "normal-only":
            return pages <= free0 and pages * self._cost(0) <= free_b
        if (self.pool_mode == "augment-on-pressure"
                and pages <= free0
                and pages * self._cost(0) <= free_b):
            return True     # fits in the static plane, no pressure at all
        if pages > free1:
            return False
        need = pages * self._cost(1) - free_b
        if need <= 0:
            return True
        per = self._cost(0) - self._cost(1)   # bytes one augmentation frees
        n_aug = -(-need // per)
        # each augmentation consumes one free packed slot ON TOP of the
        # request's own pages — don't promise an admission alloc_page
        # cannot deliver
        return (self.pool_mode == "augment-on-pressure"
                and n_aug <= self._augmentable_count()
                and pages + n_aug <= free1)

    # -- allocation -----------------------------------------------------------

    def alloc_page(self, row: int, lp: int, step: int) -> bool:
        """Allocate the logical page (row, lp). Mode policy: normal-only /
        always-augmented pin the plane; augment-on-pressure prefers Normal
        and falls back to Augmented, augmenting cold pages when even the
        packed plane doesn't fit the budget. False = pool exhausted."""
        assert not self.allocated[row, lp], (row, lp)
        order = {"normal-only": (0,), "always-augmented": (1,),
                 "augment-on-pressure": (0, 1)}[self.pool_mode]
        while True:
            for mode in order:
                if self._try_place(row, lp, mode, step):
                    return True
            if self.pool_mode == "augment-on-pressure":
                # pressure: demote cold Normal pages to the packed plane
                # until the budget fits one more Augmented page; cold
                # shared-prefix pages demote along this ladder too, and
                # idle cached prefixes are evicted (refcount 0) last
                while (self.live_bytes + self._cost(1) > self.budget_bytes
                       or self.free_page_count(1) == 0):
                    if self._augment_coldest(step):
                        continue
                    if self._reclaim_prefix(step):
                        continue
                    self.stats["alloc_failures"] += 1
                    return False
                if self._try_place(row, lp, 1, step):
                    return True
            # pinned-mode pools reach here with both planes exhausted:
            # evicting one idle cached prefix frees its pages + bytes,
            # then the order loop retries
            if not self._reclaim_prefix(step):
                self.stats["alloc_failures"] += 1
                return False

    def _try_place(self, row: int, lp: int, mode: int, step: int) -> bool:
        cost = self._cost(mode)
        free = self.free_normal if mode == 0 else self.free_packed
        if not free or self.live_bytes + cost > self.budget_bytes:
            return False
        phys = free.pop()
        self._tables_cache = None
        self.page_table[row, lp] = phys
        self.page_mode[row, lp] = mode
        self.allocated[row, lp] = True
        self.last_write[row, lp] = step
        self._refcount[(mode, phys)] = 1
        self.live_bytes += cost
        self._live_by_mode[mode] += 1
        self.stats["peak_live_bytes"] = max(self.stats["peak_live_bytes"],
                                            self.live_bytes)
        if mode == 1:
            pol = RefreshPolicy(retention_steps=self.retention_steps)
            pol.stamp(step)
            self.policies[(row, lp)] = pol
            if self._fm is not None:
                self._dirty.add((row, lp))
        return True

    def free_row(self, row: int) -> None:
        for lp in np.flatnonzero(self.allocated[row]):
            self._release(row, int(lp))

    # -- StateStore interface --------------------------------------------------
    # (serve/state_store.py documents the contract; the scheduler and the
    # engine talk to every decode-state store through these.)

    kind = "paged"

    def _prefix_row(self, row: int) -> int:
        return self.max_batch + row

    def admit_row(self, row: int, n_tokens: int, step: int, *,
                  shared=None) -> bool:
        """All-or-nothing admission: the prompt's decode-band pages plus
        (when this pool carries a static prefix) the row's prefix pages,
        zero-initialized so recycled physical pages never leak a previous
        row's KV through the static-length read. With ``shared=(erow, m)``
        the first ``m`` prompt tokens are covered by the cached prefix
        anchored at share-band row ``erow``: its full pages are mapped
        into this row's table by refcount (no new storage, no prefill),
        only the tail allocates fresh pages."""
        pages = -(-max(n_tokens, 1) // self.geom.page_size)
        done: list[tuple[int, int]] = []
        share_pages = 0
        if shared is not None and self.share_entries:
            erow, m = shared
            # ceil: a mid-page match maps the entry's boundary page too —
            # the first write past the match COWs it (ensure_position)
            share_pages = min(-(-m // self.geom.page_size), pages)
            for lp in range(share_pages):
                self.share_page(erow, lp, row, lp, step)
                done.append((row, lp))
        for lp in range(share_pages, pages):
            if not self.alloc_page(row, lp, step):
                for r, d in done:
                    self._release(r, d)
                return False
            done.append((row, lp))
        prow = self._prefix_row(row)
        for lp in range(self.prefix_pages):
            if not self.alloc_page(prow, lp, step):
                for r, d in done:
                    self._release(r, d)
                return False
            done.append((prow, lp))
            self._zero_physical(prow, lp)
        return True

    def _zero_physical(self, row: int, lp: int) -> None:
        """Zero the physical page behind (row, lp) in its current plane —
        prefix pages are read to their full static length, so stale data
        from a recycled page must be scrubbed at allocation."""
        phys = int(self.page_table[row, lp])
        mode = int(self.page_mode[row, lp])
        self.arenas = _zero_page_op(self.arenas, phys, mode=mode)
        self.stats["maintenance_dispatches"] += 1

    def ensure_position(self, row: int, pos: int, step: int) -> bool:
        """Guarantee the decode-band page holding `pos` exists before a
        dispatch writes it (growth is one token per decode step)."""
        lp = pos // self.geom.page_size
        assert lp < self.max_pages, (
            f"position {pos} past the page table ({self.max_pages} pages): "
            f"the engine's max_seq done-condition should retire rows "
            f"before this")
        if self.allocated[row, lp]:
            key = (int(self.page_mode[row, lp]),
                   int(self.page_table[row, lp]))
            if self._refcount.get(key, 1) > 1:
                # about to write into a shared-prefix page: copy-on-write
                # the tokens below `pos` into a private page first
                return self._cow_page(row, lp,
                                      pos - lp * self.geom.page_size, step)
            return True
        return self.alloc_page(row, lp, step)

    def release_row(self, row: int) -> None:
        self.free_row(row)
        if self.prefix_pages:
            self.free_row(self._prefix_row(row))

    def note_token_writes(self, rows: np.ndarray, positions: np.ndarray,
                          step: int) -> None:
        """Stamp the decode-band pages the given absolute `positions`
        land in (one entry per row)."""
        rows = np.asarray(rows).ravel()
        lps = np.asarray(positions).ravel() // self.geom.page_size
        self.note_writes(rows, lps, step)

    def refresh(self, key: tuple, step: int) -> None:
        self.refresh_page(key[0], key[1], step)

    def retract_token_writes(self, rows: np.ndarray,
                             new_lengths: np.ndarray) -> int:
        """Speculative rollback: release decode-band pages that hold ONLY
        draft tokens the verify pass rejected (pages whose first slot is
        at or past the row's post-accept length). The rejected slots of
        the surviving boundary page were already scrubbed by the verify
        step's masked commit re-scatter; retracted pages may hold stale
        bytes but are never read — the kernel's walk is bounded by
        `lengths`, and any re-allocation rewrites before the first read.
        Returns the number of pages released."""
        page = self.geom.page_size
        n = 0
        for row, length in zip(np.asarray(rows).ravel(),
                               np.asarray(new_lengths).ravel()):
            row, length = int(row), int(length)
            first_dead = -(-max(length, 0) // page)      # ceil
            for lp in np.flatnonzero(self.allocated[row]):
                if int(lp) >= first_dead:
                    self._release(row, int(lp))
                    n += 1
        if n:
            self.stats["retracted_pages"] += n
        return n

    def max_row_tokens(self) -> Optional[int]:
        """Upper bound on tokens ONE row can ever hold in this pool (the
        admission-time capacity check), assuming the rest of the pool is
        empty: the page table's depth, the cheapest plane's arena, and
        the byte budget in the cheapest mode the policy can reach — each
        less the row's static prefix pages."""
        if self.pool_mode == "normal-only":
            arena, cheapest = self.pages_normal, self._cost(0)
        elif self.pool_mode == "always-augmented":
            arena, cheapest = self.pages_packed, self._cost(1)
        else:
            arena = self.pages_normal + self.pages_packed
            cheapest = self._cost(1)
        pages = min(self.max_pages,
                    arena - self.prefix_pages,
                    self.budget_bytes // cheapest - self.prefix_pages)
        return max(pages, 0) * self.geom.page_size

    @property
    def state(self):
        """Device-side decode-state tree (donated through the jitted step)."""
        return self.arenas

    @state.setter
    def state(self, new) -> None:
        self.arenas = new

    @property
    def aug_bits(self) -> int:
        return self.geom.aug_bits

    def physical_bytes(self) -> int:
        """Usable staged capacity of both planes (write-dump lines
        excluded; `arena_bytes()` reports the raw allocation)."""
        return (self.pages_normal * self.geom.page_bytes_normal
                + self.pages_packed * self.geom.page_bytes_aug)

    # -- array event accounting (engine folds these into the IMC ledger) -----

    @property
    def _values_per_token(self) -> int:
        g = self.geom
        return 2 * g.n_layers * g.kv_heads * g.head_dim

    def read_value_counts(self, rows: np.ndarray,
                          lengths: np.ndarray) -> tuple[int, int]:
        """(normal, augmented) cache VALUES a decode dispatch reads for
        `rows` at valid `lengths`, split by page mode — prefix-band pages
        are read to their full static length every step."""
        if rows.size == 0:
            return 0, 0
        page = self.geom.page_size
        tw = self.table_width
        tok_per_page = np.clip(
            lengths[:, None] - np.arange(tw)[None, :] * page, 0, page)
        bands = [(rows, tok_per_page)]
        if self.prefix_pages:
            prows = self.max_batch + rows
            ptok = np.clip(
                self.prefix_tokens - np.arange(tw)[None, :] * page, 0, page)
            bands.append((prows, np.broadcast_to(ptok, (rows.size, tw))))
        n_norm = n_aug = 0
        for band_rows, tok in bands:
            alloc = self.allocated[band_rows]
            modes = self.page_mode[band_rows]
            n_norm += int((tok * (alloc & (modes == 0))).sum())
            n_aug += int((tok * (alloc & (modes == 1))).sum())
        v = self._values_per_token
        return n_norm * v, n_aug * v

    def write_value_counts(self, rows: np.ndarray, n_new: int,
                           write_starts: np.ndarray) -> tuple[int, int]:
        """(normal, augmented) cache VALUES one dispatch writes: `n_new`
        tokens per row from `write_starts`, costed by the mode of the
        decode-band page each token lands in."""
        if rows.size == 0:
            return 0, 0
        page = self.geom.page_size
        pos = write_starts[:, None] + np.arange(n_new)[None, :]
        lp = np.minimum(pos // page, self.max_pages - 1)
        mode = self.page_mode[rows[:, None], lp]
        alive = self.allocated[rows[:, None], lp]
        v = self._values_per_token
        wn = int((alive & (mode == 0)).sum()) * v
        wa = int((alive & (mode == 1)).sum()) * v
        return wn, wa

    def _release(self, row: int, lp: int) -> None:
        mode = int(self.page_mode[row, lp])
        phys = int(self.page_table[row, lp])
        rck = (mode, phys)
        rc = self._refcount.get(rck, 1)
        if rc > 1:
            # shared physical page: drop this alias only. The byte charge
            # and the canonical refresh/integrity metadata stay with the
            # surviving refs (rehomed if this alias was carrying them).
            self._refcount[rck] = rc - 1
            self._rehome_meta((row, lp), mode, phys)
            self._tables_cache = None
            self.allocated[row, lp] = False
            self.page_table[row, lp] = 0
            self.page_mode[row, lp] = 0
            self.last_write[row, lp] = -1
            return
        self._refcount.pop(rck, None)
        if mode == 1 and phys in self._decommission:
            # repeat-offender packed page: map the weak array out instead
            # of recycling it — capacity genuinely shrinks
            self._decommission.discard(phys)
            self.pages_packed -= 1
            self.stats["pages_decommissioned"] += 1
            if self._obs is not None:
                self._obs.store_event("decommission", f"pg{phys}", -1)
        else:
            (self.free_normal if mode == 0 else self.free_packed).append(phys)
        key = (row, lp)
        if key in self._pending:
            # the corruption evaporated with the storage before any read
            # reached it (row finished / preempted / array drained)
            self._pending.discard(key)
            self.stats["faults_masked"] += 1
        self._words.pop(key, None)
        self._masters.pop(key, None)
        self._dirty.discard(key)
        self._tables_cache = None
        self.live_bytes -= self._cost(mode)
        self._live_by_mode[mode] -= 1
        self.allocated[row, lp] = False
        self.page_table[row, lp] = 0
        self.page_mode[row, lp] = 0
        self.last_write[row, lp] = -1
        self.policies.pop((row, lp), None)

    # -- shared-prefix page reuse (refcounted aliases + copy-on-write) ---------
    # serve/prefix.py owns the token-hash index; the pool owns the pages.
    # Every cached prefix is anchored by one SHARE-band row (its "entry
    # row") whose table maps the run's physical pages; decode rows alias
    # the same physical pages by refcount. Invariants:
    #   * live_bytes charges each PHYSICAL page exactly once — the alias
    #     that carries the charge is whichever ref releases LAST.
    #   * refresh/integrity metadata (policies/_words/_masters/_pending/
    #     _dirty) for a shared page lives on exactly ONE key — the entry
    #     row while the entry is alive — so an expiring refcounted page
    #     restamps once, not once per sharer.

    def entry_row(self, slot: int) -> int:
        return self._share_base + slot

    def attach_prefix_index(self, idx) -> None:
        """Wire the engine's PrefixIndex so allocation pressure can evict
        idle cached prefixes (refcount 0) as the last reclaim rung."""
        self._prefix_index = idx

    def _reclaim_prefix(self, step: int) -> bool:
        if self._prefix_index is None:
            return False
        return self._prefix_index.evict_one(self, step)

    def _refs(self, mode: int, phys: int) -> list[tuple[int, int]]:
        """All logical keys currently mapping physical page (mode, phys)."""
        hits = np.argwhere(self.allocated & (self.page_mode == mode)
                           & (self.page_table == phys))
        return [(int(r), int(l)) for r, l in hits]

    def page_refcount(self, row: int, lp: int) -> int:
        if not self.allocated[row, lp]:
            return 0
        return self._refcount.get((int(self.page_mode[row, lp]),
                                   int(self.page_table[row, lp])), 1)

    def bytes_shared(self) -> int:
        """Bytes the sharing layer is currently saving: each extra ref of
        a physical page is storage a private copy would have cost."""
        return sum((rc - 1) * self._cost(m)
                   for (m, _p), rc in self._refcount.items() if rc > 1)

    def share_page(self, src_row: int, src_lp: int, dst_row: int,
                   dst_lp: int, step: int) -> None:
        """Alias the physical page behind (src_row, src_lp) into
        (dst_row, dst_lp): pure table writes + a refcount bump — no
        storage, no bytes, no dispatch."""
        assert self.allocated[src_row, src_lp], (src_row, src_lp)
        assert not self.allocated[dst_row, dst_lp], (dst_row, dst_lp)
        mode = int(self.page_mode[src_row, src_lp])
        phys = int(self.page_table[src_row, src_lp])
        self.page_table[dst_row, dst_lp] = phys
        self.page_mode[dst_row, dst_lp] = mode
        self.allocated[dst_row, dst_lp] = True
        self.last_write[dst_row, dst_lp] = step
        k = (mode, phys)
        self._refcount[k] = self._refcount.get(k, 1) + 1
        self._tables_cache = None

    def _move_canonical(self, src: tuple[int, int],
                        dst: tuple[int, int]) -> None:
        """Move whatever refresh/integrity metadata `src` holds to `dst`
        (no-op for entries `src` doesn't hold)."""
        if src == dst:
            return
        pol = self.policies.pop(src, None)
        if pol is not None:
            self.policies[dst] = pol
        for d in (self._words, self._masters):
            if src in d:
                d[dst] = d.pop(src)
        for s in (self._dirty, self._pending):
            if src in s:
                s.discard(src)
                s.add(dst)

    def _rehome_meta(self, key: tuple[int, int], mode: int,
                     phys: int) -> None:
        """An alias of shared page (mode, phys) is releasing: if it was
        the canonical metadata holder, hand the metadata to a surviving
        ref (highest row wins — the share band outranks decode rows, so
        an entry keeps custody of its own pages)."""
        if (key not in self.policies and key not in self._words
                and key not in self._masters and key not in self._pending
                and key not in self._dirty):
            return
        refs = [r for r in self._refs(mode, phys) if r != key]
        if not refs:
            return
        self._move_canonical(key, max(refs))

    def register_entry_pages(self, erow: int, src_row: int, n_pages: int,
                             step: int) -> None:
        """Anchor a freshly prefilled prefix: alias `src_row`'s first
        `n_pages` pages into share-band row `erow` and move each page's
        canonical metadata there (restamp-once invariant)."""
        for lp in range(n_pages):
            self.share_page(src_row, lp, erow, lp, step)
            self._move_canonical((src_row, lp), (erow, lp))

    def note_entry_use(self, erow: int, n_tokens: int, step: int) -> None:
        """A hit re-warmed this entry's first ceil(n/page) pages: reset
        coldness (NOT the retention clock — no bits were rewritten)."""
        for lp in range(-(-n_tokens // self.geom.page_size)):
            if self.allocated[erow, lp]:
                self.last_write[erow, lp] = step

    def _prefix_idle_counts(self) -> tuple[int, int]:
        """(normal, augmented) physical pages held ONLY by share-band
        entries — reclaimable headroom for the admission check, since
        `_reclaim_prefix` frees them at refcount 0 before alloc fails."""
        if not self.share_entries:
            return 0, 0
        counts: dict[tuple[int, int], int] = {}
        base = self._share_base
        for erow in range(base, base + self.share_entries):
            for lp in np.flatnonzero(self.allocated[erow]):
                k = (int(self.page_mode[erow, lp]),
                     int(self.page_table[erow, lp]))
                counts[k] = counts.get(k, 0) + 1
        idle = [k for k, n in counts.items()
                if self._refcount.get(k, 0) == n]
        return (sum(1 for m, _ in idle if m == 0),
                sum(1 for m, _ in idle if m == 1))

    def _cow_page(self, row: int, lp: int, keep: int, step: int) -> bool:
        """Copy-on-write: (row, lp) aliases a shared physical page and is
        about to diverge at token `keep` of the page. Copy tokens
        [0, keep) into a private page (masked page-copy dispatch), zero
        the rest, and repoint only this row. False = pool exhausted."""
        src_mode = int(self.page_mode[row, lp])
        src_phys = int(self.page_table[row, lp])
        order = {"normal-only": (0,), "always-augmented": (1,),
                 "augment-on-pressure": (0, 1)}[self.pool_mode]
        dst_mode = None
        while dst_mode is None:
            for mode in order:
                free = self.free_normal if mode == 0 else self.free_packed
                if free and self.live_bytes + self._cost(mode) \
                        <= self.budget_bytes:
                    dst_mode = mode
                    break
            else:
                if self.pool_mode == "augment-on-pressure" \
                        and self._augment_coldest(step):
                    continue
                if self._reclaim_prefix(step):
                    # reclaim may have freed OUR source's last other ref —
                    # then the page is private now and no copy is needed
                    if self._refcount.get((src_mode, src_phys), 1) == 1:
                        return True
                    continue
                self.stats["alloc_failures"] += 1
                return False
        free = self.free_normal if dst_mode == 0 else self.free_packed
        dst_phys = free.pop()
        self.arenas = _cow_page_op(self.arenas, src_phys, dst_phys, keep,
                                   src_mode=src_mode, dst_mode=dst_mode,
                                   aug_bits=self.geom.aug_bits)
        self.stats["maintenance_dispatches"] += 1
        self.stats["cow_events"] += 1
        self.stats["cow_bytes"] += self._cost(src_mode) + self._cost(dst_mode)
        sk = (src_mode, src_phys)
        self._refcount[sk] = self._refcount.get(sk, 2) - 1
        self._refcount[(dst_mode, dst_phys)] = 1
        self._rehome_meta((row, lp), src_mode, src_phys)
        self.page_table[row, lp] = dst_phys
        self.page_mode[row, lp] = dst_mode
        self.last_write[row, lp] = step
        self.live_bytes += self._cost(dst_mode)
        self._live_by_mode[dst_mode] += 1
        self.stats["peak_live_bytes"] = max(self.stats["peak_live_bytes"],
                                            self.live_bytes)
        if dst_mode == 1:
            pol = RefreshPolicy(retention_steps=self.retention_steps)
            pol.stamp(step)
            self.policies[(row, lp)] = pol
            if self._fm is not None:
                self._dirty.add((row, lp))
        self._tables_cache = None
        if self._obs is not None:
            self._obs.store_event("cow", f"pg{src_phys}>{dst_phys}", step)
        return True

    # -- mode switching (the paper's WL/SL reconfiguration) --------------------

    def _augmentable_count(self) -> int:
        # PHYSICAL Normal pages the pressure ladder may demote; actively
        # shared pages (refcount > 1) are pinned in place — mutating the
        # bits under a concurrent reader is never allowed
        return sum(1 for (m, _p), rc in self._refcount.items()
                   if m == 0 and rc == 1)

    def _coldest_normal(self) -> Optional[tuple[int, int]]:
        cand = self.allocated & (self.page_mode == 0)
        if self.share_entries:
            for (m, phys), rc in self._refcount.items():
                if m == 0 and rc > 1:
                    cand &= ~((self.page_table == phys)
                              & (self.page_mode == 0))
        if not cand.any():
            return None
        age = np.where(cand, self.last_write, np.iinfo(np.int64).max)
        row, lp = np.unravel_index(int(age.argmin()), age.shape)
        return int(row), int(lp)

    def _augment_coldest(self, step: int) -> bool:
        target = self._coldest_normal()
        if target is None or not self.free_packed:
            return False
        self.augment_page(*target, step=step)
        return True

    def augment_page(self, row: int, lp: int, step: int) -> None:
        """Normal -> Augmented in place: quantize-pack the page into the
        dynamic plane, release the byte difference back to the budget.
        The bf16 master is gone afterwards — the page is now dynamic data
        under the retention clock. Shared pages move ALL their aliases
        (the pressure ladder only sends refcount-1 pages here, but a
        direct call on a shared page stays consistent); a share-band
        page taking this path is a prefix DEMOTION — the dual-context
        alternative to eviction."""
        assert self.page_mode[row, lp] == 0 and self.allocated[row, lp]
        src = int(self.page_table[row, lp])
        refs = self._refs(0, src)
        dst = self.free_packed.pop()
        self.arenas = _augment_page_op(self.arenas, src, dst,
                                       aug_bits=self.geom.aug_bits)
        self.stats["maintenance_dispatches"] += 1
        self.free_normal.append(src)
        self._tables_cache = None
        for r, l in refs:
            self.page_table[r, l] = dst
            self.page_mode[r, l] = 1
        rc = self._refcount.pop((0, src), 1)
        self._refcount[(1, dst)] = rc
        self.live_bytes -= self._cost(0) - self._cost(1)
        self._live_by_mode[0] -= 1
        self._live_by_mode[1] += 1
        ckey = max(refs) if refs else (row, lp)
        pol = RefreshPolicy(retention_steps=self.retention_steps)
        pol.stamp(step)
        self.policies[ckey] = pol
        if self._fm is not None:
            self._dirty.add(ckey)
        self.stats["augment_events"] += 1
        self.stats["augment_bytes"] += self._cost(0) + self._cost(1)
        demoted = self.share_entries and ckey[0] >= self._share_base
        if demoted:
            self.stats["prefix_demotions"] += 1
        if self._obs is not None:
            self._obs.store_event("demote" if demoted else "augment",
                                  f"pg{dst}", step)

    def promote_page(self, row: int, lp: int, step: int) -> bool:
        """Augmented -> Normal (refresh-promote): dequantize back into the
        static plane when the budget has room again. Shared pages move
        ALL their aliases and clear the single canonical metadata key."""
        assert self.page_mode[row, lp] == 1 and self.allocated[row, lp]
        src = int(self.page_table[row, lp])
        refs = self._refs(1, src)
        ckey = max(refs) if refs else (row, lp)
        if ckey in self._pending:
            # never materialize a corrupted packed page into the static
            # plane — the fault pass must detect and heal it first
            return False
        cost_up = self._cost(0) - self._cost(1)
        if not self.free_normal or self.live_bytes + cost_up > self.budget_bytes:
            return False
        dst = self.free_normal.pop()
        self.arenas = _promote_page_op(self.arenas, src, dst,
                                       aug_bits=self.geom.aug_bits)
        self.stats["maintenance_dispatches"] += 1
        self.free_packed.append(src)
        self._tables_cache = None
        for r, l in refs:
            self.page_table[r, l] = dst
            self.page_mode[r, l] = 0
            self.last_write[r, l] = step
        rc = self._refcount.pop((1, src), 1)
        self._refcount[(0, dst)] = rc
        self.live_bytes += cost_up
        self._live_by_mode[1] -= 1
        self._live_by_mode[0] += 1
        self.policies.pop(ckey, None)
        self._words.pop(ckey, None)
        self._masters.pop(ckey, None)
        self._dirty.discard(ckey)
        self.stats["promote_events"] += 1
        if self._obs is not None:
            self._obs.store_event("promote", f"pg{dst}", step)
        return True

    # -- retention / refresh ----------------------------------------------------

    def note_writes(self, rows: np.ndarray, lps: np.ndarray,
                    step: int) -> None:
        """Stamp pages written by this dispatch (decode tail slots or
        prefill chunks): resets both coldness and the retention clock."""
        for row, lp in zip(np.asarray(rows).ravel(), np.asarray(lps).ravel()):
            row, lp = int(row), int(lp)
            if not self.allocated[row, lp]:
                continue
            self.last_write[row, lp] = step
            pol = self.policies.get((row, lp))
            if pol is not None:
                pol.stamp(step)
                if self._fm is not None:
                    self._dirty.add((row, lp))

    def refresh_due(self, step: int) -> list[tuple[int, int]]:
        return [key for key, pol in self.policies.items()
                if pol.needs_refresh(step)]

    def refresh_page(self, row: int, lp: int, step: int, *,
                     promote_ok: bool = True) -> None:
        """DRAM-style refresh of one expired Augmented page: promote back
        to Normal when allowed and the budget has room, else re-write the
        packed rows in place (restamp) and account the traffic."""
        if (self._fm is not None and (row, lp) in self.policies
                and self._fm.refresh_miss(self._unit_id((row, lp)), step)):
            # the refresh pulse itself failed (paper Table II tail): the
            # page stays on the old stamp and keeps aging toward certain
            # fault — inject/scan will catch what decays
            self.stats["refresh_misses"] += 1
            return
        if promote_ok and self.pool_mode == "augment-on-pressure" \
                and self.cfg.amc.refresh_promote \
                and self.promote_page(row, lp, step):
            self.stats["refreshes"] += 1
            self.stats["refresh_bytes"] += self._cost(1) + self._cost(0)
            return
        pol = self.policies.get((row, lp))
        if pol is None:                      # freed/promoted concurrently
            return
        pol.stamp(step)
        self.stats["refreshes"] += 1
        self.stats["refresh_bytes"] += 2 * self._cost(1)   # read + re-write
        if self._obs is not None:
            self._obs.store_event("restamp", f"r{row}.p{lp}", step)

    def max_augmented_age(self, step: int) -> int:
        """Oldest unrefreshed augmented page, in steps (invariant probe:
        the scheduler must keep this <= retention_steps)."""
        return max((pol.age(step) for pol in self.policies.values()),
                   default=0)

    # -- observability ----------------------------------------------------------

    def attach_obs(self, obs) -> None:
        """Wire the engine's observability facade: mode transitions and
        fault injections emit refresh/fault-lane events from here."""
        self._obs = obs

    def mode_mix(self) -> tuple[int, int]:
        """(live Normal pages, live Augmented pages) — one sample of the
        paper's 6T/8T+ mode-mix timeline. O(1): incremental counters,
        sampled every engine step (describe() recomputes the same pair
        by reduction as the ground-truth cross-check)."""
        return self._live_by_mode[0], self._live_by_mode[1]

    # -- retention-fault injection / detection / healing ------------------------
    # (core/faults.py FaultModel; the engine's fault pass drives these.
    # Only Augmented pages are at risk — the Normal plane is the paper's
    # static 6T configuration and never decays.)

    def attach_fault_model(self, fm: F.FaultModel, *, integrity: bool = True,
                           tag: str = "") -> None:
        self._fm = fm
        self._integrity = integrity
        self._fault_tag = tag
        # pages placed before attach have no integrity words yet
        self._dirty.update(self.policies.keys())

    def _unit_id(self, key: tuple[int, int]) -> str:
        """Stable PHYSICAL identity of the cells behind a logical page —
        repeat-offender tracking must follow the weak array, not the
        logical page that happens to occupy it."""
        return f"{self._fault_tag}pg{int(self.page_table[key])}"

    def _unit_payload_np(self, key: tuple[int, int]) -> tuple:
        phys = int(self.page_table[key])
        return tuple(np.asarray(self.arenas[k][:, phys])
                     for k in ("kp", "vp", "ks", "vs"))

    def _unit_word(self, key: tuple[int, int]) -> int:
        return F.integrity_word(*self._unit_payload_np(key))

    def _flush_integrity(self) -> None:
        """Bring integrity words up to date for every augmented page that
        was (re)written since the last flush — the host-side mirror of the
        fused `quantize_pack_kv(with_integrity=True)` store-back. Static
        prefix-band pages (write-once) also stash a host master copy, the
        scrub source of `scrub_from_master`."""
        for key in self.policies:
            if key in self._words and key not in self._dirty:
                continue
            payload = self._unit_payload_np(key)
            self._words[key] = F.integrity_word(*payload)
            if key[0] >= self.max_batch:
                self._masters[key] = payload
        self._dirty.clear()

    def inject_faults(self, step: int) -> int:
        """Sample retention faults for every live augmented page and
        corrupt the packed payload on device (deterministic under the
        model's seed). Returns the number of pages corrupted."""
        if self._fm is None:
            return 0
        self._flush_integrity()
        n = 0
        for key, pol in list(self.policies.items()):
            if key in self._pending:
                continue
            uid = self._unit_id(key)
            if self._fm.fault(uid, step, pol.age(step), self.retention_steps):
                phys = int(self.page_table[key])
                mask = self._fm.corruption_mask(uid, step)
                self.arenas = _corrupt_page_op(self.arenas, phys, mask)
                self._pending.add(key)
                self.stats["faults_injected"] += 1
                if self._obs is not None:
                    self._obs.on_fault("inject", uid, step)
                n += 1
        return n

    def scan_integrity(self, step: int) -> list[tuple[int, int]]:
        """Verify every augmented page's payload against its stored
        integrity word; return the corrupted keys (detected, never
        silently served). With integrity off this is a no-op — the
        zero-silent-corruption property is then forfeited by config."""
        if self._fm is None or not self._integrity:
            return []
        self._flush_integrity()
        bad: list[tuple[int, int]] = []
        for key, word in list(self._words.items()):
            self.stats["integrity_checks"] += 1
            if self._unit_word(key) == word:
                continue
            bad.append(key)
            self._pending.discard(key)
            self.stats["faults_detected"] += 1
            uid = self._unit_id(key)
            self._offenders[uid] = self._offenders.get(uid, 0) + 1
            if (self._offenders[uid] >= self._fm.pin_threshold
                    and key[0] < self.max_batch):
                # decode-band repeat offender: retire the weak physical
                # page when its current tenant releases it
                self._decommission.add(int(self.page_table[key]))
        return bad

    def scrub_from_master(self, key: tuple[int, int]) -> bool:
        """Heal a detected-corrupt page by re-writing it from the host
        master copy (static prefix band only — decode-band pages have no
        master and must be recomputed). Repeat-offender pages are pinned
        back to the Normal plane when the budget allows."""
        master = self._masters.get(key)
        if master is None:
            return False
        phys = int(self.page_table[key])
        kp, vp, ks, vs = master
        self.arenas = _restore_page_op(self.arenas, phys,
                                       jnp.asarray(kp), jnp.asarray(vp),
                                       jnp.asarray(ks), jnp.asarray(vs))
        self.stats["maintenance_dispatches"] += 1
        self._words[key] = F.integrity_word(*master)
        self._dirty.discard(key)
        if self._offenders.get(self._unit_id(key), 0) >= self._fm.pin_threshold:
            if self.promote_page(key[0], key[1], step=0):
                self.stats["pinned_normal"] += 1
        return True

    def fault_row(self, key: tuple[int, int]) -> Optional[int]:
        """Engine row whose request owns the faulted page (prefix-band
        rows map back to their decode slot; SHARE-band rows have no
        single owner — unhealed faults there are handled by entry
        eviction, not by retrying one request)."""
        row = key[0]
        if self.share_entries and row >= self._share_base:
            return None
        return row if row < self.max_batch else row - self.max_batch

    def fault_unit_bytes(self, key: tuple[int, int]) -> int:
        return self.geom.page_bytes_aug

    def fault_counters(self) -> dict:
        return {k: self.stats[k] for k in
                ("faults_injected", "faults_detected", "faults_masked",
                 "refresh_misses", "integrity_checks", "pinned_normal",
                 "pages_decommissioned")}

    def faults_pending(self) -> int:
        """Injected-but-unscanned corruptions still live in the arenas."""
        return len(self._pending)

    # -- device views -----------------------------------------------------------

    def device_tables(self) -> dict:
        """Scalar-prefetch operands for the paged kernel + write tables.
        normal_idx / packed_idx carry HOLD-PREVIOUS semantics per row so
        the mode-mismatched arena never issues a DMA. With a prefix band,
        the same tables are also emitted for rows [B, 2B) under the
        ``cross_*`` keys together with the static ``cross_lengths``."""
        if self._tables_cache is not None:
            return self._tables_cache
        pt, md = self.page_table, self.page_mode
        n_rows, tw = pt.shape
        nidx = np.zeros((n_rows, tw), np.int32)
        pidx = np.zeros((n_rows, tw), np.int32)
        lastn = np.zeros(n_rows, np.int32)
        lastp = np.zeros(n_rows, np.int32)
        for s in range(tw):
            live = self.allocated[:, s]
            lastn = np.where(live & (md[:, s] == 0), pt[:, s], lastn)
            lastp = np.where(live & (md[:, s] == 1), pt[:, s], lastp)
            nidx[:, s], pidx[:, s] = lastn, lastp
        B, maxP = self.max_batch, self.max_pages
        tables = {"page_table": to_device(pt[:B, :maxP]),
                  "page_modes": to_device(md[:B, :maxP]),
                  "normal_idx": to_device(nidx[:B, :maxP]),
                  "packed_idx": to_device(pidx[:B, :maxP])}
        if self.prefix_pages:
            Pc = self.prefix_pages
            clen = np.where(self.allocated[B:, :Pc].any(axis=1),
                            self.prefix_tokens, 0).astype(np.int32)
            tables.update({
                "cross_table": to_device(pt[B:, :Pc]),
                "cross_modes": to_device(md[B:, :Pc]),
                "cross_normal_idx": to_device(nidx[B:, :Pc]),
                "cross_packed_idx": to_device(pidx[B:, :Pc]),
                "cross_lengths": to_device(clen),
            })
        self._tables_cache = tables
        return self._tables_cache

    def arena_bytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.arenas))

    def describe(self) -> dict:
        g = self.geom
        # PHYSICAL live pages (aliases of a shared page count once) — the
        # ground-truth cross-check of the incremental _live_by_mode pair
        live_n = sum(1 for (m, _p) in self._refcount if m == 0)
        live_a = sum(1 for (m, _p) in self._refcount if m == 1)
        return {
            "kind": self.kind,
            "pool_mode": self.pool_mode,
            "page_size": g.page_size,
            "aug_bits": g.aug_bits,
            "prefix_tokens": self.prefix_tokens,
            "pages_live_normal": live_n,
            "pages_live_augmented": live_a,
            "pages_shared": sum(1 for rc in self._refcount.values()
                                if rc > 1),
            "bytes_shared": self.bytes_shared(),
            "page_bytes_normal": g.page_bytes_normal,
            "page_bytes_aug": g.page_bytes_aug,
            "page_capacity_factor": g.capacity_factor,
            "budget_bytes": self.budget_bytes,
            "live_bytes": self.live_bytes,
            "arena_bytes": self.arena_bytes(),
            "retention_steps": self.retention_steps,
            **self.stats,
        }


# ---------------------------------------------------------------------------
# jitted maintenance ops (mode switches move one page between planes)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("aug_bits",), donate_argnums=(0,))
def _augment_page_op(arenas: dict, src: int, dst: int, *, aug_bits: int):
    """Quantize-pack Normal page `src` into packed page `dst` (all layers,
    K and V) — the existing quantize_pack_kv path is the write driver."""
    out = dict(arenas)
    for plane, packed, scale in (("kn", "kp", "ks"), ("vn", "vp", "vs")):
        x = arenas[plane][:, src]                       # (L, KV, page, hd)
        if aug_bits == 4:
            p, s = K.quantize_pack_kv(x)
        else:
            p, s = L.pack_kv_int8(x)
        out[packed] = out[packed].at[:, dst].set(p)
        out[scale] = out[scale].at[:, dst].set(s[..., 0].astype(jnp.bfloat16))
    return out


@functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=(0,))
def _zero_page_op(arenas: dict, phys: int, *, mode: int):
    """Scrub one physical page in its plane (prefix-page allocation)."""
    out = dict(arenas)
    keys = ("kn", "vn") if mode == 0 else ("kp", "vp", "ks", "vs")
    for k in keys:
        out[k] = out[k].at[:, phys].set(jnp.zeros_like(out[k][:, phys]))
    return out


@functools.partial(jax.jit,
                   static_argnames=("src_mode", "dst_mode", "aug_bits"),
                   donate_argnums=(0,))
def _cow_page_op(arenas: dict, src: int, dst: int, keep, *,
                 src_mode: int, dst_mode: int, aug_bits: int):
    """Masked page copy for copy-on-write divergence: tokens [0, keep) of
    physical page `src` land in `dst` (crossing planes when the modes
    differ), the rest of `dst` is scrubbed to the plane's neutral value.
    The Normal->Augmented leg reuses `quantize_pack_kv(valid=)` — the
    same masked write driver the verify-commit path uses."""
    out = dict(arenas)
    P = arenas["kn"].shape[3]
    tokmask = jnp.arange(P) < keep
    if src_mode == 0 and dst_mode == 0:
        for k in ("kn", "vn"):
            page = jnp.where(tokmask[None, None, :, None],
                             arenas[k][:, src], 0)
            out[k] = out[k].at[:, dst].set(page)
    elif src_mode == 1 and dst_mode == 1:
        for p, s in (("kp", "ks"), ("vp", "vs")):
            pg = jnp.where(tokmask[None, None, :, None], arenas[p][:, src],
                           jnp.zeros_like(arenas[p][:, src]))
            sc = jnp.where(tokmask[None, None, :], arenas[s][:, src],
                           jnp.ones_like(arenas[s][:, src]))
            out[p] = out[p].at[:, dst].set(pg)
            out[s] = out[s].at[:, dst].set(sc)
    elif src_mode == 0 and dst_mode == 1:
        for plane, packed, scale in (("kn", "kp", "ks"), ("vn", "vp", "vs")):
            x = arenas[plane][:, src]                   # (L, KV, page, hd)
            if aug_bits == 4:
                p, s = K.quantize_pack_kv(x, tokmask[None, None, :])
            else:
                p, s = L.pack_kv_int8(x)
                p = jnp.where(tokmask[None, None, :, None], p,
                              jnp.zeros_like(p))
                s = jnp.where(tokmask[None, None, :, None], s,
                              jnp.ones_like(s))
            out[packed] = out[packed].at[:, dst].set(p)
            out[scale] = out[scale].at[:, dst].set(
                s[..., 0].astype(jnp.bfloat16))
    else:                                               # Augmented -> Normal
        unpack = L.unpack_kv_int4 if aug_bits == 4 else L.unpack_kv_int8
        for plane, packed, scale in (("kn", "kp", "ks"), ("vn", "vp", "vs")):
            d = unpack(arenas[packed][:, src], arenas[scale][:, src][..., None])
            d = jnp.where(tokmask[None, None, :, None], d, 0)
            out[plane] = out[plane].at[:, dst].set(d.astype(jnp.bfloat16))
    return out


@functools.partial(jax.jit, static_argnames=("aug_bits",), donate_argnums=(0,))
def _promote_page_op(arenas: dict, src: int, dst: int, *, aug_bits: int):
    """Dequantize packed page `src` back into Normal page `dst`."""
    unpack = L.unpack_kv_int4 if aug_bits == 4 else L.unpack_kv_int8
    out = dict(arenas)
    for plane, packed, scale in (("kn", "kp", "ks"), ("vn", "vp", "vs")):
        d = unpack(arenas[packed][:, src], arenas[scale][:, src][..., None])
        out[plane] = out[plane].at[:, dst].set(d.astype(jnp.bfloat16))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _corrupt_page_op(arenas: dict, phys, mask):
    """Retention-fault injection: XOR the packed payload of physical page
    `phys` with a nonzero byte `mask` (bitcast keeps the op dtype-safe for
    the uint8/int4 and int8 planes alike). `phys`/`mask` are traced
    scalars so repeated injections reuse one compilation."""
    out = dict(arenas)
    m = jnp.asarray(mask, jnp.uint8)
    for k in ("kp", "vp"):
        page = arenas[k][:, phys]
        b = jax.lax.bitcast_convert_type(page, jnp.uint8)
        b = jnp.bitwise_xor(b, m)
        out[k] = out[k].at[:, phys].set(
            jax.lax.bitcast_convert_type(b, page.dtype))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_page_op(arenas: dict, phys, kp, vp, ks, vs):
    """Scrub-on-detect: re-write physical page `phys` from a master copy."""
    out = dict(arenas)
    for k, v in (("kp", kp), ("vp", vp), ("ks", ks), ("vs", vs)):
        out[k] = out[k].at[:, phys].set(v.astype(out[k].dtype))
    return out
