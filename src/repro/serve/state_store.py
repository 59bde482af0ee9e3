"""Unified per-family decode-state stores — every architecture's decode
state behind ONE augmented-storage interface.

The paper's array re-provisions its own capacity on demand: the same SRAM
cells hold Normal (6T, one bit per cell) or Augmented (8T/7T, multi-bit
dynamic) data. PR 3/4 modeled that for transformer KV caches only
(`cache_pool.PagedKVPool`); this module generalizes "KV cache" to ANY
per-request decode state, so ssm / hybrid / encdec / vlm rows get the same
admission control, augment-on-pressure, preemption-with-recompute, refresh
clocking and array-event accounting as dense/MoE rows.

StateStore interface (duck-typed; implemented by `PagedKVPool`,
`AugmentedStatePool` and `CompositeStore`):

  kind                      "paged" | "slab" | "composite"
  can_admit_tokens(n)       admission probe, counting augmentation headroom
  admit_row(row, n, step, *, shared=None)  all-or-nothing capacity grab
                            for a fresh row; paged pools accept
                            shared=(entry_row, m) to map a cached
                            prefix's pages by refcount instead of
                            allocating the first ceil(m/page) pages
  ensure_position(row, pos, step)  capacity for the next token write
  release_row(row)          free a finished / preempted row
  note_token_writes(rows, positions, step)  restamp written storage
  refresh_due(step) / refresh(key, step)    retention-driven maintenance
  max_augmented_age(step)   refresh-invariant probe
  state (property)          device tree, donated through the jitted step
  device_tables()           extra per-dispatch batch operands
  read/write_value_counts() array-event counts for the energy ledger
  live_bytes / budget_bytes / aug_bits / describe()

`AugmentedStatePool` is the new member: FIXED-SIZE per-row slabs (the
SSM/conv recurrent state of ssm rows, the LRU/conv/ring-window state of
hybrid rows, the static patch-KV prefix of vlm rows). A slab lives in one
of two modes:

  Normal     native dtype (bf16 / f32) rows in the ``normal`` plane
  Augmented  int8 or nibble-packed int4 rows + per-vector scales
             (``packed`` + ``scale`` planes, via `core/quant`)

against one byte budget. Under pressure the pool augments cold slabs in
place so more rows can be admitted (the same on-demand capacity the paged
pool gives KV pages). Augmented slabs are DYNAMIC storage in the paper's
sense: every decode step reads them through the "sense amp" (dequantize),
updates, and re-writes them through the "write driver" (quantize) — the
write restamps the slab's `RefreshPolicy`; a slab that goes unwritten
(a static vlm prefix) expires after `retention_steps` and the refresh
pass re-materializes or promotes it, exactly like the paged pool's pages.

Integer leaves (a hybrid row's already-packed int8 ring KV) pass through
the packed plane unchanged — they are packed storage already.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import faults as F
from repro.core import quant
from repro.core.retention import RefreshPolicy
from repro.serve.cache_pool import (PagedKVPool, resolve_pool_mode,
                                    to_device)


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _is_float(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# pure slab-plane ops (traced inside the jitted decode step)
# ---------------------------------------------------------------------------

def _quant_leaf(x: jax.Array, bits: int):
    """Float leaf -> (packed, scale) with per-vector (last-axis) scales.
    int8 stores one value per byte; int4 nibble-packs adjacent pairs."""
    if bits == 8:
        q, s = quant.quantize_int8(x.astype(jnp.float32), axis=-1)
        return q, s.astype(jnp.bfloat16)
    q, s = quant.quantize_int4(x.astype(jnp.float32), axis=-1)
    packed = quant.pack_int4_pair(q[..., ::2], q[..., 1::2])
    return packed, s.astype(jnp.bfloat16)


def _dequant_leaf(p: jax.Array, s: jax.Array, bits: int, dtype) -> jax.Array:
    if bits == 8:
        return quant.dequantize(p, s, dtype)
    hi = quant.unpack_int4_hi(p)
    lo = quant.unpack_int4_lo(p)
    q = jnp.stack([hi, lo], axis=-1).reshape(p.shape[:-1] + (-1,))
    return quant.dequantize(q, s, dtype)


def _packed_zeros(leaf: jax.Array, bits: int):
    """(packed, scale) zero planes matching `leaf` (q=0 dequantizes to an
    exact 0.0 whatever the scale, so zeroed planes read back as zeros)."""
    if bits == 8:
        p = jnp.zeros(leaf.shape, jnp.int8)
    else:
        if leaf.shape[-1] % 2:
            raise ValueError(
                f"state_bits=4 needs an even trailing dim, got {leaf.shape}")
        p = jnp.zeros(leaf.shape[:-1] + (leaf.shape[-1] // 2,), jnp.uint8)
    s = jnp.ones(leaf.shape[:-1] + (1,), jnp.bfloat16)
    return p, s


def _mode_mask(modes: jax.Array, leaf: jax.Array) -> jax.Array:
    """(B,) slot modes -> boolean mask broadcastable over a slab leaf
    (batch axis 1): True where the slot is Augmented."""
    shape = (1, modes.shape[0]) + (1,) * (leaf.ndim - 2)
    return (modes == 1).reshape(shape)


def _row_mask(write: jax.Array, leaf: jax.Array) -> jax.Array:
    """(B,) bool write mask -> broadcastable over a slab leaf."""
    shape = (1, write.shape[0]) + (1,) * (leaf.ndim - 2)
    return write.reshape(shape)


def _quantizable(leaf: jax.Array) -> bool:
    """Whether a slab leaf takes the packed dynamic plane: float data
    with a real vector axis. Integer leaves are packed storage already,
    and trailing-dim-1 float leaves are the SCALES of such packed
    storage (quantizing a scale against itself is meaningless) — both
    pass through the normal plane untouched."""
    return _is_float(leaf) and leaf.shape[-1] > 1


def slab_reconstitute(state: dict, modes: Optional[jax.Array],
                      bits: int) -> dict:
    """Merge the two planes into the logical native-dtype cache tree the
    family decode step consumes: Normal slots read the ``normal`` plane,
    Augmented slots dequantize the ``packed`` plane (the sense-amp path).
    A single-plane state (normal-only pool) passes through untouched."""
    if "packed" not in state:
        return state["normal"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(state["normal"])
    out = []
    for (path, leaf) in flat:
        key = _keystr(path)
        if key in state["packed"]:
            d = _dequant_leaf(state["packed"][key],
                              state["scale"][key], bits, leaf.dtype)
            leaf = jnp.where(_mode_mask(modes, leaf), d, leaf)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def slab_store_back(state: dict, new_cache: dict,
                    modes: Optional[jax.Array], bits: int,
                    write: Optional[jax.Array] = None) -> dict:
    """Write the updated cache back into its slot's plane: Normal slots
    into the ``normal`` plane, Augmented slots quantized into ``packed``
    (the write-driver path — lossy, and the physical restamp the host
    RefreshPolicy records). Each written slot's OTHER plane is zeroed so
    no stale native master shadows an augmented slab.

    `write` is the (B,) dispatch write mask: rows NOT being written keep
    BOTH planes bit-identical — the slab form of the paged pool's
    write-masked scatter. (The legacy contiguous engine skipped this and
    let one request's prefill advance every other row's recurrent state
    with pad-token updates; the unified store isolates rows.)"""
    if "packed" not in state:
        if write is None:
            return {"normal": new_cache}
        old_flat, treedef = jax.tree_util.tree_flatten_with_path(
            state["normal"])
        new_leaves = jax.tree.leaves(new_cache)
        merged = [jnp.where(_row_mask(write, new), new, old)
                  for (_, old), new in zip(old_flat, new_leaves)]
        return {"normal": jax.tree_util.tree_unflatten(treedef, merged)}
    flat, treedef = jax.tree_util.tree_flatten_with_path(new_cache)
    old_normal = jax.tree.leaves(state["normal"])
    normal_out = []
    packed_out, scale_out = dict(state["packed"]), dict(state["scale"])
    for (path, leaf), old in zip(flat, old_normal):
        key = _keystr(path)
        w = (jnp.ones((), bool) if write is None
             else _row_mask(write, leaf))
        if key in state["packed"]:
            mask = _mode_mask(modes, leaf)        # (1, B, 1...): broadcasts
            q, s = _quant_leaf(leaf, bits)
            packed_out[key] = jnp.where(
                w & mask, q, jnp.where(w, jnp.zeros_like(q),
                                       state["packed"][key]))
            scale_out[key] = jnp.where(
                w & mask, s, jnp.where(w, jnp.ones_like(s),
                                       state["scale"][key]))
            leaf = jnp.where(mask, jnp.zeros_like(leaf), leaf)
        normal_out.append(jnp.where(w, leaf, old))
    return {"normal": jax.tree_util.tree_unflatten(treedef, normal_out),
            "packed": packed_out, "scale": scale_out}


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_row_op(state: dict, row: jax.Array) -> dict:
    """Zero one slot across every plane (admission starts from fresh
    state; recycled rows must not leak the previous request's state)."""
    def z(leaf):
        if leaf.ndim >= 2 and not leaf.shape[0] == 0:
            return leaf.at[:, row].set(jnp.zeros_like(leaf[:, row]))
        return leaf
    out = {"normal": jax.tree.map(z, state["normal"])}
    if "packed" in state:
        out["packed"] = {k: z(v) for k, v in state["packed"].items()}
        out["scale"] = {k: v.at[:, row].set(jnp.ones_like(v[:, row]))
                        for k, v in state["scale"].items()}
    return out


@functools.partial(jax.jit, static_argnames=("bits",), donate_argnums=(0,))
def _augment_row_op(state: dict, row: jax.Array, *, bits: int) -> dict:
    """Normal -> Augmented for one slot: quantize its float rows into the
    packed plane and drop the native master (the in-place WL/SL mode
    switch of the paper, at slab granularity)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state["normal"])
    _, treedef = jax.tree.flatten(state["normal"])
    normal_out, packed, scale = [], dict(state["packed"]), \
        dict(state["scale"])
    for (path, leaf) in flat:
        key = _keystr(path)
        if key in packed:
            q, s = _quant_leaf(leaf[:, row], bits)
            packed[key] = packed[key].at[:, row].set(q)
            scale[key] = scale[key].at[:, row].set(s)
            leaf = leaf.at[:, row].set(jnp.zeros_like(leaf[:, row]))
        normal_out.append(leaf)
    return {"normal": jax.tree.unflatten(treedef, normal_out),
            "packed": packed, "scale": scale}


@functools.partial(jax.jit, static_argnames=("bits",), donate_argnums=(0,))
def _promote_row_op(state: dict, row: jax.Array, *, bits: int) -> dict:
    """Augmented -> Normal for one slot (refresh-promote)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state["normal"])
    _, treedef = jax.tree.flatten(state["normal"])
    normal_out, packed, scale = [], dict(state["packed"]), \
        dict(state["scale"])
    for (path, leaf) in flat:
        key = _keystr(path)
        if key in packed:
            d = _dequant_leaf(packed[key][:, row], scale[key][:, row],
                              bits, leaf.dtype)
            leaf = leaf.at[:, row].set(d)
            packed[key] = packed[key].at[:, row].set(
                jnp.zeros_like(packed[key][:, row]))
        normal_out.append(leaf)
    return {"normal": jax.tree.unflatten(treedef, normal_out),
            "packed": packed, "scale": scale}


@functools.partial(jax.jit, donate_argnums=(0,))
def _corrupt_row_op(state: dict, row, mask) -> dict:
    """Retention-fault injection: XOR slot `row` of every packed plane
    with a nonzero byte `mask` (bitcast keeps it dtype-safe for uint8 and
    int8 planes). Traced scalars: repeated injections reuse one compile."""
    out = dict(state)
    m = jnp.asarray(mask, jnp.uint8)
    packed = {}
    for k, v in state["packed"].items():
        slab = v[:, row]
        b = jax.lax.bitcast_convert_type(slab, jnp.uint8)
        b = jnp.bitwise_xor(b, m)
        packed[k] = v.at[:, row].set(
            jax.lax.bitcast_convert_type(b, slab.dtype))
    out["packed"] = packed
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_row_op(state: dict, row, packed: dict, scale: dict) -> dict:
    """Scrub-on-detect: re-write slot `row`'s packed planes from masters."""
    out = dict(state)
    out["packed"] = {k: v.at[:, row].set(packed[k].astype(v.dtype))
                     for k, v in state["packed"].items()}
    out["scale"] = {k: v.at[:, row].set(scale[k].astype(v.dtype))
                    for k, v in state["scale"].items()}
    return out


# ---------------------------------------------------------------------------
# AugmentedStatePool — fixed-size per-row decode-state slabs
# ---------------------------------------------------------------------------

class AugmentedStatePool:
    """See module docstring. `specs` is the family's abstract decode-state
    tree (PSpec leaves, batch at axis 1). `static=True` marks a
    write-once prefix store (vlm patch KV): decode never rewrites it, so
    augmented slabs genuinely age and the refresh pass restamps them."""

    kind = "slab"

    def __init__(self, cfg: ModelConfig, specs, *, max_batch: int,
                 budget_bytes: Optional[int] = None,
                 retention_steps: Optional[int] = None,
                 static: bool = False, table_key: str = "slot_modes"):
        self.cfg = cfg
        self.max_batch = max_batch
        self.static = static
        self.table_key = table_key
        # "auto" pins slabs to Normal: kv_mode governs the KV CACHE (the
        # family code packs its own ring/cross KV leaves accordingly, and
        # those already-packed leaves pass through this store untouched)
        # — quantizing the accumulated recurrent state is a different,
        # lossy decision the pool_mode knob must opt into explicitly.
        if cfg.amc.pool_mode == "auto":
            self.pool_mode = "normal-only"
        else:
            self.pool_mode = resolve_pool_mode(cfg)
        self.state_bits = cfg.amc.state_bits
        if self.state_bits not in (4, 8):
            raise ValueError(f"state_bits must be 4 or 8, "
                             f"got {self.state_bits}")
        self.retention_steps = (cfg.amc.retention_steps
                                if retention_steps is None
                                else retention_steps)
        from repro.models.params import is_pspec
        normal = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.jdtype), specs, is_leaf=is_pspec)
        for leaf in jax.tree.leaves(normal):
            assert leaf.ndim >= 2 and leaf.shape[1] == max_batch, (
                "slab leaves must carry the batch at axis 1", leaf.shape)
        self._state = {"normal": normal}
        self.mixed = self.pool_mode != "normal-only"
        n_norm = n_aug = n_values = 0
        for leaf in jax.tree.leaves(normal):
            per_slot = int(np.prod(leaf.shape)) // max_batch
            per_slot_bytes = leaf.nbytes // max_batch
            n_norm += per_slot_bytes
            n_values += per_slot
            if _quantizable(leaf):
                scale_vals = per_slot // leaf.shape[-1]
                n_aug += per_slot * self.state_bits // 8 + 2 * scale_vals
            else:
                # already-packed integer leaves and their scale tensors
                n_aug += per_slot_bytes
        self.slab_bytes_normal, self.slab_bytes_aug = n_norm, n_aug
        self.values_per_slot = n_values
        if self.mixed:
            packed, scale = {}, {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    normal)[0]:
                if _quantizable(leaf):
                    p, s = _packed_zeros(leaf, self.state_bits)
                    packed[_keystr(path)] = p
                    scale[_keystr(path)] = s
            self._state["packed"], self._state["scale"] = packed, scale
        cheapest = n_aug if self.mixed else n_norm
        self.budget_bytes = (max_batch * n_norm if budget_bytes is None
                             else budget_bytes)
        if self.budget_bytes < cheapest:
            raise ValueError(
                f"budget_bytes={self.budget_bytes} cannot hold one slab "
                f"({cheapest} B in the pool's cheapest mode)")
        self.live_bytes = 0
        self.slot_mode = np.zeros(max_batch, np.int32)   # 0 normal, 1 aug
        self.slot_alloc = np.zeros(max_batch, bool)
        self.last_write = np.full(max_batch, -1, np.int64)
        self.policies: dict[int, RefreshPolicy] = {}
        self._tables_cache: Optional[dict] = None
        self._spec_snapshot: Optional[dict] = None
        self.stats = {
            "augment_events": 0, "promote_events": 0, "refreshes": 0,
            "refresh_bytes": 0, "augment_bytes": 0,
            "maintenance_dispatches": 0, "alloc_failures": 0,
            "peak_live_bytes": 0, "spec_snapshots": 0, "spec_rollbacks": 0,
            "faults_injected": 0, "faults_detected": 0, "faults_masked": 0,
            "refresh_misses": 0, "integrity_checks": 0, "pinned_normal": 0,
        }
        # retention-fault machinery (core/faults.py) — inert until a
        # FaultModel is attached
        self._fm: Optional[F.FaultModel] = None
        self._integrity = False
        self._fault_tag = ""
        self._words: dict[int, int] = {}       # per-slab integrity words
        self._dirty: set[int] = set()          # rewritten since last flush
        self._pending: set[int] = set()        # injected, unscanned
        self._masters: dict[int, tuple] = {}   # static-store host copies
        self._offenders: dict[str, int] = {}   # by physical unit id
        self._pin_normal = np.zeros(max_batch, bool)  # repeat offenders
        self._obs = None        # EngineObs facade (attach_obs) — optional
        self._live_by_mode = [0, 0]   # live slabs per mode, kept
        # incrementally so the per-step mode-mix sample is O(1)

    # -- byte accounting ----------------------------------------------------

    @property
    def aug_bits(self) -> int:
        return self.state_bits

    def _cost(self, mode: int) -> int:
        return self.slab_bytes_normal if mode == 0 else self.slab_bytes_aug

    def can_admit_tokens(self, n_tokens: int) -> bool:
        """Fixed-size slabs: the token count is irrelevant, the question
        is whether one more slab fits — augmenting cold Normal slabs if
        the policy allows (the on-demand capacity probe)."""
        free_b = self.budget_bytes - self.live_bytes
        if self.pool_mode == "normal-only":
            return self._cost(0) <= free_b
        if (self.pool_mode == "augment-on-pressure"
                and self._cost(0) <= free_b):
            return True
        need = self._cost(1) - free_b
        if need <= 0:
            return True
        if self.pool_mode != "augment-on-pressure":
            return False
        per = self._cost(0) - self._cost(1)
        n_norm = int((self.slot_alloc & (self.slot_mode == 0)).sum())
        return -(-need // per) <= n_norm

    # -- allocation ---------------------------------------------------------

    def admit_row(self, row: int, n_tokens: int, step: int, *,
                  shared=None) -> bool:
        # `shared` (prefix page reuse) is a paged-pool concept; slab
        # state has no pages to alias — accepted and ignored
        assert not self.slot_alloc[row], row
        order = {"normal-only": (0,), "always-augmented": (1,),
                 "augment-on-pressure": (0, 1)}[self.pool_mode]
        if self._pin_normal[row] and self.pool_mode != "normal-only":
            # repeat-offender slot: its dynamic cells misbehave, so prefer
            # the static plane whenever the budget allows
            order = (0,) + tuple(m for m in order if m != 0)
        mode = None
        for m in order:
            if self.live_bytes + self._cost(m) <= self.budget_bytes:
                mode = m
                break
        if mode is None and self.pool_mode == "augment-on-pressure":
            while self.live_bytes + self._cost(1) > self.budget_bytes:
                if not self._augment_coldest(step):
                    self.stats["alloc_failures"] += 1
                    return False
            mode = 1
        if mode is None:
            self.stats["alloc_failures"] += 1
            return False
        self.slot_alloc[row] = True
        self.slot_mode[row] = mode
        self.last_write[row] = step
        self.live_bytes += self._cost(mode)
        self._live_by_mode[mode] += 1
        self.stats["peak_live_bytes"] = max(self.stats["peak_live_bytes"],
                                            self.live_bytes)
        if mode == 1:
            pol = RefreshPolicy(retention_steps=self.retention_steps)
            pol.stamp(step)
            self.policies[row] = pol
            if self._fm is not None:
                self._dirty.add(row)
        self._state = _reset_row_op(self._state, row)
        self.stats["maintenance_dispatches"] += 1
        self._tables_cache = None
        return True

    def ensure_position(self, row: int, pos: int, step: int) -> bool:
        """Slabs are fixed-size: an admitted row always has room."""
        return bool(self.slot_alloc[row])

    def max_row_tokens(self) -> Optional[int]:
        """Fixed-size slabs hold a row's whole recurrent state whatever
        its length: no per-row token capacity bound."""
        return None

    def release_row(self, row: int) -> None:
        if not self.slot_alloc[row]:
            return
        if row in self._pending:
            # the corruption evaporated with the row's state before any
            # scan reached it
            self._pending.discard(row)
            self.stats["faults_masked"] += 1
        self._words.pop(row, None)
        self._masters.pop(row, None)
        self._dirty.discard(row)
        self.live_bytes -= self._cost(int(self.slot_mode[row]))
        self._live_by_mode[int(self.slot_mode[row])] -= 1
        self.slot_alloc[row] = False
        self.slot_mode[row] = 0
        self.last_write[row] = -1
        self.policies.pop(row, None)
        self._tables_cache = None

    # -- mode switching -------------------------------------------------------

    def _coldest_normal(self) -> Optional[int]:
        cand = self.slot_alloc & (self.slot_mode == 0) & ~self._pin_normal
        if not cand.any():
            return None
        age = np.where(cand, self.last_write, np.iinfo(np.int64).max)
        return int(age.argmin())

    def _augment_coldest(self, step: int) -> bool:
        row = self._coldest_normal()
        if row is None or not self.mixed:
            return False
        self.augment_slot(row, step)
        return True

    def augment_slot(self, row: int, step: int) -> None:
        """Normal -> Augmented in place: quantize the slab into the packed
        plane, release the byte difference back to the budget. The native
        master is gone — the slab is dynamic data on the retention clock."""
        assert self.mixed and self.slot_alloc[row] \
            and self.slot_mode[row] == 0
        self._state = _augment_row_op(self._state, row,
                                      bits=self.state_bits)
        self.stats["maintenance_dispatches"] += 1
        self.slot_mode[row] = 1
        self.live_bytes -= self._cost(0) - self._cost(1)
        self._live_by_mode[0] -= 1
        self._live_by_mode[1] += 1
        pol = RefreshPolicy(retention_steps=self.retention_steps)
        pol.stamp(step)
        self.policies[row] = pol
        if self._fm is not None:
            self._dirty.add(row)
        self.stats["augment_events"] += 1
        self.stats["augment_bytes"] += self._cost(0) + self._cost(1)
        self._tables_cache = None
        if self._obs is not None:
            self._obs.store_event("augment", f"slab{row}", step)

    def promote_slot(self, row: int, step: int) -> bool:
        """Augmented -> Normal (refresh-promote) when the budget has room."""
        assert self.slot_alloc[row] and self.slot_mode[row] == 1
        if row in self._pending:
            # never materialize a corrupted packed slab into the static
            # plane — the fault pass must detect and heal it first
            return False
        cost_up = self._cost(0) - self._cost(1)
        if self.live_bytes + cost_up > self.budget_bytes:
            return False
        self._state = _promote_row_op(self._state, row,
                                      bits=self.state_bits)
        self.stats["maintenance_dispatches"] += 1
        self.slot_mode[row] = 0
        self.live_bytes += cost_up
        self._live_by_mode[1] -= 1
        self._live_by_mode[0] += 1
        self.last_write[row] = step
        self.policies.pop(row, None)
        self._words.pop(row, None)
        self._masters.pop(row, None)
        self._dirty.discard(row)
        self.stats["promote_events"] += 1
        self._tables_cache = None
        if self._obs is not None:
            self._obs.store_event("promote", f"slab{row}", step)
        return True

    # -- retention / refresh --------------------------------------------------

    def note_token_writes(self, rows: np.ndarray, positions: np.ndarray,
                          step: int) -> None:
        """Decode rewrote these rows' slabs through the write driver:
        restamp coldness and (augmented rows) the retention clock."""
        if self.static:
            return                      # decode never writes a prefix slab
        for row in np.asarray(rows).ravel():
            row = int(row)
            if not self.slot_alloc[row]:
                continue
            self.last_write[row] = step
            pol = self.policies.get(row)
            if pol is not None:
                pol.stamp(step)
                if self._fm is not None:
                    self._dirty.add(row)

    def refresh_due(self, step: int) -> list[int]:
        return [row for row, pol in self.policies.items()
                if pol.needs_refresh(step)]

    def refresh(self, row: int, step: int) -> None:
        """Refresh one expired augmented slab: promote back to Normal when
        allowed and affordable, else restamp in place (re-write the packed
        rows) and account the traffic."""
        pol = self.policies.get(row)
        if pol is None:
            return
        if (self._fm is not None
                and self._fm.refresh_miss(self._unit_id(row), step)):
            # the refresh pulse itself failed: the slab keeps aging toward
            # certain fault — inject/scan will catch what decays
            self.stats["refresh_misses"] += 1
            return
        if self.pool_mode == "augment-on-pressure" \
                and self.cfg.amc.refresh_promote \
                and self.promote_slot(row, step):
            self.stats["refreshes"] += 1
            self.stats["refresh_bytes"] += self._cost(1) + self._cost(0)
            return
        pol.stamp(step)
        self.stats["refreshes"] += 1
        self.stats["refresh_bytes"] += 2 * self._cost(1)   # read + re-write
        self.last_write[row] = step
        if self._obs is not None:
            self._obs.store_event("restamp", f"slab{row}", step)

    def max_augmented_age(self, step: int) -> int:
        return max((pol.age(step) for pol in self.policies.values()),
                   default=0)

    # -- observability ----------------------------------------------------------

    def attach_obs(self, obs) -> None:
        """Wire the engine's observability facade: mode transitions and
        fault injections emit refresh/fault-lane events from here."""
        self._obs = obs

    def mode_mix(self) -> tuple[int, int]:
        """(live Normal slabs, live Augmented slabs) — one sample of the
        paper's 6T/8T+ mode-mix timeline. O(1): incremental counters,
        sampled every engine step (describe() recomputes the same pair
        by reduction as the ground-truth cross-check)."""
        return self._live_by_mode[0], self._live_by_mode[1]

    # -- retention-fault injection / detection / healing ------------------------
    # (core/faults.py FaultModel; mirrors PagedKVPool's page-level
    # machinery at slab granularity. A slab's physical unit IS its slot —
    # rows never migrate between arrays — so offender tracking keys on
    # the row index.)

    def attach_fault_model(self, fm: F.FaultModel, *, integrity: bool = True,
                           tag: str = "") -> None:
        self._fm = fm
        self._integrity = integrity
        self._fault_tag = tag
        self._dirty.update(self.policies.keys())

    def _unit_id(self, row: int) -> str:
        return f"{self._fault_tag}slab{row}"

    def _packed_keys(self) -> list[str]:
        return sorted(self._state.get("packed", {}))

    def _unit_payload_np(self, row: int) -> tuple:
        ps = []
        for key in self._packed_keys():
            ps.append(np.asarray(self._state["packed"][key][:, row]))
            ps.append(np.asarray(self._state["scale"][key][:, row]))
        return tuple(ps)

    def _unit_word(self, row: int) -> int:
        return F.integrity_word(*self._unit_payload_np(row))

    def _flush_integrity(self) -> None:
        """Bring integrity words up to date for every augmented slab that
        was (re)written since the last flush. Static stores (write-once
        vlm prefix) also stash a host master copy — the scrub source."""
        for row in self.policies:
            if row in self._words and row not in self._dirty:
                continue
            payload = self._unit_payload_np(row)
            self._words[row] = F.integrity_word(*payload)
            if self.static:
                self._masters[row] = payload
        self._dirty.clear()

    def inject_faults(self, step: int) -> int:
        """Sample retention faults for every live augmented slab and
        corrupt the packed planes on device (deterministic under seed)."""
        if self._fm is None or not self.mixed:
            return 0
        self._flush_integrity()
        n = 0
        for row, pol in list(self.policies.items()):
            if row in self._pending:
                continue
            uid = self._unit_id(row)
            if self._fm.fault(uid, step, pol.age(step), self.retention_steps):
                mask = self._fm.corruption_mask(uid, step)
                self._state = _corrupt_row_op(self._state, row, mask)
                self._pending.add(row)
                self.stats["faults_injected"] += 1
                if self._obs is not None:
                    self._obs.on_fault("inject", uid, step)
                n += 1
        return n

    def scan_integrity(self, step: int) -> list[int]:
        """Verify every augmented slab against its stored integrity word;
        return the corrupted rows (detected, never silently served)."""
        if self._fm is None or not self._integrity:
            return []
        self._flush_integrity()
        bad: list[int] = []
        for row, word in list(self._words.items()):
            self.stats["integrity_checks"] += 1
            if self._unit_word(row) == word:
                continue
            bad.append(row)
            self._pending.discard(row)
            self.stats["faults_detected"] += 1
            uid = self._unit_id(row)
            self._offenders[uid] = self._offenders.get(uid, 0) + 1
            if (self._offenders[uid] >= self._fm.pin_threshold
                    and not self._pin_normal[row]):
                self._pin_normal[row] = True
                self.stats["pinned_normal"] += 1
        return bad

    def scrub_from_master(self, row: int) -> bool:
        """Heal a detected-corrupt slab from the host master copy (static
        stores only — dynamic slabs must be recomputed). Repeat-offender
        rows are pinned back to the Normal plane when the budget allows."""
        master = self._masters.get(row)
        if master is None:
            return False
        keys = self._packed_keys()
        packed = {k: jnp.asarray(master[2 * i])
                  for i, k in enumerate(keys)}
        scale = {k: jnp.asarray(master[2 * i + 1])
                 for i, k in enumerate(keys)}
        self._state = _restore_row_op(self._state, row, packed, scale)
        self.stats["maintenance_dispatches"] += 1
        self._words[row] = F.integrity_word(*master)
        self._dirty.discard(row)
        if self._pin_normal[row]:
            self.promote_slot(row, step=0)
        return True

    def fault_row(self, row: int) -> Optional[int]:
        return row

    def fault_unit_bytes(self, row: int) -> int:
        return self.slab_bytes_aug

    def fault_counters(self) -> dict:
        return {k: self.stats[k] for k in
                ("faults_injected", "faults_detected", "faults_masked",
                 "refresh_misses", "integrity_checks", "pinned_normal")}

    def faults_pending(self) -> int:
        return len(self._pending)

    # -- speculative decode: slab snapshot / rollback --------------------------

    def speculative_snapshot(self) -> None:
        """Pin the pre-draft slab planes. The draft pass advances the real
        recurrent state, so the engine dispatches drafts through a
        NON-donating step (these buffers stay valid) and the verify scan
        replays the window from this exact tree — rejected draft steps
        never touch committed storage."""
        self._spec_snapshot = self._state
        self.stats["spec_snapshots"] += 1

    def speculative_restore(self) -> None:
        """Roll the slab planes back to the pre-draft snapshot (always
        called before verify: verify itself re-runs the accepted steps)."""
        assert self._spec_snapshot is not None, "no speculative snapshot"
        self._state = self._spec_snapshot
        self._spec_snapshot = None
        self.stats["spec_rollbacks"] += 1

    def retract_token_writes(self, rows: np.ndarray,
                             new_lengths: np.ndarray) -> int:
        """Slab rollback is wholesale (snapshot/restore above): there is
        no per-token storage to retract."""
        return 0

    # -- device views ---------------------------------------------------------

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, new) -> None:
        self._state = new

    def device_tables(self) -> dict:
        if not self.mixed:
            return {}
        if self._tables_cache is None:
            self._tables_cache = {
                self.table_key: to_device(self.slot_mode)}
        return self._tables_cache

    # -- array event accounting ------------------------------------------------

    def _value_counts(self, rows: np.ndarray) -> tuple[int, int]:
        if rows.size == 0:
            return 0, 0
        modes = self.slot_mode[rows]
        alive = self.slot_alloc[rows]
        v = self.values_per_slot
        return (int((alive & (modes == 0)).sum()) * v,
                int((alive & (modes == 1)).sum()) * v)

    def read_value_counts(self, rows: np.ndarray,
                          lengths: np.ndarray) -> tuple[int, int]:
        """Every dispatch senses each active row's whole slab once."""
        return self._value_counts(rows)

    def write_value_counts(self, rows: np.ndarray, n_new: int,
                           write_starts: np.ndarray) -> tuple[int, int]:
        """...and (non-static stores) re-writes it once."""
        if self.static:
            return 0, 0
        return self._value_counts(rows)

    def physical_bytes(self) -> int:
        """Staged plane capacity (both planes when mode-mixing is on —
        the slab analogue of the pool's two arenas)."""
        phys = self.max_batch * self.slab_bytes_normal
        if self.mixed:
            phys += self.max_batch * self.slab_bytes_aug
        return phys

    def describe(self) -> dict:
        live_n = int((self.slot_alloc & (self.slot_mode == 0)).sum())
        live_a = int((self.slot_alloc & (self.slot_mode == 1)).sum())
        return {
            "kind": self.kind,
            "pool_mode": self.pool_mode,
            "static": self.static,
            "state_bits": self.state_bits,
            "slab_bytes_normal": self.slab_bytes_normal,
            "slab_bytes_aug": self.slab_bytes_aug,
            "slab_capacity_factor": (self.slab_bytes_normal
                                     / self.slab_bytes_aug),
            "slabs_live_normal": live_n,
            "slabs_live_augmented": live_a,
            "budget_bytes": self.budget_bytes,
            "live_bytes": self.live_bytes,
            "retention_steps": self.retention_steps,
            **self.stats,
        }


# ---------------------------------------------------------------------------
# CompositeStore — one row spans several stores (vlm: paged KV + prefix)
# ---------------------------------------------------------------------------

class CompositeStore:
    """Fans the StateStore interface out over named parts; a row is
    admitted into ALL parts or none. `state` is {part_name: part_state};
    refresh keys are (part_name, part_key)."""

    kind = "composite"

    def __init__(self, parts: dict):
        self.parts = parts

    def can_admit_tokens(self, n: int) -> bool:
        return all(p.can_admit_tokens(n) for p in self.parts.values())

    def admit_row(self, row: int, n_tokens: int, step: int, *,
                  shared=None) -> bool:
        done = []
        for name, p in self.parts.items():
            if not p.admit_row(row, n_tokens, step):
                for d in done:
                    d.release_row(row)
                return False
            done.append(p)
        return True

    def ensure_position(self, row: int, pos: int, step: int) -> bool:
        return all(p.ensure_position(row, pos, step)
                   for p in self.parts.values())

    def max_row_tokens(self) -> Optional[int]:
        caps = [c for c in (p.max_row_tokens()
                            for p in self.parts.values()) if c is not None]
        return min(caps) if caps else None

    def release_row(self, row: int) -> None:
        for p in self.parts.values():
            p.release_row(row)

    def note_token_writes(self, rows, positions, step) -> None:
        for p in self.parts.values():
            p.note_token_writes(rows, positions, step)

    def refresh_due(self, step: int) -> list:
        return [(name, key) for name, p in self.parts.items()
                for key in p.refresh_due(step)]

    def refresh(self, key, step: int) -> None:
        name, part_key = key
        self.parts[name].refresh(part_key, step)

    def max_augmented_age(self, step: int) -> int:
        return max(p.max_augmented_age(step) for p in self.parts.values())

    def attach_obs(self, obs) -> None:
        for p in self.parts.values():
            p.attach_obs(obs)

    def mode_mix(self) -> tuple[int, int]:
        return self._sum_counts(lambda p: p.mode_mix())

    # -- retention faults: fan out, part-qualified keys -------------------------

    def attach_fault_model(self, fm, *, integrity: bool = True,
                           tag: str = "") -> None:
        for name, p in self.parts.items():
            p.attach_fault_model(fm, integrity=integrity,
                                 tag=f"{tag}{name}:")

    def inject_faults(self, step: int) -> int:
        return sum(p.inject_faults(step) for p in self.parts.values())

    def scan_integrity(self, step: int) -> list:
        return [(name, key) for name, p in self.parts.items()
                for key in p.scan_integrity(step)]

    def scrub_from_master(self, key) -> bool:
        name, part_key = key
        return self.parts[name].scrub_from_master(part_key)

    def fault_row(self, key) -> Optional[int]:
        name, part_key = key
        return self.parts[name].fault_row(part_key)

    def fault_unit_bytes(self, key) -> int:
        name, part_key = key
        return self.parts[name].fault_unit_bytes(part_key)

    def fault_counters(self) -> dict:
        out: dict = {}
        for p in self.parts.values():
            for k, v in p.fault_counters().items():
                out[k] = out.get(k, 0) + v
        return out

    def faults_pending(self) -> int:
        return sum(p.faults_pending() for p in self.parts.values())

    @property
    def state(self):
        return {name: p.state for name, p in self.parts.items()}

    @state.setter
    def state(self, new) -> None:
        for name, p in self.parts.items():
            p.state = new[name]

    def device_tables(self) -> dict:
        out = {}
        for p in self.parts.values():
            out.update(p.device_tables())
        return out

    @property
    def live_bytes(self) -> int:
        return sum(p.live_bytes for p in self.parts.values())

    @property
    def budget_bytes(self) -> int:
        return sum(p.budget_bytes for p in self.parts.values())

    @property
    def aug_bits(self) -> int:
        return next(iter(self.parts.values())).aug_bits

    def _sum_counts(self, fn) -> tuple[int, int]:
        n = a = 0
        for p in self.parts.values():
            pn, pa = fn(p)
            n, a = n + pn, a + pa
        return n, a

    def read_value_counts(self, rows, lengths):
        return self._sum_counts(
            lambda p: p.read_value_counts(rows, lengths))

    def write_value_counts(self, rows, n_new, starts):
        return self._sum_counts(
            lambda p: p.write_value_counts(rows, n_new, starts))

    def physical_bytes(self) -> int:
        return sum(p.physical_bytes() for p in self.parts.values())

    def describe(self) -> dict:
        parts = {name: p.describe() for name, p in self.parts.items()}
        agg = {"kind": self.kind, "parts": parts,
               "budget_bytes": self.budget_bytes,
               "live_bytes": self.live_bytes}
        for k in ("refreshes", "refresh_bytes", "augment_events",
                  "promote_events", "maintenance_dispatches",
                  "alloc_failures", "peak_live_bytes", "augment_bytes",
                  "faults_injected", "faults_detected", "faults_masked",
                  "refresh_misses", "integrity_checks", "pinned_normal",
                  "pages_decommissioned"):
            agg[k] = sum(d.get(k, 0) for d in parts.values())
        return agg


# ---------------------------------------------------------------------------
# store registry + per-family step builders
# ---------------------------------------------------------------------------

def make_store(cfg: ModelConfig, *, max_batch: int, max_seq: int,
               budget_bytes: Optional[int] = None,
               pages_normal: Optional[int] = None,
               pages_packed: Optional[int] = None,
               retention_steps: Optional[int] = None):
    """The per-family store registry: every architecture's decode state
    maps onto paged KV pages, fixed-size augmented slabs, or both."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return PagedKVPool(cfg, max_batch=max_batch, max_seq=max_seq,
                           pages_normal=pages_normal,
                           pages_packed=pages_packed,
                           budget_bytes=budget_bytes,
                           retention_steps=retention_steps)
    if fam == "audio":
        # decoder self-KV pages + the cross-attention KV as a STATIC
        # prefix band of the same pool (the paper's static plane)
        return PagedKVPool(cfg, max_batch=max_batch, max_seq=max_seq,
                           pages_normal=pages_normal,
                           pages_packed=pages_packed,
                           budget_bytes=budget_bytes,
                           retention_steps=retention_steps,
                           prefix_tokens=cfg.encdec.n_frames)
    if fam == "vlm":
        from repro.models import vision
        nb = vision._n_blocks(cfg)
        pool_kw = dict(max_batch=max_batch, max_seq=max_seq,
                       pages_normal=pages_normal,
                       pages_packed=pages_packed,
                       retention_steps=retention_steps,
                       n_layers=nb * vision.N_SELF_PER_BLOCK)
        pool = PagedKVPool(cfg, budget_bytes=None, **pool_kw)
        prefix = AugmentedStatePool(
            cfg, vision.prefix_state_specs(cfg, max_batch),
            max_batch=max_batch, retention_steps=retention_steps,
            static=True, table_key="prefix_modes")
        if budget_bytes is not None:
            # ONE operator budget spans both parts: split proportionally
            # to their default (full-capacity) shares so stats() reports
            # exactly the requested total and prefix admission is bound
            # by it too
            total_default = pool.budget_bytes + prefix.budget_bytes
            kv_share = budget_bytes * pool.budget_bytes // total_default
            pool.budget_bytes = kv_share
            prefix.budget_bytes = budget_bytes - kv_share
        return CompositeStore({"kv": pool, "prefix": prefix})
    if fam in ("ssm", "hybrid"):
        from repro.models import model as M
        from repro.configs.base import ShapeConfig
        shape = ShapeConfig("serve", max_seq, max_batch, "decode")
        return AugmentedStatePool(cfg, M.abstract_cache(cfg, shape),
                                  max_batch=max_batch,
                                  budget_bytes=budget_bytes,
                                  retention_steps=retention_steps)
    raise ValueError(f"no decode-state store for family {fam!r}")


def make_step_fns(cfg: ModelConfig, store, *,
                  rules=None) -> dict[str, Optional[Callable]]:
    """(decode, prefill, verify) callables for `jax.jit` over (params,
    state, batch) — the ONE place the store kind meets the family
    dispatch. ``verify`` is the speculative-decode verify step (None for
    families without one: the engine falls back to stepwise decode)."""
    from repro.models import model as M
    fam = cfg.family

    if fam in ("dense", "moe", "audio"):
        # named functions: jit names each program after its function
        # (`jit_decode_step(...)` in a profiler trace, not `jit__lambda`)
        def decode_step(params, state, batch):
            return M.paged_decode_step(cfg, params, state, batch,
                                       rules=rules)

        def prefill_step(params, state, batch):
            return M.paged_prefill_step(cfg, params, state, batch,
                                        rules=rules)

        def verify_step(params, state, batch):
            return M.paged_verify_step(cfg, params, state, batch,
                                       rules=rules)

        return {"decode": decode_step,
                "prefill": prefill_step if fam != "audio" else None,
                "verify": verify_step if fam != "audio" else None}
    if fam == "vlm":
        prefix_bits = store.parts["prefix"].state_bits

        def vlm_decode(params, state, batch):
            prefix = slab_reconstitute(state["prefix"],
                                       batch.get("prefix_modes"),
                                       prefix_bits)
            logits, new_kv = M.paged_decode_step(
                cfg, params, state["kv"], {**batch, **prefix}, rules=rules)
            return logits, {"kv": new_kv, "prefix": state["prefix"]}
        return {"decode": vlm_decode, "prefill": None, "verify": None}

    # slab families (ssm / hybrid): reconstitute -> family step -> store
    bits = store.state_bits

    def slab_decode(params, state, batch):
        cache = slab_reconstitute(state, batch.get("slot_modes"), bits)
        logits, new_cache = M.decode_step(cfg, params, cache, batch,
                                          rules=rules)
        return logits, slab_store_back(state, new_cache,
                                       batch.get("slot_modes"), bits,
                                       write=batch.get("write_mask"))

    def slab_verify(params, state, batch):
        """Speculative verify for recurrent-state families: replay the
        W-token window as a `lax.scan` of the SAME single-token decode
        step from the pre-draft slab state (the engine restored it),
        then commit exactly the state after the accepted prefix.

        Each scan step is bit-identical to one stepwise dispatch (same
        function, same single-token shapes), which is what makes slab
        speculation token-identical; the wholesale restore + re-scan IS
        the rollback — rejected draft steps live only in intermediate
        scan carries that are never stored back."""
        tokens = batch["tokens"]                        # (B, W)
        starts = batch["positions"]                     # (B,)
        wmask = batch["write_mask"]                     # (B, W) bool
        modes = batch.get("slot_modes")
        B, W = tokens.shape
        cache0 = slab_reconstitute(state, modes, bits)

        def body(cache, w):
            step_batch = {
                "tokens": jax.lax.dynamic_slice_in_dim(tokens, w, 1, 1),
                "positions": starts + w}
            lg, new_cache = M.decode_step(cfg, params, cache, step_batch,
                                          rules=rules)
            return new_cache, (lg[:, -1], new_cache)

        _, (lgs, caches) = jax.lax.scan(body, cache0, jnp.arange(W))
        logits = jnp.moveaxis(lgs, 0, 1)                # (B, W, V)

        # greedy acceptance (same formula as the paged verify step);
        # capped by the host's per-row window mask near retirement
        v = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        mism = jnp.concatenate([tokens[:, 1:] != v[:, :-1],
                                jnp.ones((B, 1), bool)], axis=1)
        n_acc = jnp.argmax(mism, axis=1) + 1            # (B,) in [1, W]
        cap = jnp.maximum(wmask.sum(axis=1), 1)
        sel = jnp.minimum(n_acc, cap) - 1               # committed step idx

        def pick(leaf):
            # stacked scan ys: (W, L?, B, ...) with batch at axis 2
            idx = sel.reshape((1, 1, -1) + (1,) * (leaf.ndim - 3))
            return jnp.take_along_axis(leaf, idx.astype(jnp.int32),
                                       axis=0)[0]

        committed = jax.tree.map(pick, caches)
        return logits, slab_store_back(state, committed, modes, bits,
                                       write=wmask[:, 0])

    return {"decode": slab_decode, "prefill": None, "verify": slab_verify}
