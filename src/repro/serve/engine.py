"""Serving engine: continuous batching over unified augmented state stores.

EVERY model family serves through the same path: a `Scheduler` (FIFO
admission, slot-free join/leave, preemption-with-recompute, refresh pass)
driving a per-family `state_store.StateStore`:

  dense / moe   PagedKVPool — two-plane paged KV whose pages mode-switch
                between Normal (bf16) and Augmented (packed int4/int8)
  audio         PagedKVPool with a STATIC prefix band: decoder self-KV
                pages plus the cross-attention KV as fixed-length pages
  vlm           CompositeStore: PagedKVPool (self-KV) + AugmentedStatePool
                (static patch-KV prefix slabs)
  ssm / hybrid  AugmentedStatePool — fixed-size recurrent-state slabs
                (SSD/LRU/conv state, window ring KV) stored per-slot as
                Normal native dtype or Augmented packed int8/int4

All of them budget bytes against the same modeled SRAM array, augment
cold storage under pressure to admit more concurrent sequences, clock
augmented (dynamic) storage with `core/retention.RefreshPolicy`, and feed
the array-level event/energy ledger (`stats()["imc"]`).

Requests are never dropped: `add_request` enqueues when the store or the
running batch is full and returns the row index on immediate admission or
None when queued; `generate` drains the queue to completion. Empty
prompts require an explicit `bos_id` — there is no silent token-0 feed.

Hot-path shape is unchanged: a P-token prompt costs ceil(P /
prefill_chunk) jitted dispatches on families with chunked prefill (P
decode steps otherwise, as before), one batched decode dispatch serves
every running row, and host bookkeeping is vectorized numpy. Store
maintenance (augment / promote / refresh / slab reset) dispatches are
accounted separately (`stats()["pool"]["maintenance_dispatches"]`).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import amc
from repro.core import faults as faults_mod
from repro.distributed.fault import (SimulatedFailure, StragglerMonitor,
                                     Supervisor)
from repro.distributed.sharding import Rules
from repro.imc import energy as imc_energy
from repro.launch.mesh import mesh_context
from repro.models import augment
from repro.models import model as M
from repro.models.params import init_params, is_pspec
from repro.obs import hooks as obs_hooks
from repro.serve import state_store
from repro.serve.cache_pool import to_device
from repro.serve.prefix import PrefixIndex
from repro.serve.scheduler import QueueEntry, Scheduler


@dataclasses.dataclass(eq=False)
class Request:
    prompt: np.ndarray            # (plen,) int32
    max_new_tokens: int = 16
    id: int = 0


def _resolve_draft_cfg(cfg: ModelConfig) -> ModelConfig:
    """Config the speculative draft pass decodes with — the paper's cheap
    dynamic-plane read of the same stored bits. "dequant" / "dense" swap
    the Pallas kernels for the plain-XLA reference paths (much cheaper in
    interpret mode, and still reading the augmented storage); "packed"
    forces the packed matmuls; "imcN" drafts through the bit-serial IMC
    dot at N-bit activations; "same" drafts at full quality (every draft
    accepted — a latency-hiding baseline, not a cost saving)."""
    impl = cfg.amc.spec_draft_impl
    a = cfg.amc
    if impl == "same":
        return cfg
    if impl == "dequant":
        amc_cfg = dataclasses.replace(a, kv_impl="dequant")
    elif impl == "dense":
        amc_cfg = dataclasses.replace(a, matmul_impl="dense",
                                      kv_impl="dequant")
    elif impl == "packed":
        amc_cfg = dataclasses.replace(a, matmul_impl="packed")
    elif impl.startswith("imc") and impl[3:] in ("1", "4", "8"):
        amc_cfg = dataclasses.replace(a, matmul_impl="imc",
                                      imc_abits=int(impl[3:]))
    else:
        raise ValueError(
            f"unknown spec_draft_impl {impl!r} (expected dequant | dense "
            f"| packed | imc1/imc4/imc8 | same)")
    return dataclasses.replace(cfg, amc=amc_cfg)


def init_dense_params(dense_cfg: ModelConfig, seed: int, sharding):
    """Seeded dense weights of `dense_cfg`, made on `sharding`'s devices by
    one jitted program: each leaf is drawn and cast in place, with no
    eager f32 copy of a whole stacked weight."""
    tree = M.abstract_params(dense_cfg)
    return jax.jit(lambda key: init_params(tree, key),
                   out_shardings=sharding)(jax.random.PRNGKey(seed))


def _abstract_bytes(tree) -> int:
    """Total bytes of a PSpec tree (dense logical footprint)."""
    leaves = jax.tree.leaves(tree, is_leaf=is_pspec)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.jdtype).itemsize
               for l in leaves)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, mesh, *, max_batch: int = 8,
                 max_seq: int = 256, prefill_chunk: int = 32, params=None,
                 weight_mode: Optional[str] = None,
                 kv_mode: Optional[str] = None, seed: int = 0,
                 bos_id: Optional[int] = None,
                 pool_mode: Optional[str] = None,
                 pool_budget_bytes: Optional[int] = None,
                 pool_pages_normal: Optional[int] = None,
                 pool_pages_packed: Optional[int] = None,
                 retention_steps: Optional[int] = None,
                 matmul_impl: Optional[str] = None,
                 imc_abits: Optional[int] = None,
                 state_bits: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_draft_impl: Optional[str] = None,
                 prefix_cache: Optional[int] = None,
                 fault_rate: Optional[float] = None,
                 fault_seed: Optional[int] = None,
                 array_loss_rate: Optional[float] = None,
                 fault_temp_c: Optional[float] = None,
                 integrity_check: Optional[bool] = None,
                 max_retries: Optional[int] = None,
                 fault_pin_threshold: Optional[int] = None,
                 trace: Optional[bool] = None,
                 metrics: Optional[bool] = None,
                 obs_sample_every: Optional[int] = None,
                 obs=None):
        # engine-level AMC knobs override the config (e.g. serve a dense
        # checkpoint with ternary weights without touching the arch file)
        fault_overrides = (fault_rate, fault_seed, array_loss_rate,
                           fault_temp_c, integrity_check, max_retries,
                           fault_pin_threshold)
        obs_overrides = (trace, metrics, obs_sample_every)
        if weight_mode is not None or kv_mode is not None \
                or pool_mode is not None or matmul_impl is not None \
                or imc_abits is not None or state_bits is not None \
                or spec_k is not None or spec_draft_impl is not None \
                or prefix_cache is not None \
                or any(v is not None for v in fault_overrides) \
                or any(v is not None for v in obs_overrides):
            # numeric/bool fault knobs need explicit None checks — 0.0 and
            # False are legitimate override values an `or` would drop
            cfg = dataclasses.replace(cfg, amc=dataclasses.replace(
                cfg.amc,
                weight_mode=weight_mode or cfg.amc.weight_mode,
                kv_mode=kv_mode or cfg.amc.kv_mode,
                pool_mode=pool_mode or cfg.amc.pool_mode,
                matmul_impl=matmul_impl or cfg.amc.matmul_impl,
                imc_abits=imc_abits or cfg.amc.imc_abits,
                state_bits=state_bits or cfg.amc.state_bits,
                spec_k=cfg.amc.spec_k if spec_k is None else spec_k,
                spec_draft_impl=spec_draft_impl or cfg.amc.spec_draft_impl,
                prefix_cache=(cfg.amc.prefix_cache if prefix_cache is None
                              else prefix_cache),
                fault_rate=(cfg.amc.fault_rate if fault_rate is None
                            else fault_rate),
                fault_seed=(cfg.amc.fault_seed if fault_seed is None
                            else fault_seed),
                array_loss_rate=(cfg.amc.array_loss_rate
                                 if array_loss_rate is None
                                 else array_loss_rate),
                fault_temp_c=(cfg.amc.fault_temp_c if fault_temp_c is None
                              else fault_temp_c),
                integrity_check=(cfg.amc.integrity_check
                                 if integrity_check is None
                                 else integrity_check),
                max_retries=(cfg.amc.max_retries if max_retries is None
                             else max_retries),
                fault_pin_threshold=(cfg.amc.fault_pin_threshold
                                     if fault_pin_threshold is None
                                     else fault_pin_threshold),
                trace=cfg.amc.trace if trace is None else trace,
                metrics=cfg.amc.metrics if metrics is None else metrics,
                obs_sample_every=(cfg.amc.obs_sample_every
                                  if obs_sample_every is None
                                  else obs_sample_every)))
        self.cfg, self.mesh = cfg, mesh
        self.max_batch, self.max_seq = max_batch, max_seq
        self.prefill_chunk = min(prefill_chunk, max_seq)
        self.bos_id = bos_id
        shape = ShapeConfig("serve", max_seq, max_batch, "decode")
        self.rules = Rules.make(mesh, cfg, shape)
        dense_cfg = dataclasses.replace(
            cfg, amc=dataclasses.replace(cfg.amc, weight_mode="normal"))
        # weights and decode state live on this engine's own mesh (an
        # ArrayFleet gives every array its own devices), replicated over
        # all of its devices: each holds the whole packed tree, and only
        # the step's activations follow the Rules' head sharding
        on_mesh = NamedSharding(mesh, PartitionSpec())
        with mesh_context(mesh), jax.default_device(mesh.devices.flat[0]):
            if params is None:
                params = init_dense_params(dense_cfg, seed, on_mesh)
            # pack the matmul weights into augmented storage (no-op for
            # weight_mode="normal", already-packed trees, other families);
            # jitted, so the packing streams each weight once instead of
            # holding eager f32 copies of it
            self.params = jax.jit(
                functools.partial(augment.augment_params, cfg),
                out_shardings=on_mesh)(jax.device_put(params, on_mesh))
            self.store = state_store.make_store(
                cfg, max_batch=max_batch, max_seq=max_seq,
                budget_bytes=pool_budget_bytes,
                pages_normal=pool_pages_normal,
                pages_packed=pool_pages_packed,
                retention_steps=retention_steps)
            self.store.state = jax.device_put(self.store.state, on_mesh)
        # observability facade (obs/): Null unless a plane is switched on,
        # so every hook below is a constant no-op on the default path.
        # A pre-built facade may be injected (`obs=`): the ArrayFleet
        # passes per-array facades that share one trace epoch and one
        # metrics registry but record on distinct trace pids.
        self.obs = obs if obs is not None else obs_hooks.make_engine_obs(
            cfg.amc)
        if self.obs.enabled:
            self.store.attach_obs(self.obs)
        self.scheduler = Scheduler(self.store, max_batch=max_batch,
                                   obs=self.obs)
        # shared-prefix page reuse (serve/prefix.py): paged stores with a
        # share band get a token-hash index over cached prefix page runs;
        # hits map the SAME physical pages into the new row (refcounted)
        # and prefill only the tail. None on every other path — zero cost.
        self._prefix_index: Optional[PrefixIndex] = None
        if self.store.kind == "paged" \
                and getattr(self.store, "share_entries", 0) > 0:
            self._prefix_index = PrefixIndex(self.store.share_entries,
                                             self.cfg.amc.page_size)
            self.store.attach_prefix_index(self._prefix_index)
        self.prefill_dispatch_count = 0   # prefill-only subset of dispatches
        self._prefix_saved = 0            # prefill dispatches skipped by hits
        # retention-fault injection + self-healing (core/faults.py): the
        # model samples per-page/per-slab early expiries and refresh
        # misses deterministically under the seed; the store detects them
        # via integrity words; recovery runs scrub / recompute / retry
        # through the scheduler. Inert at fault_rate == array_loss_rate
        # == 0 (no model attached, zero hot-path cost).
        a2 = self.cfg.amc
        self._fault_model: Optional[faults_mod.FaultModel] = None
        if a2.fault_rate > 0.0 or a2.array_loss_rate > 0.0:
            self._fault_model = faults_mod.FaultModel(
                rate=a2.fault_rate, seed=a2.fault_seed,
                temp_c=a2.fault_temp_c,
                array_loss_rate=a2.array_loss_rate,
                pin_threshold=a2.fault_pin_threshold)
            self.store.attach_fault_model(self._fault_model,
                                          integrity=a2.integrity_check)
        # whole-array failure events drain-and-requeue through the
        # distributed fault supervisor; slow fault-recovery steps feed the
        # straggler monitor (mitigations are counted, not acted on)
        self.supervisor = Supervisor(self._recover_array_loss,
                                     max_restarts=64)
        self.straggler = StragglerMonitor()
        self._forced_array_loss = False
        self.failed: dict[int, list[int]] = {}
        self._fault_stats = {
            "recovered_scrub": 0, "recovered_recompute": 0, "retried": 0,
            "uncorrectable": 0, "array_losses": 0, "array_loss_requeues": 0,
            "straggler_mitigations": 0,
        }
        self._logical_weight_bytes = _abstract_bytes(
            M.abstract_params(dense_cfg))
        self._logical_cache_bytes = _abstract_bytes(M.abstract_cache(
            dataclasses.replace(
                cfg, amc=dataclasses.replace(cfg.amc, kv_mode="normal")),
            shape))
        fns = state_store.make_step_fns(cfg, self.store, rules=self.rules)
        self._decode = jax.jit(fns["decode"], donate_argnums=(1,))
        self._prefill = (jax.jit(fns["prefill"], donate_argnums=(1,))
                         if fns["prefill"] is not None else None)
        # self-speculative decoding: draft spec_k - 1 tokens per round out
        # of the cheap (dynamic-plane) representation, verify the whole
        # window through the full packed path in ONE dispatch, accept the
        # longest matching prefix (token-identical to stepwise decode)
        self.spec_k = cfg.amc.spec_k
        self._verify = (jax.jit(fns["verify"], donate_argnums=(1,))
                        if fns.get("verify") is not None else None)
        self._spec = self.spec_k > 1 and self._verify is not None
        self._spec_stats = {"spec_rounds": 0, "draft_dispatches": 0,
                            "verify_dispatches": 0, "accepted_tokens": 0}
        if self._spec:
            self._draft_cfg = _resolve_draft_cfg(cfg)
            draft_fns = state_store.make_step_fns(self._draft_cfg,
                                                  self.store,
                                                  rules=self.rules)
            # slab drafts advance the recurrent state the snapshot holds a
            # REFERENCE to — the draft step must not donate those buffers;
            # paged drafts only write fresh page slots, so donation is safe
            donate = (1,) if self.store.kind == "paged" else ()
            self._draft_decode = jax.jit(draft_fns["decode"],
                                         donate_argnums=donate)
        # slot bookkeeping (host side, int32 once — dispatched as-is)
        self.positions = np.zeros(max_batch, np.int32)
        self.remaining = np.zeros(max_batch, np.int32)
        self.active = np.zeros(max_batch, bool)
        self.last_token = np.zeros(max_batch, np.int32)
        self.slot_req: list[Optional[Request]] = [None] * max_batch
        self._slot_entry: list[Optional[QueueEntry]] = [None] * max_batch
        self.outputs: dict[int, list[int]] = {}
        self.dispatch_count = 0   # jitted device dispatches (prefill+decode)
        self.step_idx = 0         # decode-step clock (retention time base)
        self._admitted_since_step = 0   # engine.step's `admitted` argument
        # array-level event/energy ledger (imc/energy.py): weight-side
        # events follow cfg.amc.matmul_impl, state-side events follow the
        # per-page / per-slab mode (Normal storage costs 6T accesses,
        # Augmented the 8T dynamic ones). Analytic, host-side — per real
        # dispatch, for every family.
        self.energy_ledger = imc_energy.ImcEventLedger()
        self._refresh_bytes_seen = 0

    # -- store views -----------------------------------------------------------

    @property
    def pool(self):
        """The row's decode-state store (historic name kept: benches and
        tests address the byte budget / page geometry through it)."""
        return self.store

    @property
    def cache(self):
        """The decode-state device tree (arenas and/or slab planes)."""
        return self.store.state

    @property
    def _queue(self) -> deque:
        return self.scheduler.queue

    def _store_parts(self) -> list:
        """The store's billable units (composite stores bill each part at
        its own augmented width)."""
        if self.store.kind == "composite":
            return list(self.store.parts.values())
        return [self.store]

    def _sync_refresh_events(self) -> None:
        """Fold store refresh traffic accrued since the last sync into the
        ledger's "refresh" group, so energy totals include maintenance."""
        rb = sum(p.stats["refresh_bytes"] for p in self._store_parts())
        if rb > self._refresh_bytes_seen:
            self.energy_ledger.add(
                imc_energy.refresh_events(rb - self._refresh_bytes_seen),
                "refresh")
            self._refresh_bytes_seen = rb

    # -- array event accounting ------------------------------------------------

    def _account_dispatch(self, rows: np.ndarray, n_new: int,
                          read_lengths: Optional[np.ndarray],
                          write_starts: np.ndarray) -> None:
        """Fold one dispatch into the event ledger: weight-side matmul
        events for `n_new` useful tokens per row, state reads over
        `read_lengths` (None for write-only accounting), and the write of
        the `n_new` tokens, costed by the mode of the storage each lands
        in (the store splits the counts by page/slab mode)."""
        if rows.size == 0:
            return
        n_tok = int(rows.size) * n_new
        self.energy_ledger.add(
            imc_energy.decode_matmul_events(self.cfg, n_tok), "weights")
        # each part bills at ITS augmented width (a vlm engine's int4 KV
        # pages and int8 prefix slabs cost different 8T cells/value)
        for part in self._store_parts():
            if read_lengths is not None:
                nn, na = part.read_value_counts(rows, read_lengths)
                self.energy_ledger.add(
                    imc_energy.kv_read_events(nn, na,
                                              aug_bits=part.aug_bits),
                    "kv_read")
            wn, wa = part.write_value_counts(rows, n_new, write_starts)
            self.energy_ledger.add(
                imc_energy.kv_write_events(wn, wa, aug_bits=part.aug_bits),
                "kv_write")

    # -- continuous batching ---------------------------------------------------

    def add_request(self, req: Request) -> Optional[int]:
        """Enqueue a request and admit as many queued requests as fit.

        Returns the running-batch row if THIS request was admitted
        immediately, else None — meaning queued, never dropped: the
        scheduler admits it between later decode steps (`generate` and
        `step_all` both drain the queue)."""
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens} "
                f"(a request that generates nothing would occupy a row "
                f"forever)")
        if req.id in self.outputs or any(
                e.req.id == req.id for e in self.scheduler.queue):
            # outputs covers running AND completed ids: reusing either
            # would silently append the new request's tokens onto the
            # old one's list
            raise ValueError(
                f"request id {req.id} is already queued, running or "
                f"completed on this engine — ids key the output map, so "
                f"reusing one would silently merge two requests' tokens")
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            if self.bos_id is None:
                raise ValueError(
                    "empty prompt with no bos_id: pass bos_id=<token> to "
                    "ServeEngine to define what an empty prompt decodes "
                    "from (there is no implicit token 0)")
            prompt = np.array([self.bos_id], np.int32)
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.cfg.vocab:
            # out-of-range ids would gather garbage rows deep inside
            # prefill (and poison the prefix index) — reject at the door
            bad = prompt[(prompt < 0) | (prompt >= self.cfg.vocab)]
            raise ValueError(
                f"prompt contains token id(s) outside the vocab "
                f"[0, {self.cfg.vocab}): {bad[:8].tolist()}"
                f"{'...' if bad.size > 8 else ''}")
        if prompt.size > self.max_seq:
            # past max_seq every cache write would clamp to the last slot,
            # silently corrupting the row — reject instead
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds "
                f"max_seq={self.max_seq} cache slots")
        cap_tokens = self.store.max_row_tokens()
        if cap_tokens is not None:
            # the row stores min(P + N - 1, max_seq - 1) tokens at peak
            # (prompt + generated, last generation never written); a
            # request the store can NEVER hold would otherwise loop
            # admission/preemption forever inside generate()
            need = min(prompt.size + req.max_new_tokens - 1,
                       self.max_seq - 1)
            if need > cap_tokens:
                raise ValueError(
                    f"request needs {need} cache tokens at peak (prompt "
                    f"{prompt.size} + max_new_tokens {req.max_new_tokens}"
                    f", capped by max_seq={self.max_seq}) but the store "
                    f"holds at most {cap_tokens} tokens per row — raise "
                    f"the pool budget/pages or shrink the request")
        entry = QueueEntry(req=req, prompt=prompt,
                           remaining=req.max_new_tokens,
                           enqueue_step=self.step_idx)
        self.obs.on_enqueue(req.id, int(prompt.size), req.max_new_tokens,
                            self.step_idx)
        self.scheduler.enqueue(entry)
        admitted = self._admit()
        return admitted.get(req.id)

    def _admit(self) -> dict[int, int]:
        """Admission pass: move queued requests into free rows while both
        a row and store capacity exist. FIFO, head-of-line."""
        admitted: dict[int, int] = {}
        prompt_tokens = 0
        with self.obs.phase_span("admit") as span:
            while True:
                free = np.flatnonzero(~self.active)
                if free.size == 0:
                    break
                row = int(free[0])
                entry = self.scheduler.pop_admittable(self.step_idx)
                if entry is None:
                    break
                shared = self._prefix_match(entry)
                if not self.scheduler.admit(
                        row, len(entry.prompt), self.step_idx,
                        shared=(None if shared is None else
                                (shared[0].row, shared[1]))):
                    # can_admit_tokens raced a concurrent change; requeue
                    self.scheduler.enqueue(entry, front=True)
                    break
                prompt_tokens += self._start_row(row, entry, shared=shared)
                admitted[entry.req.id] = row
            span.set_metadata(admitted=len(admitted),
                              prompt_tokens=prompt_tokens)
        self._admitted_since_step += len(admitted)
        return admitted

    def _prefix_match(self, entry: QueueEntry):
        """Deepest cached prefix of the tokens this admission will FEED
        (prompt[:-1] — the last prompt token goes through decode), as
        (PrefixEntry, matched_tokens), or None."""
        if self._prefix_index is None:
            return None
        fed = entry.prompt[:-1]
        if fed.size < self.cfg.amc.page_size:
            return None
        e, m = self._prefix_index.match(fed)
        if e is None:
            self._prefix_index.note_miss()
            self.obs.on_prefix("miss", entry.req.id, 0, self.step_idx)
            return None
        return e, m

    def _start_row(self, row: int, entry: QueueEntry, shared=None) -> int:
        """Seat `entry` in `row` and prefill its prompt; returns the
        number of prompt tokens prefilled (prefix-cache hits skip some)."""
        self.active[row] = True
        self.slot_req[row] = entry.req
        self._slot_entry[row] = entry
        self.positions[row] = 0
        self.remaining[row] = entry.remaining
        self.outputs.setdefault(entry.req.id, [])
        self.obs.on_admit(entry.req.id, row, self.step_idx)
        prompt = entry.prompt
        # feed prompt[:-1] into the cache (the last prompt token is fed by
        # the first batched decode step, whose argmax is the first
        # generated token)
        fed = prompt[:-1]
        m = 0
        if shared is not None:
            # prefix hit: admit_row already mapped the cached run's pages
            # into this row — skip their prefill dispatches entirely and
            # start the position clock past the shared tokens
            e, m = shared
            self.positions[row] = m
            self._prefix_index.note_hit(e, m, self.step_idx)
            self.store.note_entry_use(e.row, m, self.step_idx)
            C = self.prefill_chunk
            self._prefix_saved += -(-m // C)
            self.obs.on_prefix("hit", entry.req.id, m, self.step_idx)
        if fed.size > m:
            with self.obs.prefill_span(entry.req.id, int(fed.size) - m):
                self.prefill(row, fed[m:])
        if shared is None:
            self._register_prefix(row, fed)
        self.last_token[row] = int(prompt[-1])
        return max(int(fed.size) - m, 0)

    def _register_prefix(self, row: int, fed: np.ndarray) -> None:
        """Cache the freshly prefilled prompt's full pages as a prefix
        entry: alias them into a share-band row and index the token run.
        Skipped when the run is shorter than one page or no slot can be
        freed (every cached entry still has live sharers)."""
        idx = self._prefix_index
        if idx is None:
            return
        page = self.cfg.amc.page_size
        full = fed.size // page
        if full == 0:
            return
        slot = idx.acquire_slot(self.store, self.step_idx)
        if slot is None:
            return
        erow = self.store.entry_row(slot)
        self.store.register_entry_pages(erow, row, full, self.step_idx)
        idx.add_entry(slot, erow, fed[:full * page], self.step_idx)

    def _preempt(self, victim: int) -> None:
        """Preemption: release the victim's storage and requeue it with
        prompt := prompt + generated-so-far (greedy recompute on resume —
        work is lost, tokens are not)."""
        entry = self._slot_entry[victim]
        gen = np.asarray(self.outputs[entry.req.id], np.int32)
        # rebuild from the ORIGINAL prompt + every generated token so far:
        # entry.prompt of an already-resumed entry contains earlier stints'
        # tokens, and outputs holds them too — concatenating those would
        # duplicate them on a second preemption
        resumed = QueueEntry(
            req=entry.req,
            prompt=np.concatenate([entry.base_prompt, gen]),
            base_prompt=entry.base_prompt,
            remaining=int(self.remaining[victim]),
            resumed=True, enqueue_step=self.step_idx)
        self.scheduler.release_row(victim)
        self.active[victim] = False
        self.slot_req[victim] = None
        self._slot_entry[victim] = None
        self.obs.on_preempt(entry.req.id, self.step_idx, "capacity")
        self.scheduler.enqueue(resumed, front=True)
        self.scheduler.stats["preemptions"] += 1

    # -- fleet hand-off (serve/fleet.py drives these) ---------------------------

    def adopt_request(self, entry: QueueEntry, generated: list[int], *,
                      front: bool = False) -> None:
        """Take over a request mid-flight from another array: seed the
        output list with the tokens it already generated (the resume
        prompt in `entry.prompt` contains them, so `_start_row`'s
        setdefault keeps the seed and a later preemption rebuilds from
        base_prompt + outputs without duplication), then enqueue. The
        caller (ArrayFleet) moves each request id between at most one
        engine's books at a time."""
        rid = entry.req.id
        if rid in self.outputs or any(
                e.req.id == rid for e in self.scheduler.queue):
            raise ValueError(
                f"request id {rid} already lives on this array — the "
                f"fleet must pop it from the source array first")
        self.outputs[rid] = list(generated)
        self.obs.on_enqueue(rid, int(len(entry.prompt)), entry.remaining,
                            self.step_idx)
        self.scheduler.enqueue(entry, front=front)

    def drain_requests(self) -> list[tuple[QueueEntry, list[int]]]:
        """Array-loss drain for fleet mode: release every running row and
        empty the queue, handing back [(entry, generated-so-far)] ready
        for `adopt_request` on a surviving array. `fault_retries` budgets
        are PRESERVED, never charged — losing the array is not the
        request's fault (the cross-array extension of the single-array
        `_recover_array_loss` guarantee)."""
        drained: list[tuple[QueueEntry, list[int]]] = []
        for row in np.flatnonzero(self.active):
            entry = self._slot_entry[int(row)]
            gen = self.outputs.pop(entry.req.id, [])
            resumed = QueueEntry(
                req=entry.req,
                prompt=np.concatenate([entry.base_prompt,
                                       np.asarray(gen, np.int32)]),
                base_prompt=entry.base_prompt,
                remaining=int(self.remaining[row]),
                resumed=True, enqueue_step=self.step_idx,
                fault_retries=entry.fault_retries,
                not_before=entry.not_before)
            self.scheduler.release_row(int(row))
            self.active[row] = False
            self.slot_req[row] = None
            self._slot_entry[row] = None
            self.obs.on_handoff(entry.req.id, self.step_idx, "drained")
            drained.append((resumed, gen))
        while self.scheduler.queue:
            e = self.scheduler.queue.popleft()
            self.obs.on_handoff(e.req.id, self.step_idx, "drained")
            drained.append((e, self.outputs.pop(e.req.id, [])))
        self.obs.on_queue_depth(0)
        return drained

    # -- prefill ---------------------------------------------------------------

    def _dispatch(self, fn, batch: dict):
        """One jitted dispatch against the store's state tree, with the
        store's device tables merged in. The ONE place dispatch plumbing
        lives — every family, every store kind."""
        batch = {**self.store.device_tables(), **batch}
        with mesh_context(self.mesh):
            logits, self.store.state = fn(self.params, self.store.state,
                                          batch)
        self.dispatch_count += 1
        return logits

    def _ensure_prefill_pages(self, slot: int, first: int, last: int) -> None:
        """Chunked prefill writes positions [first, last] — every page in
        that span must exist (admission allocates them; direct `prefill`
        callers would otherwise silently scatter into the dump page)."""
        page = self.cfg.amc.page_size
        for lp in range(first // page, last // page + 1):
            # pass the page's first WRITTEN position, not its first slot:
            # a shared boundary page must copy-on-write with exactly the
            # tokens below `first` preserved
            if not self.scheduler.ensure_position(slot, max(first, lp * page),
                                                  self.step_idx):
                raise RuntimeError(
                    f"store exhausted allocating prefill page {lp} of row "
                    f"{slot}")

    def prefill(self, slot: int, tokens: np.ndarray,
                return_next: bool = False) -> Optional[int]:
        """Feed `tokens` into the slot's decode state.

        One jitted dispatch per `prefill_chunk` tokens — ceil(P / chunk)
        total, vs P decode steps for the per-token warmup loop — on
        families with a chunked prefill step; per-token elsewhere. With
        `return_next` also returns the greedy continuation of the last
        prefilled token — that argmax blocks on the async dispatches, so
        the admission hot path (`add_request`) leaves it off.
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            return None
        if self._prefill is None:           # family without chunked prefill
            return self._prefill_stepwise(slot, tokens)
        C = self.prefill_chunk
        write_mask = np.zeros(self.max_batch, bool)
        write_mask[slot] = True
        req = self.slot_req[slot]
        rid = req.id if req is not None else None
        last_logits, last_n = None, 0
        for start in range(0, tokens.size, C):
            chunk = tokens[start:start + C]
            n = chunk.size
            p = int(self.positions[slot])
            if p + n > self.max_seq:
                # genuinely no room for the real tokens
                return self._prefill_stepwise(slot, tokens[start:])
            # A padded dispatch writes C slots; near the cache end the
            # scatter start is shifted left so the write window is
            # [max_seq - C, max_seq) and the left-pad REPLAYS the last
            # `shift` already-prefilled tokens (deterministic recompute ->
            # bit-identical KV rewrite, exact attention). A short final
            # chunk therefore still costs ONE dispatch instead of falling
            # back to per-token steps.
            shift = max(0, p + C - self.max_seq)
            if shift > start:
                # the replay tokens precede this call's buffer
                return self._prefill_stepwise(slot, tokens[start:])
            tok = np.zeros((self.max_batch, C), np.int32)
            tok[slot, :shift + n] = tokens[start - shift:start + n]
            positions = self.positions.copy()
            positions[slot] = p - shift
            self._ensure_prefill_pages(slot, p - shift, p + n - 1)
            with self.obs.chunk_span(rid, n):
                logits = self._dispatch(
                    self._prefill,
                    {"tokens": to_device(tok),
                     "positions": to_device(positions),
                     "write_mask": to_device(write_mask)})
            self.prefill_dispatch_count += 1
            self._account_dispatch(np.array([slot]), n,
                                   np.array([p + n]), np.array([p]))
            self.energy_ledger.note_tokens(n)
            self.positions[slot] += n
            self.store.note_token_writes(
                np.full(n + shift, slot), np.arange(p - shift, p + n),
                self.step_idx)
            last_logits, last_n = logits, shift + n
        if not return_next:
            return None
        return int(jnp.argmax(last_logits[slot, last_n - 1]))

    def _prefill_stepwise(self, slot: int, tokens: np.ndarray):
        last = None
        for t in tokens:
            last = self._step_slot(slot, int(t))
            self.prefill_dispatch_count += 1
        return last

    def _step_slot(self, slot: int, token: int) -> int:
        # defensive: direct prefill() callers may outrun the storage
        # reserved at admission
        if not self.scheduler.ensure_position(
                slot, int(self.positions[slot]), self.step_idx):
            raise RuntimeError("store exhausted during stepwise prefill")
        tokens = np.zeros((self.max_batch, 1), np.int32)
        tokens[slot, 0] = token
        mask = np.zeros(self.max_batch, bool)
        mask[slot] = True
        batch = {"tokens": to_device(tokens),
                 "positions": to_device(self.positions),
                 "write_mask": to_device(mask)}
        logits = self._dispatch(self._decode, batch)
        self.store.note_token_writes(np.array([slot]),
                                     np.array([self.positions[slot]]),
                                     self.step_idx)
        self._account_dispatch(np.array([slot]), 1,
                               np.array([self.positions[slot] + 1]),
                               np.array([self.positions[slot]]))
        self.energy_ledger.note_tokens(1)
        self.positions[slot] += 1
        return int(jnp.argmax(logits[slot, -1]))

    # -- decode ----------------------------------------------------------------

    def _ensure_decode_capacity(self) -> None:
        """Every active row must own the storage its next token lands in;
        under pressure the store augments cold storage, and when even that
        fails the youngest-admitted row is preempted (requeued, not
        dropped)."""
        for row in np.flatnonzero(self.active):
            if not self.active[row]:
                continue    # preempted by an earlier row's allocation
            pos = int(self.positions[row])
            while not self.scheduler.ensure_position(row, pos,
                                                     self.step_idx):
                victim = self.scheduler.preemption_victim(row, self.active)
                if victim is None:
                    raise RuntimeError(
                        "state store cannot hold one growing sequence — "
                        "budget_bytes too small for max_seq")
                self._preempt(victim)

    def step_all(self, last_tokens: Optional[dict[int, int]] = None) -> dict:
        """One scheduler pass + one batched decode step for every active
        row: refresh expired augmented storage, admit queued requests into
        free rows, grow/augment/preempt for capacity, then dispatch.

        `last_tokens` optionally overrides the tracked per-slot feed
        token (kept for API compatibility; `generate` no longer needs
        it). Returns {row: next_token} for rows still running.
        """
        if last_tokens:
            for s, t in last_tokens.items():
                self.last_token[s] = t
        if self._fault_model is not None or self._forced_array_loss:
            if not self.supervisor.run_step(self._array_health_check):
                # whole-array loss: every running row was drained and
                # requeued by _recover_array_loss; the step clock still
                # ticks (retry backoff is measured in steps)
                self.step_idx += 1
                return {}
        t0 = time.perf_counter()
        with self.obs.step_span(self.step_idx,
                                "spec" if self._spec else "decode") as span:
            self._admit()
            if self._fault_model is not None:
                # inject -> detect -> heal BEFORE refresh and dispatch, so
                # corrupted storage is never read, refreshed or promoted
                with self.obs.fault_span(self.step_idx):
                    self._fault_pass()
            with self.obs.phase_span("refresh"):
                n_refreshed = self.scheduler.refresh_pass(self.step_idx)
                self.obs.on_refresh_pass(n_refreshed, self.step_idx)
                self._sync_refresh_events()
            if self._spec and self.active.any():
                out, rows = self._step_all_spec()
            else:
                out, rows = self._step_all_decode()
            # rows: active rows in the dispatch; admitted: requests
            # admitted since the previous step (add_request's included)
            span.set_metadata(rows=rows, admitted=self._admitted_since_step)
        self._admitted_since_step = 0
        dt = time.perf_counter() - t0
        self._note_step_time(dt)
        self.obs.on_step_done(self.step_idx, dt)
        if self.obs.wants_sample(self.step_idx):
            self.obs.sample(self.step_idx, self._obs_sample_payload())
        return out

    def _step_all_decode(self) -> tuple[dict, int]:
        """The non-speculative decode round: one batched dispatch serves
        every active row (the body `step_all` wraps in scheduling,
        refresh, fault and observability passes). Returns the step's
        {row: next_token} and the number of rows it served."""
        with self.obs.phase_span("capacity"):
            self._ensure_decode_capacity()
        with self.obs.phase_span("dispatch"):
            tokens = np.where(self.active, self.last_token, 0
                              ).astype(np.int32)[:, None]
            batch = {"tokens": to_device(tokens),
                     "positions": to_device(self.positions),
                     "write_mask": to_device(self.active)}
            logits = self._dispatch(self._decode, batch)
            rows = np.flatnonzero(self.active)
            self._account_dispatch(rows, 1, self.positions[rows] + 1,
                                   self.positions[rows])
            self.energy_ledger.note_tokens(rows.size)
        with self.obs.phase_span("readback"):
            arg = np.asarray(jnp.argmax(logits[:, -1], axis=-1)
                             ).astype(np.int32)
        with self.obs.phase_span("bookkeeping"):
            # vectorized slot bookkeeping: no per-slot Python for the
            # numeric state, only the per-request output append below
            act = self.active.copy()
            if act.any():
                self.store.note_token_writes(rows, self.positions[rows],
                                             self.step_idx)
            self.positions[act] += 1
            self.remaining[act] -= 1
            self.last_token = np.where(act, arg, self.last_token)
            done = act & ((self.remaining <= 0)
                          | (self.positions >= self.max_seq - 1))
            self.active &= ~done
            for s in rows:
                rid = self.slot_req[s].id
                self.outputs[rid].append(int(arg[s]))
                self.obs.on_tokens(rid, 1, self.step_idx)
            for s in np.flatnonzero(done):
                rid = self.slot_req[s].id
                self.slot_req[s] = None      # release row (cont. batching)
                self._slot_entry[s] = None
                self.scheduler.release_row(int(s))
                self.obs.on_complete(rid, self.step_idx)
            self.step_idx += 1
            out = {int(s): int(arg[s]) for s in np.flatnonzero(act & ~done)}
        return out, int(rows.size)

    def _step_all_spec(self) -> tuple[dict, int]:
        """One self-speculative round for every active row: spec_k - 1
        cheap draft dispatches propose a spec_k-token window out of the
        dynamic-plane read, ONE full-path verify dispatch scores and
        commits it, and the longest greedily-matching prefix is emitted.
        Greedy accept keeps the emitted stream token-identical to
        step-by-step decode; rejected draft storage is rolled back (page
        retraction on paged stores, snapshot restore on slab stores).
        Returns the round's {row: last emitted token} and its row count."""
        W = self.spec_k
        B = self.max_batch
        # per-row window cap >= 1: stepwise decode retires a row once its
        # position reaches max_seq - 1, so no window slot may write past
        # max_seq - 2
        cap = np.ones(B, np.int32)
        rows = np.flatnonzero(self.active)
        cap[rows] = np.clip(self.max_seq - 1 - self.positions[rows], 1, W)
        # every window slot needs storage BEFORE the draft writes it; the
        # same augment-then-preempt ladder as _ensure_decode_capacity
        with self.obs.phase_span("capacity"):
            for row in rows:
                if not self.active[row]:
                    continue    # preempted by an earlier row's allocation
                while not self.scheduler.ensure_window(
                        int(row), int(self.positions[row]), int(cap[row]),
                        self.step_idx):
                    victim = self.scheduler.preemption_victim(int(row),
                                                              self.active)
                    if victim is None:
                        raise RuntimeError(
                            "state store cannot hold one growing sequence"
                            " — budget_bytes too small for max_seq")
                    self._preempt(victim)
        rows = np.flatnonzero(self.active)
        wmask2d = self.active[:, None] & (np.arange(W)[None, :]
                                          < cap[:, None])
        # -- draft: W - 1 cheap single-token steps propose the window tail
        toks = np.zeros((B, W), np.int32)
        toks[:, 0] = np.where(self.active, self.last_token, 0)
        if self.store.kind == "slab":
            self.store.speculative_snapshot()
        with self.obs.phase_span("spec_draft", k=W - 1):
            for i in range(W - 1):
                # clamp keeps INACTIVE rows' stale positions inside the
                # table; active rows never exceed max_seq - 2 by the cap
                # above
                pos_i = np.minimum(self.positions + i, self.max_seq - 1)
                lg = self._dispatch(
                    self._draft_decode,
                    {"tokens": to_device(toks[:, i:i + 1]),
                     "positions": to_device(pos_i),
                     "write_mask": to_device(wmask2d[:, i])})
                self.energy_ledger.add(
                    imc_energy.decode_matmul_events(self._draft_cfg,
                                                    int(rows.size)),
                    "draft")
                self._spec_stats["draft_dispatches"] += 1
                with self.obs.phase_span("readback"):
                    toks[:, i + 1] = np.asarray(
                        jnp.argmax(lg[:, -1], axis=-1)).astype(np.int32)
        if self.store.kind == "slab":
            # the verify scan replays the window from the pre-draft state
            self.store.speculative_restore()
        # -- verify: ONE full-quality dispatch over the whole window
        with self.obs.phase_span("spec_verify", k=W):
            logits = self._dispatch(
                self._verify,
                {"tokens": to_device(toks),
                 "positions": to_device(self.positions),
                 "write_mask": to_device(wmask2d)})
            self._spec_stats["verify_dispatches"] += 1
            self._spec_stats["spec_rounds"] += 1
            self._account_dispatch(rows, W,
                                   self.positions[rows] + cap[rows],
                                   self.positions[rows])
        # -- host accept: longest prefix where the verifier agrees with
        # the draft (same formula the verify step committed KV with)
        with self.obs.phase_span("readback"):
            v = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
        with self.obs.phase_span("bookkeeping"):
            out = self._spec_accept(rows, toks, v, cap)
        return out, int(rows.size)

    def _spec_accept(self, rows: np.ndarray, toks: np.ndarray,
                     v: np.ndarray, cap: np.ndarray) -> dict:
        """Host accept of one speculative round: emit each row's longest
        prefix on which the verifier's tokens `v` agree with the draft
        `toks`, commit or roll back its storage, retire finished rows."""
        B = self.max_batch
        mism = np.concatenate([toks[:, 1:] != v[:, :-1],
                               np.ones((B, 1), bool)], axis=1)
        n_acc = np.minimum(mism.argmax(axis=1).astype(np.int32) + 1, cap)
        act = self.active.copy()
        n_emit = np.where(act, np.minimum(n_acc, self.remaining),
                          0).astype(np.int32)
        rw, ps = [], []
        total = 0
        for s in rows:
            na = int(n_emit[s])
            rid = self.slot_req[s].id
            self.outputs[rid].extend(int(t) for t in v[s, :na])
            self.obs.on_tokens(rid, na, self.step_idx)
            total += na
            nc = int(n_acc[s])     # committed (may exceed the emit budget)
            rw.extend([int(s)] * nc)
            ps.extend(range(int(self.positions[s]),
                            int(self.positions[s]) + nc))
        if rw:
            self.store.note_token_writes(np.array(rw), np.array(ps),
                                         self.step_idx)
        self.energy_ledger.note_tokens(total)
        self._spec_stats["accepted_tokens"] += total
        self.obs.on_spec_round(total, int(rows.size), self.step_idx)
        # roll back pages that held only rejected draft tokens (slab
        # stores already rolled back wholesale via the snapshot)
        if rows.size:
            self.store.retract_token_writes(
                rows, self.positions[rows] + n_acc[rows])
        self.positions[act] += n_emit[act]
        self.remaining[act] -= n_emit[act]
        last = v[np.arange(B), np.maximum(n_emit - 1, 0)]
        self.last_token = np.where(act, last, self.last_token)
        done = act & ((self.remaining <= 0)
                      | (self.positions >= self.max_seq - 1))
        self.active &= ~done
        for s in np.flatnonzero(done):
            rid = self.slot_req[s].id
            self.slot_req[s] = None
            self._slot_entry[s] = None
            self.scheduler.release_row(int(s))
            self.obs.on_complete(rid, self.step_idx)
        self.step_idx += 1
        return {int(s): int(v[s, n_emit[s] - 1])
                for s in np.flatnonzero(act & ~done)}

    # -- retention faults: inject / detect / heal ------------------------------

    def _note_step_time(self, dt: float) -> None:
        """Feed the per-step wall time to the straggler monitor. Always
        recorded (stats()["step_times"] surfaces min/mean/max for every
        run), mitigations only counted — never acted on."""
        if self.straggler.record(self.step_idx, dt):
            self._fault_stats["straggler_mitigations"] += 1

    def prefix_probe(self, prompt: np.ndarray) -> int:
        """Tokens of `prompt` this engine's prefix cache already holds
        (0 without one) — pure; the affinity placement policy's
        prefix-locality signal."""
        if self._prefix_index is None:
            return 0
        fed = np.asarray(prompt, np.int32).reshape(-1)[:-1]
        if fed.size < self.cfg.amc.page_size:
            return 0
        return self._prefix_index.probe(fed)

    def inject_array_loss(self) -> None:
        """Force a whole-array failure event at the next `step_all` (the
        chaos hook `examples/elastic_restart.py` and the tests drive):
        the supervisor drains every running row back to the queue and the
        engine resumes from recompute — work lost, tokens never."""
        self._forced_array_loss = True

    def _array_health_check(self) -> None:
        if self._forced_array_loss:
            self._forced_array_loss = False
            raise SimulatedFailure(
                f"injected array loss at step {self.step_idx}")
        if self._fault_model is not None \
                and self._fault_model.array_loss(self.step_idx):
            raise SimulatedFailure(
                f"sampled array loss at step {self.step_idx}")

    def _recover_array_loss(self) -> int:
        """Supervisor restore hook: the array's dynamic contents are gone,
        so every running row is preempted (released + requeued with
        prompt := prompt + generated-so-far) — the drain-and-requeue path.
        Fault-retry budgets are NOT charged: an array loss is not the
        request's fault, and charging it would fail innocent requests."""
        rows = np.flatnonzero(self.active)
        self.obs.on_fault("array_loss", f"rows={rows.size}", self.step_idx)
        for row in rows:
            self._preempt(int(row))
            self._fault_stats["array_loss_requeues"] += 1
        if self._prefix_index is not None:
            # the arenas behind every cached prefix are gone with the
            # array — the index must not serve stale physical pages
            self._prefix_index.invalidate(self.store)
        self._fault_stats["array_losses"] += 1
        return int(rows.size)

    def _fault_pass(self) -> None:
        """One inject -> detect -> heal cycle. Detected-corrupt units heal
        by scrub-from-master where a master exists (static prefix bands),
        else by recompute-via-preemption with bounded exponential-backoff
        retry; recovery traffic is billed to the energy ledger's
        "recovery" group like any other maintenance."""
        bad = self.scheduler.fault_pass(self.step_idx)
        for key in bad:
            self.obs.on_fault("detect", str(key), self.step_idx)
            self.energy_ledger.add(
                imc_energy.refresh_events(self.store.fault_unit_bytes(key)),
                "recovery")
            if self.store.scrub_from_master(key):
                self._fault_stats["recovered_scrub"] += 1
                self.obs.on_fault("heal_scrub", str(key), self.step_idx)
                continue
            row = self.store.fault_row(key)
            if row is None or not self.active[row]:
                continue    # second corrupt unit of an already-healed row
            self._heal_row_recompute(int(row))

    def _heal_row_recompute(self, row: int) -> None:
        """Recompute-via-preemption: no master exists for decode-band
        storage, so the row's state is rebuilt from its token history
        (deterministic greedy recompute — token-identical on resume).
        Each retry backs off exponentially; a request that exceeds
        cfg.amc.max_retries is failed, never silently served."""
        entry = self._slot_entry[row]
        retries = entry.fault_retries + 1
        if retries > self.cfg.amc.max_retries:
            self._fail_row(row)
            return
        gen = np.asarray(self.outputs[entry.req.id], np.int32)
        resumed = QueueEntry(
            req=entry.req,
            prompt=np.concatenate([entry.base_prompt, gen]),
            base_prompt=entry.base_prompt,
            remaining=int(self.remaining[row]),
            resumed=True, enqueue_step=self.step_idx,
            fault_retries=retries,
            not_before=self.step_idx + 2 ** (retries - 1))
        self.scheduler.release_row(row)
        self.active[row] = False
        self.slot_req[row] = None
        self._slot_entry[row] = None
        self.obs.on_fault("heal_recompute", f"row{row}", self.step_idx)
        self.obs.on_preempt(entry.req.id, self.step_idx, "fault_recompute")
        self.scheduler.enqueue(resumed, front=True)
        self._fault_stats["recovered_recompute"] += 1
        self._fault_stats["retried"] += 1

    def _fail_row(self, row: int) -> None:
        """Retry budget exhausted: surface the request in `failed` with
        whatever it generated — an explicit uncorrectable outcome, never
        a silently corrupt completion."""
        entry = self._slot_entry[row]
        self.failed[entry.req.id] = self.outputs.pop(entry.req.id, [])
        self.scheduler.release_row(row)
        self.active[row] = False
        self.slot_req[row] = None
        self._slot_entry[row] = None
        self.obs.on_fault("uncorrectable", f"req{entry.req.id}",
                          self.step_idx)
        self.obs.on_failed(entry.req.id, self.step_idx)
        self._fault_stats["uncorrectable"] += 1

    # -- stats -----------------------------------------------------------------

    def stats(self) -> dict:
        """Augmented-storage accounting (the paper's capacity headline).

        Logical bytes = what the dense bf16 representation would occupy;
        physical bytes = what the store's staged planes actually occupy
        (paged pools exclude the write-dump lines; `describe()` has the
        raw numbers). Store/scheduler/refresh counters ride along under
        "pool" and "scheduler".
        """
        a = self.cfg.amc
        weight_phys = sum(x.nbytes for x in jax.tree.leaves(self.params))
        cache_phys = self.store.physical_bytes()
        # families augment_params doesn't cover keep dense weights: report
        # the physical reality, not the requested mode
        weight_mode = (a.weight_mode if augment.is_augmented(self.params)
                       else "normal")
        wmode = amc.WEIGHT_MODES[weight_mode]
        out = {
            "kv_mode": a.kv_mode,
            "weight_mode": weight_mode,
            "weight_bits_per_value": amc.mode_bits_per_value(
                wmode, a.ternary_fmt),
            "kv_bits_per_value": amc.KV_BITS_PER_VALUE[a.kv_mode],
            "weight_bytes_logical": self._logical_weight_bytes,
            "weight_bytes_physical": weight_phys,
            "weight_capacity_factor": self._logical_weight_bytes
                                      / weight_phys,
            "cache_bytes_logical": self._logical_cache_bytes,
            "cache_bytes_physical": cache_phys,
            "cache_capacity_factor": self._logical_cache_bytes / cache_phys,
            "total_bytes_logical": (self._logical_weight_bytes
                                    + self._logical_cache_bytes),
            "total_bytes_physical": weight_phys + cache_phys,
            "capacity_factor": (self._logical_weight_bytes
                                + self._logical_cache_bytes)
                               / (weight_phys + cache_phys),
            "dispatches": self.dispatch_count,
        }
        # array-level event/energy accounting (imc/energy.py): weight-side
        # events follow matmul_impl (IMC wordline/bitline/ADC vs fetch),
        # state reads are split by page/slab mode — Normal storage costs
        # 6T read events, Augmented the 8T dynamic-read events (the
        # paper's Tables III/IV structure)
        E = imc_energy.EVENT_ENERGY_FJ
        self._sync_refresh_events()
        imc = self.energy_ledger.describe()
        imc["matmul_impl"] = a.matmul_impl
        imc["imc_abits"] = a.imc_abits
        imc["kv_read_fj_per_value_normal_mode"] = 16 * E["read_6t"]
        imc["kv_read_fj_per_value_augmented_mode"] = (
            self.store.aug_bits * E["read_8t_dynamic"])
        imc["refresh_energy_fj"] = imc["groups"].get(
            "refresh", {}).get("energy_fj", 0.0)
        out["imc"] = imc
        sp = dict(self._spec_stats)
        nd = sp["draft_dispatches"] + sp["verify_dispatches"]
        sp.update({
            "enabled": self._spec,
            "spec_k": self.spec_k,
            "spec_draft_impl": a.spec_draft_impl,
            # the speedup headline: useful tokens per device dispatch
            # across the whole draft + verify round (stepwise decode is
            # 1.0 by construction)
            "accepted_tokens_per_dispatch":
                sp["accepted_tokens"] / nd if nd else 0.0,
            "accepted_tokens_per_round":
                sp["accepted_tokens"] / sp["spec_rounds"]
                if sp["spec_rounds"] else 0.0,
        })
        out["spec"] = sp
        # retention-fault accounting: injection/detection counters from
        # the store(s), recovery outcomes from the engine, and the
        # zero-silent-corruption property — with integrity on, every
        # injected fault is either detected or masked (its storage was
        # released before any read); nothing corrupt is ever served
        fc = self.store.fault_counters()
        pending = self.store.faults_pending()
        injected = fc["faults_injected"]
        served_clean = injected == (fc["faults_detected"]
                                    + fc["faults_masked"])
        out["faults"] = {
            "enabled": self._fault_model is not None,
            "fault_rate": a.fault_rate,
            "fault_seed": a.fault_seed,
            "array_loss_rate": a.array_loss_rate,
            "fault_temp_c": a.fault_temp_c,
            "integrity_check": a.integrity_check,
            "max_retries": a.max_retries,
            "fault_pin_threshold": a.fault_pin_threshold,
            **fc,
            **self._fault_stats,
            "faults_pending": pending,
            "recovered": (self._fault_stats["recovered_scrub"]
                          + self._fault_stats["recovered_recompute"]),
            "failed_requests": len(self.failed),
            "supervisor_restarts": self.supervisor.restarts,
            "recovery_energy_fj": imc["groups"].get(
                "recovery", {}).get("energy_fj", 0.0),
            "zero_silent_corruption": bool(
                injected == 0 or (a.integrity_check and served_clean
                                  and pending == 0)),
        }
        pool = self.store.describe()
        out["pool"] = pool
        out["prefix"] = {
            "enabled": self._prefix_index is not None,
            "prefill_dispatches": self.prefill_dispatch_count,
            "dispatches_saved": self._prefix_saved,
            "cow_events": pool.get("cow_events", 0),
            "cow_bytes": pool.get("cow_bytes", 0),
            "demotions": pool.get("prefix_demotions", 0),
            "evictions": pool.get("prefix_evictions", 0),
            "pages_shared": pool.get("pages_shared", 0),
            "bytes_shared": pool.get("bytes_shared", 0),
        }
        if self._prefix_index is not None:
            out["prefix"].update(self._prefix_index.describe())
        out["scheduler"] = self.scheduler.describe()
        for k in ("refreshes", "refresh_bytes", "augment_events",
                  "promote_events", "maintenance_dispatches"):
            out[k] = pool[k]
        out["preemptions"] = self.scheduler.stats["preemptions"]
        # per-step wall times (straggler monitor feed — recorded on every
        # run, fault model or not) and the observability planes; both
        # describes are pure snapshots, so stats() stays idempotent
        out["step_times"] = self.straggler.describe()
        out["obs"] = self.obs.describe()
        return out

    # -- observability ----------------------------------------------------------

    def _obs_sample_payload(self) -> dict:
        """One time-series tick of the store/scheduler/energy state (the
        mode-mix, occupancy, refresh-debt and energy-group timelines)."""
        mode_n, mode_a = self.store.mode_mix()
        payload = {
            "pool_occupancy": self.store.live_bytes
                              / max(self.store.budget_bytes, 1),
            "mode_normal": mode_n,
            "mode_augmented": mode_a,
            "queue_depth": len(self.scheduler.queue),
            "running": int(self.active.sum()),
            "refresh_debt": self.store.max_augmented_age(self.step_idx),
        }
        E = imc_energy.EVENT_ENERGY_FJ
        for (group, cls), n in self.energy_ledger.counts.items():
            k = "energy_" + group + "_fj"
            payload[k] = payload.get(k, 0.0) + E[cls] * n
        return payload

    def export_trace(self, path: str) -> dict:
        """Write the Chrome trace-event JSON (perfetto-loadable)."""
        return self.obs.export_trace(path)

    def export_metrics(self, path: str) -> str:
        """Write the Prometheus text exposition of the metrics plane."""
        return self.obs.export_metrics(path)

    def generate(self, requests: list[Request]) -> dict[int, list[int]]:
        """Run all requests to completion: enqueue everything, then step
        until the queue AND the running batch drain. Zero drops — the
        scheduler admits from the queue between decode steps."""
        for req in requests:
            self.add_request(req)
        while self.active.any() or self._queue:
            if not self.active.any():
                self._admit()
                if not self.active.any():
                    if self.scheduler.backlog_ready(self.step_idx):
                        raise RuntimeError(
                            "queued requests but nothing admittable — "
                            "store misconfigured (budget below one "
                            "sequence?)")
                    # every queued entry is in fault-retry backoff: tick
                    # the step clock until one becomes eligible
                    self.step_idx += 1
                    continue
            self.step_all()
        return self.outputs
