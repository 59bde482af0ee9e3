"""Array-fleet serving: placement policies (pure), device partitioning,
pinned fleet-vs-single token identity across families, migration under
pressure, array-loss drain that never charges retry budgets (the
cross-array PR-7 guarantee), byte-budget/no-loss placement invariants
(hypothesis), and per-array trace lanes merging into one valid trace."""
import dataclasses
import itertools
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_local_mesh
from repro.obs.export import validate_chrome_trace
from repro.serve import (ArrayFleet, ArrayView, Request, ServeEngine,
                         make_policy, make_serving, partition_devices)
from repro.serve.placement import make_array_meshes
from repro.serve.state_store import make_store

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _amc(cfg, **kw):
    return dataclasses.replace(cfg, amc=dataclasses.replace(cfg.amc, **kw))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, size=(n,)).astype(np.int32)


def _reqs(cfg, n, plen, max_new, seed=0, id0=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=_prompt(rng, plen, cfg.vocab),
                    max_new_tokens=max_new, id=id0 + i) for i in range(n)]


# ---------------------------------------------------------------------------
# placement policies: pure ArrayView logic, no devices
# ---------------------------------------------------------------------------

def _view(aid, *, alive=True, running=0, queued=0, free_rows=4,
          live_bytes=0, budget_bytes=1000, admit=True):
    return ArrayView(aid=aid, alive=alive, running=running, queued=queued,
                     free_rows=free_rows, live_bytes=live_bytes,
                     budget_bytes=budget_bytes,
                     admit_probe=(lambda n: admit))


def test_least_loaded_order_and_tiebreaks():
    p = make_policy("least-loaded")
    prompt = np.arange(4, dtype=np.int32)
    # fewest running+queued wins
    views = [_view(0, running=2), _view(1, running=1), _view(2, queued=3)]
    assert p.place(prompt, views) == 1
    # tie on load -> more headroom wins
    views = [_view(0, live_bytes=800), _view(1, live_bytes=100)]
    assert p.place(prompt, views) == 1
    # full tie -> lowest aid (deterministic replays)
    assert p.place(prompt, [_view(0), _view(1)]) == 0
    # dead arrays are never placement targets
    views = [_view(0, alive=False), _view(1, running=3)]
    assert p.place(prompt, views) == 1


def test_budget_headroom_prefers_free_bytes():
    p = make_policy("budget-headroom")
    prompt = np.arange(4, dtype=np.int32)
    views = [_view(0, live_bytes=100, running=0),
             _view(1, live_bytes=0, running=5)]
    # headroom dominates load for this policy
    assert p.place(prompt, views) == 1


def test_affinity_stable_and_falls_back():
    p = make_policy("affinity")
    views = [_view(0), _view(1), _view(2)]
    shared = [7, 3, 7, 3, 7, 3, 7, 3]            # same 8-token prefix...
    a = np.array(shared + [1, 2], np.int32)
    b = np.array(shared + [9, 9, 9], np.int32)   # ...different tails
    got = p.place(a, views)
    # prefix-stable: same prefix -> same array, every time
    assert got == p.place(a, views) == p.place(b, views)
    # preferred array saturated -> least-loaded fallback, not queue-behind
    views[got] = _view(got, free_rows=0, admit=False)
    fallback = p.place(a, views)
    assert fallback != got


def test_make_policy_unknown_raises():
    with pytest.raises(ValueError, match="unknown placement policy"):
        make_policy("round-robin")


def test_place_raises_when_no_survivors():
    p = make_policy("least-loaded")
    with pytest.raises(RuntimeError, match="no surviving arrays"):
        p.place(np.arange(2, dtype=np.int32),
                [_view(0, alive=False), _view(1, alive=False)])


def test_partition_devices_groups_and_round_robin():
    devs = ["d0", "d1", "d2", "d3"]
    # contiguous equal groups when devices >= arrays
    assert partition_devices(devs, 2) == [["d0", "d1"], ["d2", "d3"]]
    assert partition_devices(devs, 4) == [["d0"], ["d1"], ["d2"], ["d3"]]
    # remainder devices stay idle (equal per-array compute)
    assert partition_devices(devs, 3) == [["d0"], ["d1"], ["d2"]]
    # fewer devices than arrays: round-robin sharing (over-host case)
    assert partition_devices(["d0"], 3) == [["d0"], ["d0"], ["d0"]]
    assert partition_devices(["d0", "d1"], 4) == \
        [["d0"], ["d1"], ["d0"], ["d1"]]
    with pytest.raises(ValueError):
        partition_devices(devs, 0)


def test_make_array_meshes_share_one_cpu_device():
    meshes = make_array_meshes(3)          # 1 CPU device in the test env
    assert len(meshes) == 3
    for m in meshes:
        assert dict(m.shape) == {"data": 1, "model": 1}


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def test_make_serving_switches_on_num_arrays():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    eng = make_serving(cfg, make_local_mesh(), num_arrays=1, max_batch=2,
                       max_seq=32)
    assert isinstance(eng, ServeEngine)
    fleet = make_serving(cfg, make_local_mesh(), num_arrays=2, max_batch=2,
                         max_seq=32)
    assert isinstance(fleet, ArrayFleet) and fleet.num_arrays == 2
    # cfg knob alone is enough — no explicit argument needed
    fleet2 = make_serving(_amc(cfg, num_arrays=2), max_batch=2, max_seq=32)
    assert isinstance(fleet2, ArrayFleet)


# ---------------------------------------------------------------------------
# pinned token identity: fleet(2) == single array, per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen1.5-0.5b",       # dense paged KV
                                  "qwen3-moe-30b-a3b",  # moe
                                  "mamba2-130m"])       # ssm slab store
def test_fleet_token_identity_vs_single_array(arch):
    """Golden: the fleet decodes the SAME weights through the same
    kernels and per-request decode is batch-composition invariant, so
    sharding requests across arrays must not change one token."""
    cfg = get_arch(arch).reduced()
    reqs = _reqs(cfg, 4, 6, 5, seed=3)
    single = ServeEngine(cfg, make_local_mesh(), max_batch=4, max_seq=32,
                         seed=1)
    want = single.generate([dataclasses.replace(r) for r in reqs])
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1)
    got = fleet.generate(reqs)
    assert got == want
    assert not fleet.failed
    st_ = fleet.stats()["fleet"]
    # both arrays actually served (least-loaded spreads 4 reqs 2/2)
    assert st_["placements_per_array"] == [2, 2]
    assert st_["peak_concurrency"] >= 3


# ---------------------------------------------------------------------------
# placement: with one device per array, each array's weights and store
# live on its own device, and the fleet still matches one array
# ---------------------------------------------------------------------------

_FOUR_DEVICE_FLEET = textwrap.dedent("""
    import json

    import jax
    from repro.configs import get_arch
    from repro.launch.mesh import make_local_mesh
    from repro.serve import ArrayFleet, Request, make_serving
    import numpy as np

    devs = jax.devices()
    assert len(devs) == 4, devs

    def held(tree):
        return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}

    cfg = get_arch("granite-3-2b").reduced()
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=int(n))
                    .astype(np.int32), max_new_tokens=4, id=i)
            for i, n in enumerate(rng.integers(4, 12, size=6))]
    kw = dict(max_batch=2, max_seq=32, seed=1)
    single = make_serving(cfg, make_local_mesh(devs[2:3]), **kw)
    assert held(single.params) == held(single.store.state) == {devs[2]}
    want = single.generate([Request(prompt=r.prompt, max_new_tokens=4,
                                    id=r.id) for r in reqs])
    fleet = make_serving(cfg, make_local_mesh(devs), num_arrays=4, **kw)
    assert isinstance(fleet, ArrayFleet)
    owned = []
    for aid, eng in enumerate(fleet.engines):
        own = set(eng.mesh.devices.flat)   # the mesh may reorder devices
        assert len(own) == 1, own
        assert held(eng.params) == own, (aid, held(eng.params))
        assert held(eng.store.state) == own, aid
        owned += own
    assert sorted(owned, key=str) == sorted(devs, key=str), owned
    got = fleet.generate(reqs)
    assert got == want, (got, want)
    print(json.dumps(fleet.stats()["fleet"]["placements_per_array"]))
""")


def test_fleet_arrays_hold_their_own_devices():
    """Four host devices in a child process (the device count is fixed
    when JAX starts, and this test process keeps one): an engine built
    on one device holds its weights and store there, and a fleet of four
    arrays puts each array wholly on its own device, token-identical to
    one array."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(repo, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_FLEET],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    # least-loaded spreads the six requests over all four arrays
    per_array = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(per_array) == 4 and min(per_array) >= 1, per_array


# ---------------------------------------------------------------------------
# migration: queued work moves off a pressured array and completes
# ---------------------------------------------------------------------------

def test_rebalance_migrates_queued_work_to_idle_array():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1)
    reqs = _reqs(cfg, 6, 6, 4, seed=5)
    # bypass the policy: pile everything onto array 0 so its queue backs
    # up behind 2 rows while array 1 sits idle
    for r in reqs:
        fleet.engines[0].add_request(r)
    for _ in range(200):
        if not fleet.has_work:
            break
        fleet.step_all()
    assert not fleet.has_work
    st_ = fleet.stats()["fleet"]
    assert st_["migrations"] > 0
    assert fleet.outputs.keys() == {r.id for r in reqs}
    assert all(len(v) == 4 for v in fleet.outputs.values())
    assert not fleet.failed


# ---------------------------------------------------------------------------
# array loss: drain onto survivors, retry budgets never charged
# ---------------------------------------------------------------------------

def test_array_loss_drains_onto_survivors_without_charging_retries():
    """Satellite guarantee: losing an array is not the request's fault.
    With max_retries=0 ANY charge against the retry budget fails the
    request instantly — so every request completing proves the drain
    path leaves `fault_retries` untouched across arrays."""
    cfg = get_arch("qwen1.5-0.5b").reduced()
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1,
                       max_retries=0)
    reqs = _reqs(cfg, 6, 8, 8, seed=2)
    for r in reqs:
        fleet.add_request(r)
    for _ in range(3):
        fleet.step_all()
    lost = fleet.inject_array_loss()       # busiest array
    for _ in range(400):
        if not fleet.has_work:
            break
        fleet.step_all()
    assert not fleet.has_work
    st_ = fleet.stats()["fleet"]
    assert st_["array_losses"] == 1 and st_["dead"] == [lost]
    assert st_["drain_requeues"] > 0
    # zero-retry budget intact -> nothing failed, everything finished
    assert not fleet.failed
    assert fleet.outputs.keys() == {r.id for r in reqs}
    assert all(len(v) == 8 for v in fleet.outputs.values())
    # survivors carried every later placement
    survivor = ({0, 1} - {lost}).pop()
    assert fleet.engines[lost].store.live_bytes == 0
    assert not fleet.engines[lost].active.any()
    assert fleet.engines[survivor].step_idx > 0


def test_losing_every_array_raises():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1)
    for r in _reqs(cfg, 2, 4, 4, seed=9):
        fleet.add_request(r)
    fleet.inject_array_loss(0)
    fleet.step_all()                       # drained onto array 1
    fleet.inject_array_loss(1)
    with pytest.raises(RuntimeError, match="no\\s+survivors"):
        fleet.step_all()                   # nothing left to drain onto


# ---------------------------------------------------------------------------
# engine hand-off primitives
# ---------------------------------------------------------------------------

def test_drain_requests_rebuilds_prompts_and_keeps_retry_budget():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, make_local_mesh(), max_batch=2, max_seq=32,
                      seed=1)
    reqs = _reqs(cfg, 3, 5, 6, seed=4)
    for r in reqs:
        eng.add_request(r)
    for _ in range(2):
        eng.step_all()
    drained = eng.drain_requests()
    assert len(drained) == 3
    assert not eng.active.any() and not eng.scheduler.queue
    assert eng.store.live_bytes == 0
    # 2 rows were running (resumed on drain); the 3rd never left the queue
    assert sum(e.resumed for e, _ in drained) == 2
    by_id = {e.req.id: (e, gen) for e, gen in drained}
    for r in reqs:
        entry, gen = by_id[r.id]
        assert entry.fault_retries == 0          # budget never charged
        np.testing.assert_array_equal(entry.base_prompt, r.prompt)
        np.testing.assert_array_equal(
            entry.prompt, np.concatenate([r.prompt,
                                          np.asarray(gen, np.int32)]))
        assert entry.remaining == r.max_new_tokens - len(gen)


def test_adopt_request_rejects_duplicate_ids():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, make_local_mesh(), max_batch=2, max_seq=32,
                      seed=1)
    eng.add_request(_reqs(cfg, 1, 5, 4, seed=6)[0])
    drained = eng.drain_requests()
    entry, gen = drained[0]
    eng.adopt_request(entry, gen)
    with pytest.raises(ValueError, match="already lives on this array"):
        eng.adopt_request(entry, gen)


# ---------------------------------------------------------------------------
# placement invariants: budgets never exceeded, requests never lost
# ---------------------------------------------------------------------------

_IDS = itertools.count(10_000)


def _pressured_fleet():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    cfg = _amc(cfg, pool_mode="augment-on-pressure", retention_steps=4)
    # two Normal pages per array: tight enough that admissions contend
    probe = make_store(cfg, max_batch=2, max_seq=32)
    budget = 2 * probe.geom.page_bytes_normal
    del probe
    return ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1,
                      pool_budget_bytes=budget)


@pytest.fixture(scope="module")
def pressured_fleet():
    return _pressured_fleet()


def _check_invariants(fleet):
    for i, eng in enumerate(fleet.engines):
        assert eng.store.live_bytes <= eng.store.budget_bytes, \
            f"array {i} over budget: {eng.store.live_bytes} > " \
            f"{eng.store.budget_bytes}"


def _drive_ops(fleet, ops):
    """Random admit/step/migrate schedule against a LIVE fleet (reused
    across examples — ids from a global counter). After the tail drain
    every admitted request must exist with its exact token count."""
    cfg = fleet.cfg
    added = {}
    rng = np.random.default_rng(ops[0][1] if ops else 0)
    for kind, a, b in ops:
        if kind == "add":
            rid = next(_IDS)
            req = Request(prompt=_prompt(rng, a, cfg.vocab),
                          max_new_tokens=b, id=rid)
            fleet.add_request(req)
            added[rid] = b
        else:
            fleet.step_all()               # steps, then rebalances
        _check_invariants(fleet)
    for _ in range(500):
        if not fleet.has_work:
            break
        fleet.step_all()
        _check_invariants(fleet)
    assert not fleet.has_work
    outs = fleet.outputs
    for rid, want in added.items():        # no request ever lost
        assert rid in outs and len(outs[rid]) == want, \
            f"request {rid} lost or truncated: {outs.get(rid)}"
    assert not fleet.failed


_OP = st.one_of(
    st.tuples(st.just("add"), st.integers(1, 10), st.integers(1, 5)),
    st.tuples(st.just("step"), st.just(0), st.just(0)),
) if HAVE_HYPOTHESIS else None

if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(ops=st.lists(_OP, min_size=1, max_size=12))
    def test_placement_invariants_random_schedules(pressured_fleet, ops):
        _drive_ops(pressured_fleet, ops)
else:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_placement_invariants_random_schedules(pressured_fleet, seed):
        rng = np.random.default_rng(seed)
        ops = []
        for _ in range(12):
            if rng.random() < 0.6:
                ops.append(("add", int(rng.integers(1, 11)),
                            int(rng.integers(1, 6))))
            else:
                ops.append(("step", 0, 0))
        _drive_ops(pressured_fleet, ops)


# ---------------------------------------------------------------------------
# observability: per-array lanes merge into one schema-valid trace
# ---------------------------------------------------------------------------

def test_fleet_trace_has_per_array_lanes_and_validates(tmp_path):
    cfg = get_arch("qwen1.5-0.5b").reduced()
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1,
                       trace=True, metrics=True)
    outs = fleet.generate(_reqs(cfg, 4, 6, 4, seed=8))
    assert len(outs) == 4
    obj = fleet.export_trace(str(tmp_path / "fleet_trace.json"))
    assert validate_chrome_trace(obj) == []
    pids = {e["pid"] for e in obj["traceEvents"]}
    assert pids == {0, 1}                  # one lane per array
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"array 0", "array 1"}
    placements = [e for e in obj["traceEvents"]
                  if e.get("name") == "placement"]
    assert len(placements) == 4
    assert all(p["args"]["kind"] == "admit" for p in placements)
    # fleet-wide metrics: one shared registry counted every admission
    text = fleet.export_metrics(str(tmp_path / "fleet.prom"))
    assert "amc_placement_admit 4" in text


def test_fleet_stats_report_per_array_state():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    fleet = ArrayFleet(cfg, num_arrays=2, max_batch=2, max_seq=32, seed=1)
    fleet.generate(_reqs(cfg, 4, 6, 3, seed=11))
    st_ = fleet.stats()
    fl = st_["fleet"]
    assert fl["num_arrays"] == 2 and fl["alive"] == [0, 1]
    assert len(fl["per_array"]) == 2 and len(st_["arrays"]) == 2
    for a in fl["per_array"]:
        assert {"occupancy", "mode_normal", "mode_augmented",
                "refresh_debt", "energy_fj", "heads_axes",
                "tensor_parallel"} <= a.keys()
        # 1 CPU device -> model axis 1 -> no TP claimed
        assert a["model_axis"] == 1 and a["tensor_parallel"] is False
    assert sum(fl["placements_per_array"]) == 4
