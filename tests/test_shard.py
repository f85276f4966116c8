"""repro.shard: spec -> resolver -> ShardPlan, the per-topology
plan-cache registry, the mesh-native ShardedServingEngine's routed
admission, and (multidevice tier, subprocesses) dp/sp topology parity
against the single-device oracle."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import jax
import pytest

from _hyp_compat import given, settings, strategies as st
from repro.compat import make_mesh
from repro.configs.base import ServeConfig
from repro.configs.reduced import reduced_config
from repro.models import build_model
from repro.plan import merge_stats_snapshots
from repro.serving import Request, ServingEngine
from repro.serving.scheduler import Completion  # noqa: F401  (API surface)
from repro.shard import (
    ShardResolver,
    ShardSpec,
    ShardedServingEngine,
    clear_shard_plan_caches,
    pick_shard,
    shard_plan_cache,
)
from repro.tune import select_table
from repro.tune.table import REFERENCE_TABLE_PATH

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def _fresh_registry():
    clear_shard_plan_caches()
    yield
    clear_shard_plan_caches()


# ---------------------------------------------------------------------------
# ShardSpec (pure data)
# ---------------------------------------------------------------------------


def test_spec_validation_and_derived():
    s = ShardSpec(dp=4, sp=2, slots_per_shard=3)
    assert s.num_devices == 8
    assert s.total_slots == 12
    with pytest.raises(ValueError, match="axes must be >= 1"):
        ShardSpec(dp=0)
    with pytest.raises(ValueError, match="axes must be >= 1"):
        ShardSpec(sp=0)
    with pytest.raises(ValueError, match="slots_per_shard"):
        ShardSpec(slots_per_shard=0)
    with pytest.raises(ValueError, match="page_budget_per_shard"):
        ShardSpec(page_budget_per_shard=0)
    with pytest.raises(ValueError, match="params policy"):
        ShardSpec(params="sharded")


def test_spec_fingerprint_is_stable_identity():
    a = ShardSpec(dp=2, sp=2)
    assert a.fingerprint == ShardSpec(dp=2, sp=2).fingerprint
    assert a.fingerprint.startswith("shard.")
    # every field is identity: same grid, different budget -> new key
    assert a.fingerprint != ShardSpec(dp=2, sp=2, slots_per_shard=8).fingerprint
    assert a.fingerprint != ShardSpec(dp=2, sp=2,
                                      page_budget_per_shard=4).fingerprint
    assert a.fingerprint != a.with_(params="tp").fingerprint


def test_spec_parse_forms():
    assert ShardSpec.parse("4,2") == ShardSpec(dp=4, sp=2)
    assert ShardSpec.parse("4") == ShardSpec(dp=4, sp=1)
    assert ShardSpec.parse(" dp=2, sp=4 ") == ShardSpec(dp=2, sp=4)
    assert ShardSpec.parse("sp=2,slots_per_shard=8") == \
        ShardSpec(sp=2, slots_per_shard=8)
    # overrides win over the parsed text (serve --slots)
    assert ShardSpec.parse("2,2", slots_per_shard=6).slots_per_shard == 6
    with pytest.raises(ValueError, match="empty"):
        ShardSpec.parse(" , ")
    with pytest.raises(ValueError, match="mixed"):
        ShardSpec.parse("4,sp=2")
    with pytest.raises(ValueError, match="unknown shard topology field"):
        ShardSpec.parse("dp=2,chips=4")
    with pytest.raises(ValueError, match="positional"):
        ShardSpec.parse("2,2,2")


def test_pick_shard_least_loaded_lowest_index():
    assert pick_shard([3, 1, 2]) == 1
    assert pick_shard([2, 1, 1]) == 1          # tie -> lowest index
    assert pick_shard([0, 0, 0, 0]) == 0
    assert pick_shard([5]) == 0


# ---------------------------------------------------------------------------
# ShardResolver (validation happens at resolution, not first launch)
# ---------------------------------------------------------------------------


def test_resolver_divisibility_checked_before_devices():
    # these raise on ONE device even though the topologies need more:
    # layout divisibility fails first, with the layout in the message
    with pytest.raises(ValueError, match="max_len"):
        ShardResolver(ShardSpec(sp=2)).resolve(max_len=63)
    with pytest.raises(ValueError, match="page_size"):
        ShardResolver(ShardSpec(sp=2)).resolve(
            max_len=64, cache_layout="paged", page_size=15)


def test_resolver_rejects_short_device_set():
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        ShardResolver(ShardSpec(dp=2)).resolve(
            max_len=64, devices=jax.devices()[:1])


def test_resolved_plan_shapes_and_registry():
    plan = ShardResolver(ShardSpec(dp=1, sp=1)).resolve(max_len=64)
    assert plan.mesh.shape == {"data": 1, "model": 1}
    assert len(plan.submeshes) == 1
    assert plan.shard_devices(0) == plan.devices
    assert plan.fingerprint.startswith(plan.spec.fingerprint + ".")
    # same (topology, shard, ident) -> the SAME PlanCache object; any
    # key component changing -> a different one
    c0 = plan.plan_cache(0, ident=("a",))
    assert plan.plan_cache(0, ident=("a",)) is c0
    assert plan.plan_cache(0, ident=("b",)) is not c0
    clear_shard_plan_caches()
    assert plan.plan_cache(0, ident=("a",)) is not c0
    assert shard_plan_cache(("x",), 4).capacity == 4


# ---------------------------------------------------------------------------
# merge_stats_snapshots (the stats_path dump's aggregate section)
# ---------------------------------------------------------------------------


def test_merge_stats_snapshots_sums_and_unions():
    a = {"hits": 3, "misses": 1, "total_launches": 4,
         "launches": {"128": 4}, "seen_buckets": [128],
         "spec_proposed": 4, "spec_accepted": 2, "spec_steps": 2,
         "spec_emitted": 4, "shard": 0, "policy": "paper"}
    b = {"hits": 5, "misses": 2, "total_launches": 7,
         "launches": {"128": 3, "256": 4}, "seen_buckets": [128, 256],
         "table_registry_fallbacks": 1}
    m = merge_stats_snapshots([a, b])
    assert m["hits"] == 8 and m["misses"] == 3
    assert m["total_launches"] == 11
    assert m["launches"] == {"128": 7, "256": 4}
    assert m["seen_buckets"] == [128, 256]
    assert m["distinct_buckets"] == 2          # union, not a sum
    assert m["table_registry_fallbacks"] == 1
    assert m["spec_acceptance_rate"] == 0.5
    assert m["spec_tokens_per_step"] == 2.0
    assert m["shards"] == 2
    # annotation keys pass through to neither sums nor output
    assert "policy" not in m and "shard" not in m


# ---------------------------------------------------------------------------
# select_table: tune_table_path as a registry DIRECTORY
# ---------------------------------------------------------------------------


def _write_table_variant(dst: Path, backend: str, device: str) -> None:
    d = json.loads(REFERENCE_TABLE_PATH.read_text())
    d["fingerprint"]["backend"] = backend
    d["fingerprint"]["device"] = device
    dst.write_text(json.dumps(d))


def test_select_table_file_and_registry_match(tmp_path):
    table, matched = select_table(REFERENCE_TABLE_PATH)
    assert matched                              # plain file: trusted
    live = jax.default_backend()
    _write_table_variant(tmp_path / "a_tpu.json", "tpu", "TPU v5e")
    _write_table_variant(tmp_path / "b_live.json", live,
                         jax.devices()[0].device_kind)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # a match must not warn
        table, matched = select_table(tmp_path)
    assert matched
    assert table.fingerprint["backend"] == live


def test_select_table_registry_fallback_warns_and_counts(tmp_path,
                                                         tiny_model):
    _write_table_variant(tmp_path / "a_tpu.json", "tpu", "TPU v5e")
    _write_table_variant(tmp_path / "b_gpu.json", "gpu", "H100")
    with pytest.warns(RuntimeWarning, match="no table in registry"):
        table, matched = select_table(tmp_path)
    assert not matched
    assert table.fingerprint["backend"] == "tpu"   # sorted-name fallback
    (tmp_path / "empty").mkdir()
    with pytest.raises(ValueError, match="no \\*\\.json"):
        select_table(tmp_path / "empty")

    # the engine counts the fallback (observability, not a hard error)
    cfg, model, params = tiny_model
    with pytest.warns(RuntimeWarning, match="no table in registry"):
        eng = ServingEngine(
            model, ServeConfig(model=cfg, split_policy="measured",
                               tune_table_path=str(tmp_path)),
            max_len=64, batch_slots=1)
    assert eng.stats.table_registry_fallbacks == 1
    assert eng.tune_table is not None


# ---------------------------------------------------------------------------
# build_serve_step deprecation shim
# ---------------------------------------------------------------------------


def test_build_serve_step_shim_warns_once_and_delegates(tiny_model,
                                                        monkeypatch):
    from repro.serving import decode_step
    cfg, model, _ = tiny_model
    monkeypatch.setattr(decode_step, "_BUILD_SERVE_STEP_WARNED", False)
    mesh = make_mesh((1, 1), ("data", "model"))
    scfg = ServeConfig(model=cfg)
    with pytest.warns(DeprecationWarning, match="build_mesh_decode_step"):
        bundle = decode_step.build_serve_step(model, scfg, mesh)
    assert bundle.step is not None              # delegated, same bundle
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        bundle2 = decode_step.build_serve_step(model, scfg, mesh)
    assert not any(issubclass(x.category, DeprecationWarning)
                   for x in rec)                # warn-once
    assert type(bundle2) is type(bundle)


# ---------------------------------------------------------------------------
# dp=1 x sp=1 on the host device: full parity with the plain engine
# ---------------------------------------------------------------------------


def _reqs(n, seed=0, max_new=6):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, 250,
                                    size=int(rng.integers(2, 8))).tolist(),
                    max_new_tokens=max_new) for i in range(n)]


def test_dp1_sp1_matches_plain_engine(tiny_model):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg)
    plain = ServingEngine(model, scfg, max_len=64, batch_slots=2)
    plain.load(params)
    for r in _reqs(5):
        plain.submit(r)
    want = {c.request_id: (c.tokens, c.finish_reason)
            for c in plain.drain()}

    eng = ShardedServingEngine(
        model, scfg, spec=ShardSpec(dp=1, sp=1, slots_per_shard=2),
        max_len=64)
    eng.load(params)
    handles = [eng.submit(r) for r in _reqs(5)]
    assert len(set(handles)) == 5               # global handles
    got = {c.request_id: (c.tokens, c.finish_reason)
           for c in eng.drain()}
    assert got == want
    agg = eng.aggregate_stats()
    assert agg["shards"] == 1
    assert agg["total_launches"] == plain.stats.total_launches
    assert eng.routed(0) == [0, 1, 2, 3, 4]
    assert eng.B == 2


def test_per_shard_page_budget_and_label(tiny_model):
    """spec.page_budget_per_shard replaces the engine-wide budget: the
    sharded engine hits cache_capacity exactly like a plain engine with
    cache_page_budget set to the same number, and its conservation
    assertions carry the shard label."""
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg, cache_layout="paged",
                       cache_page_size=16)
    reqs = lambda: [Request(0, [1] * 20, max_new_tokens=60),  # noqa: E731
                    Request(1, [2] * 5, max_new_tokens=3)]
    plain = ServingEngine(
        model, dataclasses.replace(scfg, cache_page_budget=3),
        max_len=128, batch_slots=1)
    plain.load(params)
    for r in reqs():
        plain.submit(r)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        want = {c.request_id: (c.tokens, c.finish_reason)
                for c in plain.drain()}
    assert want[0][1] == "cache_capacity"       # 3 pages = 48 rows < 80
    assert want[1][1] == "length"

    eng = ShardedServingEngine(
        model, scfg,
        spec=ShardSpec(dp=1, sp=1, slots_per_shard=1,
                       page_budget_per_shard=3),
        max_len=128)
    eng.load(params)
    assert eng.cores[0].cache.label == "shard0"
    assert eng.cores[0].cache_stats()["total_pages"] == 3
    for r in reqs():
        eng.submit(r)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = {c.request_id: (c.tokens, c.finish_reason)
               for c in eng.drain()}
    assert got == want
    eng.check_conservation()
    assert eng.describe()[0]["free_pages"] == 3


def test_same_topology_engines_share_compiled_steps(tiny_model):
    """Two engines resolved to the same (topology, identity) share ONE
    PlanCache: the second serves entirely on the first's compiled
    steps (zero new misses)."""
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg)
    spec = ShardSpec(dp=1, sp=1, slots_per_shard=2)
    e1 = ShardedServingEngine(model, scfg, spec=spec, max_len=64)
    e1.load(params)
    for r in _reqs(3):
        e1.submit(r)
    out1 = e1.drain()
    misses = e1.stats.misses
    assert misses > 0

    e2 = ShardedServingEngine(model, scfg, spec=spec, max_len=64)
    e2.load(params)
    assert e2.cores[0].sched.plans is e1.cores[0].sched.plans
    for r in _reqs(3):
        e2.submit(r)
    out2 = e2.drain()
    assert e2.stats.misses == misses            # warm: hits only
    assert [c.tokens for c in out1] == [c.tokens for c in out2]

    # a different identity (policy) must NOT share
    e3 = ShardedServingEngine(model, scfg, spec=spec, max_len=64,
                              policy="fa3_baseline")
    assert e3.cores[0].sched.plans is not e1.cores[0].sched.plans


def test_stats_path_merges_shards_into_one_dump(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    out = tmp_path / "stats.json"
    eng = ShardedServingEngine(
        model, ServeConfig(model=cfg, stats_path=str(out)),
        spec=ShardSpec(dp=1, sp=1, slots_per_shard=2), max_len=64)
    eng.load(params)
    for r in _reqs(3):
        eng.submit(r)
    eng.drain()
    d = json.loads(out.read_text())
    assert d["topology"]["dp"] == 1
    assert d["fingerprint"] == eng.plan.fingerprint
    assert [s["shard"] for s in d["shards"]] == [0]
    assert d["shards"][0]["devices"]
    assert d["aggregate"]["shards"] == 1
    assert d["aggregate"]["total_launches"] == \
        d["shards"][0]["total_launches"] > 0


def test_engine_requires_a_topology(tiny_model):
    cfg, model, _ = tiny_model
    with pytest.raises(ValueError, match="no topology"):
        ShardedServingEngine(model, ServeConfig(model=cfg))
    # ServeConfig.shard is the serve-launcher path to the same spec
    eng = ShardedServingEngine(
        model, ServeConfig(model=cfg, shard="1,1"), max_len=64)
    assert eng.spec == ShardSpec(dp=1, sp=1)


# ---------------------------------------------------------------------------
# multidevice tier: real dp/sp topologies in 8-device subprocesses
# ---------------------------------------------------------------------------


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


_SETUP = """
    import dataclasses, json, warnings
    import jax, numpy as np
    from repro.configs.base import ServeConfig
    from repro.configs.reduced import reduced_config
    from repro.models import build_model
    from repro.serving import Request, ServingEngine
    from repro.shard import ShardSpec, ShardedServingEngine

    cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    def reqs(n, seed=0, max_new=6):
        rng = np.random.default_rng(seed)
        return [Request(i, rng.integers(1, 250,
                        size=int(rng.integers(2, 8))).tolist(),
                        max_new_tokens=max_new) for i in range(n)]

    def done_map(outs):
        return {c.request_id: (tuple(c.tokens), c.finish_reason)
                for c in outs}
"""


@pytest.mark.multidevice
def test_dp4_serves_4x_slots_bit_identical():
    run_py(_SETUP + """
    scfg = ServeConfig(model=cfg)
    single = ServingEngine(model, scfg, max_len=64, batch_slots=2)
    single.load(params)
    for r in reqs(8):
        single.submit(r)
    want = done_map(single.drain())

    eng = ShardedServingEngine(
        model, scfg, spec=ShardSpec(dp=4, sp=1, slots_per_shard=2),
        max_len=64)
    eng.load(params)
    assert eng.B == 4 * single.B == 8
    for r in reqs(8):
        eng.submit(r)
    assert done_map(eng.drain()) == want

    per_shard = [c.stats.total_launches for c in eng.cores]
    assert all(n > 0 for n in per_shard), per_shard
    # round-robin routing under equal load: 2 requests per shard
    assert [len(eng.routed(d)) for d in range(4)] == [2, 2, 2, 2]
    agg = eng.aggregate_stats()
    assert agg["shards"] == 4
    assert agg["total_launches"] == sum(per_shard)
    print("dp4 OK", per_shard)
    """)


@pytest.mark.multidevice
def test_sp4_long_context_decode_with_mesh_provenance():
    """sp=4 sequence-shards an L_K=4096 dense decode over 4 chips:
    tokens bit-identical to the single-device engine, and every decode
    plan carries mesh_splits=4 + the realized shard mesh."""
    run_py(_SETUP + """
    scfg = ServeConfig(model=cfg)
    prompt = np.random.default_rng(1).integers(
        1, 250, size=4000).tolist()
    def one_req():
        return [Request(0, list(prompt), max_new_tokens=5)]

    single = ServingEngine(model, scfg, max_len=4096, batch_slots=1)
    single.load(params)
    for r in one_req():
        single.submit(r)
    want = done_map(single.drain())

    eng = ShardedServingEngine(
        model, scfg, spec=ShardSpec(dp=1, sp=4, slots_per_shard=1),
        max_len=4096)
    eng.load(params)
    assert eng.cores[0].seq_shards == 4
    for r in one_req():
        eng.submit(r)
    assert done_map(eng.drain()) == want

    plans = {k: e.plan for k, e in eng.cores[0].sched.plans.items()
             if isinstance(k, int)}
    assert 4096 in plans, sorted(plans)
    assert all(p.mesh_splits == 4 and p.seq_shard_mesh is not None
               for p in plans.values()), plans
    print("sp4 OK", {k: p.mesh_splits for k, p in plans.items()})
    """)


@pytest.mark.multidevice
def test_sp4_load_makes_each_cache_shard_in_place():
    """A sequence-sharded engine makes its dense cache on the mesh: the
    storage is only ever traced, never made whole on one device (at
    published sizes the whole cache beside the weights overflows a
    chip), and each device holds a quarter of every leaf's rows."""
    run_py(_SETUP + """
    from repro.cache.manager import CacheManager
    made = []
    real = CacheManager.init_storage
    def spy(self):
        out = real(self)
        made.extend(isinstance(x, jax.core.Tracer)
                    for x in jax.tree.leaves(out))
        return out
    CacheManager.init_storage = spy

    eng = ShardedServingEngine(
        model, ServeConfig(model=cfg),
        spec=ShardSpec(dp=1, sp=4, slots_per_shard=2), max_len=256)
    eng.load(params)
    assert made and all(made), made
    for leaf in jax.tree.leaves(eng.cores[0]._caches):
        rows = {s.data.shape[2] for s in leaf.addressable_shards}
        assert len(leaf.addressable_shards) == 4 and rows == {64}, rows
    for r in reqs(2):
        eng.submit(r)
    assert all(len(c.tokens) == 6 for c in eng.drain())
    print("sp4 load OK")
    """)


@pytest.mark.multidevice
def test_dp2_paged_budget_exhaustion_is_per_shard():
    """One shard exhausting ITS page budget finishes only ITS request
    with cache_capacity — the other shard's identical budget is
    untouched and its request runs to length."""
    run_py(_SETUP + """
    scfg = ServeConfig(model=cfg, cache_layout="paged",
                       cache_page_size=16)
    eng = ShardedServingEngine(
        model, scfg,
        spec=ShardSpec(dp=2, sp=1, slots_per_shard=1,
                       page_budget_per_shard=3),
        max_len=128)
    eng.load(params)
    eng.submit(Request(0, [1] * 20, max_new_tokens=60))  # -> shard 0
    eng.submit(Request(1, [2] * 5, max_new_tokens=3))    # -> shard 1
    assert eng.routed(0) == [0] and eng.routed(1) == [1]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = done_map(eng.drain())
    assert got[0][1] == "cache_capacity", got
    assert got[1][1] == "length", got
    eng.check_conservation()
    for row in eng.describe():
        assert row["free_pages"] == row["total_pages"] == 3
    print("dp2 paged budget OK")
    """)


@pytest.mark.multidevice
def test_sp2_paged_decode_matches_oracle():
    run_py(_SETUP + """
    scfg = ServeConfig(model=cfg, cache_layout="paged",
                       cache_page_size=16)
    single = ServingEngine(model, scfg, max_len=64, batch_slots=2)
    single.load(params)
    for r in reqs(5):
        single.submit(r)
    want = done_map(single.drain())

    eng = ShardedServingEngine(
        model, scfg, spec=ShardSpec(dp=1, sp=2, slots_per_shard=2),
        max_len=64)
    eng.load(params)
    for r in reqs(5):
        eng.submit(r)
    assert done_map(eng.drain()) == want
    plans = {k: e.plan for k, e in eng.cores[0].sched.plans.items()
             if isinstance(k, int)}
    assert plans and all(p.mesh_splits == 2 for p in plans.values())
    eng.check_conservation()
    print("sp2 paged OK")
    """)


# ---------------------------------------------------------------------------
# property: ANY topology + ANY interleaving == the per-shard oracle
# ---------------------------------------------------------------------------

_PROPERTY_BODY = """
    DP, SP, LAYOUT, SEED = {dp}, {sp}, {layout!r}, {seed}
    rng = np.random.default_rng(SEED)
    scfg = ServeConfig(model=cfg, cache_layout=LAYOUT,
                       cache_page_size=16)
    budget = 4 if LAYOUT == "paged" else None
    spec = ShardSpec(dp=DP, sp=SP, slots_per_shard=2,
                     page_budget_per_shard=budget)
    eng = ShardedServingEngine(model, scfg, spec=spec, max_len=64)
    eng.load(params)
    # the oracle fleet: one single-DEVICE engine per shard, same
    # slots/budget (dp=1 sp=1 resolves to the first device only)
    oracle_scfg = dataclasses.replace(
        scfg, cache_page_budget=budget) if budget else scfg
    oracles = [ServingEngine(model, oracle_scfg, max_len=64,
                             batch_slots=2) for _ in range(DP)]
    for o in oracles:
        o.load(params)

    # mixed finish reasons: eos (random tokens), length (short), and —
    # paged: 4 pages = 64 rows shared by 2 slots — cache_capacity
    n = 9
    stream = [Request(i, rng.integers(1, 250,
                      size=int(rng.integers(2, 12))).tolist(),
                      max_new_tokens=int(rng.choice([3, 6, 40])))
              for i in range(n)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for r in stream:
            before = [len(eng.routed(d)) for d in range(DP)]
            eng.submit(Request(r.request_id, list(r.prompt),
                               r.max_new_tokens))
            after = [len(eng.routed(d)) for d in range(DP)]
            (d,) = [i for i in range(DP) if after[i] != before[i]]
            oracles[d].submit(r)
            # random interleaving, mirrored step-for-step per shard
            for _ in range(int(rng.integers(0, 3))):
                if not eng.has_work():
                    break
                pumped = [i for i, c in enumerate(eng.cores)
                          if c.has_work()]
                eng.step()
                for i in pumped:
                    assert oracles[i].has_work()   # lockstep invariant
                    oracles[i].step()
        got = done_map(eng.drain())
        want = {{}}
        for o in oracles:
            want.update(done_map(o.drain()))
    assert got == want, (got, want)
    assert sorted(r for d in range(DP) for r in eng.routed(d)) == \
        list(range(n))
    if LAYOUT == "paged":
        eng.check_conservation()
        for row in eng.describe():
            assert row["free_pages"] == row["total_pages"] == 4
    reasons = {{fr for _, fr in got.values()}}
    print("topology", (DP, SP, LAYOUT, SEED), "reasons", reasons)
"""


@pytest.mark.multidevice
@settings(max_examples=4, deadline=None)
@given(topo=st.sampled_from([(1, 2), (2, 1), (2, 2), (4, 1), (1, 4),
                             (4, 2), (2, 4), (3, 2)]),
       layout=st.sampled_from(["dense", "paged"]),
       seed=st.integers(0, 3))
def test_property_topology_parity_with_oracle(topo, layout, seed):
    dp, sp = topo
    run_py(_SETUP + _PROPERTY_BODY.format(dp=dp, sp=sp, layout=layout,
                                          seed=seed))
