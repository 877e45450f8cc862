"""repro.lifecycle: sharded resumable campaigns, shadow serving, the
promotion gate + bundle registry, and the lifecycle satellites (deadline
propagation into the numeric solve, per-shard mesh utilization)."""
import json
import os
import pickle

import pytest

from repro.engine import (EngineConfig, EngineError, SelectorBundle,
                          SolverEngine)
from repro.lifecycle import (BundleRegistry, BundleRegistryError,
                             CampaignConfig, GateRejected, NotPromotable,
                             PromotionGate, ShadowEvaluator,
                             assemble_dataset, evaluate_gate, run_campaign)
from repro.sparse.dataset import generate_suite
from repro.sparse.reorder import LABEL_ALGORITHMS

from test_engine import make_engine, synth_dataset


def tiny_suite(count=4):
    return list(generate_suite(count=count, seed=3, size_scale=0.2))


def campaign_cfg(tmp_path, **kw):
    kw.setdefault("campaign_id", "t")
    kw.setdefault("labels_dir", str(tmp_path / "labels"))
    kw.setdefault("workers", 2)
    return CampaignConfig(**kw)


# ---------------------------------------------------------------------------
# campaign: resume, sharding, assembly
# ---------------------------------------------------------------------------

def test_campaign_killed_midway_resumes_without_relabeling(tmp_path):
    mats = tiny_suite()
    cfg = campaign_cfg(tmp_path, max_cells=5)  # "killed" after 5 cells
    r1 = run_campaign(mats, cfg).report
    assert r1["cells_labeled"] == 5 and not r1["complete"]

    # poison every completed cell with a sentinel: a resume that
    # re-measured any of them would overwrite it
    poisoned = 0
    camp_dir = tmp_path / "labels" / "t"
    for fn in os.listdir(camp_dir):
        path = camp_dir / fn
        rec = json.loads(path.read_text())
        for cell in rec["cells"].values():
            cell["time"] = 123.456
            poisoned += 1
        path.write_text(json.dumps(rec))
    assert poisoned == 5

    cfg2 = campaign_cfg(tmp_path)  # no budget: finish the campaign
    r2 = run_campaign(mats, cfg2).report
    assert r2["cells_skipped"] == 5
    assert r2["cells_labeled"] == r2["cells_total"] - 5
    assert r2["complete"]
    survivors = 0
    for fn in os.listdir(camp_dir):
        rec = json.loads((camp_dir / fn).read_text())
        survivors += sum(1 for c in rec["cells"].values()
                         if c["time"] == 123.456)
    assert survivors == poisoned  # completed cells were never re-labeled


def test_campaign_report_shape(tmp_path):
    mats = tiny_suite()
    res = run_campaign(mats, campaign_cfg(tmp_path))
    r = res.report
    assert r["cells_total"] == len(mats) * len(LABEL_ALGORITHMS)
    assert r["cells_labeled"] == r["cells_total"]
    assert sum(r["per_algorithm_wins"].values()) == len(mats)
    bd = r["label_time_breakdown"]
    assert all(bd[k] >= 0 for k in ("order_s", "symbolic_s", "factor_s",
                                    "solve_s"))
    assert res.dataset is not None  # single shard + complete → assembled


def test_campaign_shards_partition_and_assemble(tmp_path):
    mats = tiny_suite()
    for i in range(2):
        cfg = campaign_cfg(tmp_path, shard_index=i, shard_count=2)
        r = run_campaign(mats, cfg).report
        assert r["complete"]
        assert r["matrices"] == len([m for j, m in enumerate(mats)
                                     if j % 2 == i])
    # the union of the shards covers the suite: assembly succeeds and
    # matches the sequential labeling layout
    ds = assemble_dataset(mats, campaign_cfg(tmp_path))
    assert ds.names == [a.name for a in mats]
    assert ds.times.shape == (len(mats), len(LABEL_ALGORITHMS))
    assert (ds.labels == ds.times.argmin(axis=1)).all()


@pytest.mark.parametrize("backend", ["pallas", "batched", "pipelined"])
def test_campaign_cli_refuses_processes_on_device_backend(tmp_path, backend):
    """Each shard process would claim the accelerator: refused up front,
    before any child is spawned."""
    from repro.lifecycle.campaign import main

    with pytest.raises(SystemExit, match="one process at a time"):
        main(["--processes", "2", "--backend", backend,
              "--labels-dir", str(tmp_path), "--out", ""])
    assert not os.listdir(tmp_path)


def test_assemble_incomplete_campaign_raises(tmp_path):
    mats = tiny_suite()
    run_campaign(mats, campaign_cfg(tmp_path, max_cells=3))
    with pytest.raises(RuntimeError, match="missing cells|no label"):
        assemble_dataset(mats, campaign_cfg(tmp_path))


def test_assembled_dataset_trains_an_engine(tmp_path):
    # 8 matrices over 4 algorithms: however noisy the timings, some
    # winner class has >= 2 members, so the stratified held-out split
    # is never empty
    mats = tiny_suite(count=8)
    res = run_campaign(mats, campaign_cfg(tmp_path))
    engine = SolverEngine(EngineConfig(
        model="decision_tree", path="host", fast_grids=True, cv=2,
        test_size=0.5, cache_dir=None))
    report = engine.train(res.dataset)
    assert engine.is_trained and "test_accuracy" in report
    name, _ = engine.select(mats[0])
    assert name in LABEL_ALGORITHMS


# ---------------------------------------------------------------------------
# shadow serving
# ---------------------------------------------------------------------------

def test_shadow_never_touches_client_responses(tmp_path, small_suite):
    engine = make_engine(tmp_path, bundle_dir=str(tmp_path / "bundles"))
    cand = make_engine(tmp_path / "cand", seed=9)
    cand_path = str(tmp_path / "cand.bundle")
    cand.save(cand_path)

    baseline = [engine.plan(a).algorithm for a in small_suite]
    built0 = engine.builder.plans_built
    engine.start_shadow(cand_path)
    shadowed = [engine.plan(a).algorithm for a in small_suite]
    assert shadowed == baseline
    assert engine.builder.plans_built == built0  # all warm, no rebuilds
    assert engine.shadow.drain(30)
    st = engine.shadow.stats()
    assert st["requests"] == len(small_suite)
    assert st["evaluated"] == len(small_suite)
    assert st["agreements"] + st["disagreements"] == st["evaluated"]
    assert st["wins"] + st["losses"] == st["evaluated"]
    # the scorecard also lands in the engine's metrics registry
    snap = engine.metrics.snapshot()
    assert snap["shadow.evaluated"] == len(small_suite)
    assert 0.0 <= snap["shadow.win_rate"] <= 1.0
    final = engine.stop_shadow()
    assert final["evaluated"] == len(small_suite)
    assert engine.shadow is None


def test_dispatcher_mirrors_warm_and_cold_decisions(tmp_path, small_suite):
    engine = make_engine(tmp_path)
    cand = make_engine(tmp_path / "cand", seed=9)
    engine.start_shadow(SelectorBundle.from_selector(cand.selector))
    server = engine.serve(batch_size=2, max_wait_ms=1.0)
    try:
        cold = [f.result(60) for f in [server.submit(a)
                                       for a in small_suite]]
        warm = [f.result(60) for f in [server.submit(a)
                                       for a in small_suite]]
        assert [p.algorithm for p in cold] == [p.algorithm for p in warm]
        assert engine.shadow.drain(30)
        st = engine.shadow.stats()
        # cold path mirrors once per unique structure, warm once per hit
        assert st["requests"] == 2 * len(small_suite)
    finally:
        server.close()
        engine.stop_shadow()


def test_shadow_observe_never_raises_and_drops_when_full(tmp_path):
    cand = make_engine(tmp_path, seed=9)
    ev = ShadowEvaluator(SelectorBundle.from_selector(cand.selector),
                         max_queue=1)
    try:
        ev.close()  # worker gone: observations can only queue up / drop
        mats = tiny_suite(2)
        for _ in range(5):
            ev.observe(mats[0], "amd")
        st = ev.stats()
        assert st["requests"] == 5
        assert st["dropped"] >= 3  # capacity 1 (+1 possibly consumed)
    finally:
        ev.close()


# ---------------------------------------------------------------------------
# promotion gate + registry
# ---------------------------------------------------------------------------

def make_v1_bundle_path(tmp_path, engine) -> str:
    """The PR 6 v1-envelope recipe: strip the v2 descriptive sections."""
    path = str(tmp_path / "v1.bundle")
    engine.save(path)
    with open(path, "rb") as f:
        env = pickle.load(f)
    env["schema_version"] = 1
    env["bundle"]["schema_version"] = 1
    del env["bundle"]["report_card"]
    del env["bundle"]["provenance"]
    with open(path, "wb") as f:
        pickle.dump(env, f)
    return path


def test_v1_bundle_loads_but_is_never_auto_promotable(tmp_path):
    engine = make_engine(tmp_path, bundle_dir=str(tmp_path / "bundles"))
    v1_path = make_v1_bundle_path(tmp_path, make_engine(tmp_path / "c",
                                                        seed=9))
    # loadable and servable...
    b = SelectorBundle.load(v1_path)
    assert b.schema_version == 1 and b.report_card is None
    assert SolverEngine.load(v1_path).is_trained
    # ...but the gate refuses it with the typed error, however permissive
    gate = PromotionGate(min_test_accuracy=0.0, require_shadow=False)
    with pytest.raises(NotPromotable, match="report card"):
        evaluate_gate(b, gate)
    with pytest.raises(NotPromotable):
        engine.promote(v1_path, gate=gate)
    # nothing changed: no registration, no swap
    assert len(engine.registry) == 0


def test_gate_rejects_on_each_threshold(tmp_path):
    cand = make_engine(tmp_path, seed=9)
    b = SelectorBundle.from_selector(cand.selector,
                                     report_card=dict(test_accuracy=0.8))
    ok_stats = dict(evaluated=20, win_rate=0.75)

    dec = evaluate_gate(b, PromotionGate(0.5, 10, 0.5), ok_stats)
    assert dec["passed"] and dec["fingerprint"] == b.fingerprint

    with pytest.raises(GateRejected) as ei:
        evaluate_gate(b, PromotionGate(0.9, 10, 0.5), ok_stats)
    assert [c["check"] for c in ei.value.decision["checks"]
            if not c["passed"]] == ["report_card.test_accuracy"]
    with pytest.raises(GateRejected):
        evaluate_gate(b, PromotionGate(0.5, 100, 0.5), ok_stats)
    with pytest.raises(GateRejected):
        evaluate_gate(b, PromotionGate(0.5, 10, 0.9), ok_stats)
    with pytest.raises(GateRejected):  # no shadow evidence at all
        evaluate_gate(b, PromotionGate(0.5, 10, 0.5), None)
    # offline-only gate ignores the missing shadow
    assert evaluate_gate(b, PromotionGate(0.5, require_shadow=False),
                         None)["passed"]


def test_registry_lineage_statuses_and_dedup(tmp_path):
    reg = BundleRegistry(str(tmp_path / "bundles"))
    b1 = SelectorBundle.from_selector(make_engine(tmp_path / "a").selector)
    b2 = SelectorBundle.from_selector(
        make_engine(tmp_path / "b", seed=9).selector)
    e1 = reg.register(b1, source="train")
    assert e1["status"] == "candidate" and e1["parent"] is None
    assert reg.register(b1)["version"] == e1["version"]  # content dedup
    assert len(reg) == 1
    reg.mark_serving(e1["version"])
    e2 = reg.register(b2, source="retrain")
    assert e2["parent"] == e1["version"]  # lineage edge to serving
    reg.mark_serving(e2["version"])
    assert reg.serving_version() == e2["version"]
    assert reg.entry(e1["version"])["status"] == "retired"
    chain = reg.lineage()
    assert [e["version"] for e in chain] == [e2["version"], e1["version"]]
    # loaded payload round-trips
    assert reg.load(e2["version"]).fingerprint == b2.fingerprint
    # rollback swaps the pointers and marks the demoted version
    back = reg.rollback()
    assert back["version"] == e1["version"]
    assert reg.entry(e2["version"])["status"] == "rolled_back"
    assert reg.previous_version() == e2["version"]
    with pytest.raises(BundleRegistryError):
        reg.entry("v9999-nope")


def test_rollback_with_no_previous_raises(tmp_path):
    with pytest.raises(BundleRegistryError, match="roll back"):
        BundleRegistry(str(tmp_path / "bundles")).rollback()


def test_promote_swaps_cache_version_and_rollback_restores(
        tmp_path, small_suite):
    engine = make_engine(tmp_path, bundle_dir=str(tmp_path / "bundles"),
                         promote_min_accuracy=0.0,
                         promote_min_shadow_requests=1,
                         promote_min_win_rate=0.0)
    fp0 = engine.fingerprint
    cand = make_engine(tmp_path / "cand", seed=9)
    cand_path = str(tmp_path / "cand.bundle")
    cand.save(cand_path)

    for a in small_suite:           # warm the incumbent's two-tier cache
        engine.plan(a)
    engine.start_shadow(cand_path)
    for a in small_suite:
        engine.plan(a)
    engine.shadow.drain(30)

    # a gate the candidate cannot clear leaves everything untouched
    with pytest.raises(GateRejected):
        engine.promote(gate=PromotionGate(0.0, 1, 1.01))
    assert engine.fingerprint == fp0

    decision = engine.promote()     # config thresholds: permissive
    assert decision["passed"] and engine.fingerprint == cand.fingerprint
    assert engine.shadow is None    # promote retires the shadow
    assert engine.config.model == "decision_tree"
    # old plans are invisible under the new cache version
    assert engine.builder.sym_builds == 0
    engine.plan(small_suite[0])
    assert engine.builder.sym_builds == 1
    # registry recorded the swap with lineage
    assert engine.registry.serving_version() == decision["version"]
    assert (engine.registry.entry(decision["version"])["parent"]
            == decision["previous_version"])

    entry = engine.rollback()
    assert entry["version"] == decision["previous_version"]
    assert engine.fingerprint == fp0
    # the incumbent's plans come back from disk: no symbolic rebuild
    sb = engine.builder.sym_builds
    engine.plan(small_suite[0])
    assert engine.builder.sym_builds == sb


def test_promote_same_bundle_twice_preserves_report_card(tmp_path):
    """After promote #1 the engine's last_report describes the OLD fit;
    registering the incumbent at promote #2 must reuse the adopted
    bundle's own card (fingerprint-matched), not a stale report."""
    engine = make_engine(tmp_path, bundle_dir=str(tmp_path / "bundles"))
    c1 = make_engine(tmp_path / "c1", seed=9)
    p1 = str(tmp_path / "c1.bundle")
    c1.save(p1)
    gate = PromotionGate(min_test_accuracy=0.0, require_shadow=False)
    d1 = engine.promote(p1, gate=gate)
    c2 = make_engine(tmp_path / "c2", seed=11)
    p2 = str(tmp_path / "c2.bundle")
    c2.save(p2)
    d2 = engine.promote(p2, gate=gate)
    # promote #2's "incumbent" registration deduped onto promote #1's
    # candidate entry (same fingerprint) — no phantom third lineage node
    assert d2["previous_version"] == d1["version"]
    reg = engine.registry
    inc = reg.entry(d1["version"])
    assert inc["fingerprint"] == c1.fingerprint
    assert inc["test_accuracy"] == pytest.approx(
        c1.last_report["test_accuracy"])


def test_promote_without_candidate_or_shadow_raises(tmp_path):
    engine = make_engine(tmp_path, bundle_dir=str(tmp_path / "bundles"))
    with pytest.raises(EngineError, match="no candidate"):
        engine.promote()


# ---------------------------------------------------------------------------
# satellites: deadline propagation + per-shard mesh utilization
# ---------------------------------------------------------------------------

class _ExpiringCtx:
    """RequestContext stand-in whose deadline passes after N expiry checks
    — deterministic mid-factorization expiry without wall-clock sleeps."""

    def __init__(self, after: int):
        self.after = after
        self.calls = 0

    def expired(self) -> bool:
        self.calls += 1
        return self.calls > self.after

    def remaining(self) -> float:
        return -0.005


@pytest.mark.parametrize("backend", ["batched", "pipelined"])
def test_deadline_exceeded_mid_factorization(small_suite, backend):
    from repro.core.reqctx import DeadlineExceeded
    from repro.sparse.multifrontal import multifrontal_cholesky

    a = small_suite[0]
    ctx = _ExpiringCtx(after=1)  # passes the entry check, expires at L0
    with pytest.raises(DeadlineExceeded, match="factorization abandoned"):
        multifrontal_cholesky(a, backend=backend, ctx=ctx)
    assert ctx.calls >= 2  # entry check + at least one level boundary
    # an unexpired context leaves the result untouched
    live = _ExpiringCtx(after=10_000)
    f = multifrontal_cholesky(a, backend=backend, ctx=live)
    assert f.stats["nsup"] > 0 and live.calls >= 2


def test_execute_plan_threads_ctx_into_numeric_phase(small_suite):
    from repro.core.plan import PlanBuilder, execute_plan
    from repro.core.reqctx import DeadlineExceeded

    a = small_suite[0]
    plan = PlanBuilder().build(a, algorithm="amd")
    ctx = _ExpiringCtx(after=1)
    with pytest.raises(DeadlineExceeded):
        execute_plan(a, plan, backend="batched", solve_dtype="fp32",
                     ctx=ctx)


def test_shard_utilization_math():
    from repro.distributed.meshctx import ServingMesh, make_serving_mesh

    sm = make_serving_mesh(1)  # tests always see one device
    assert sm.shard_utilization(3, 4) == [(3, 1)]
    assert sm.shard_utilization(4, 4) == [(4, 0)]
    assert sm.shard_utilization(0, 4) == [(0, 4)]

    class _Wide:  # the 4-shard math without needing 4 devices
        num_devices = 4
        shard_utilization = ServingMesh.shard_utilization

    wide = _Wide()
    # contiguous split: padding concentrates on the tail shards
    assert wide.shard_utilization(5, 8) == [(2, 0), (2, 0), (1, 1), (0, 2)]
    assert wide.shard_utilization(8, 8) == [(2, 0)] * 4
    with pytest.raises(ValueError):
        wide.shard_utilization(5, 6)  # 6 rows don't divide over 4 shards


def test_record_shard_utilization_metrics():
    from repro.core.metrics import MetricsRegistry
    from repro.distributed.meshctx import (make_serving_mesh,
                                           record_shard_utilization)

    m = MetricsRegistry()
    sm = make_serving_mesh(1)
    record_shard_utilization(m, sm, 3, 4)
    record_shard_utilization(m, sm, 4, 4)
    snap = m.snapshot()
    assert snap["mesh.shards"] == 1
    assert snap["mesh.shard0.requests"] == 7
    assert snap["mesh.shard0.pad_rows"] == 1
    record_shard_utilization(None, sm, 3, 4)  # metrics=None: no-op


def test_device_path_records_mesh_utilization(tmp_path, small_suite):
    engine = SolverEngine(EngineConfig(
        model="decision_tree", path="device", fast_grids=True, cv=3,
        batch_size=4, cache_dir=str(tmp_path / "plan_cache")))
    engine.train(synth_dataset())
    engine.plan_batch(small_suite)
    snap = engine.metrics.snapshot()
    assert snap["mesh.shards"] >= 1
    total = snap["mesh.shard0.requests"]
    assert total >= len(small_suite)  # every live row was accounted
