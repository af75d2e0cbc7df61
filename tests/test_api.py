"""Unified ``repro.api`` pipeline: backend parity, auto-planning,
transactional execution, overlapping classes, incremental updates, and
the deprecated free-function shims."""
import numpy as np
import pytest

from repro.api import (CompactionPlan, Compactor, get_backend, get_detector,
                       register_detector)
from repro.core import semantic_triples
from repro.core.factorize import factorize_classes
from repro.core.triples import TermDict, TripleStore
from repro.data.synthetic import (SensorGraphSpec, figure1_graph,
                                  figure7b_graph, generate,
                                  property_set_ids)


def _sensor(n=400, seed=3, **kw):
    return generate(SensorGraphSpec(n_observations=n, seed=seed, **kw))


# ---------------------------------------------------------------------------
# backend parity (acceptance criterion)
# ---------------------------------------------------------------------------

def test_backend_parity_on_sensor_graph():
    """host / device / sharded produce identical props, edges, savings AND
    evaluation counts through the same Compactor pipeline."""
    pytest.importorskip("jax")
    store = _sensor(500, seed=21)
    reports = {be: Compactor(detector="gfsp", backend=be).run(store)
               for be in ("host", "device", "sharded")}
    ref = reports["host"]
    assert len(ref.plan) == 2            # Observation + Measurement
    for be, rep in reports.items():
        assert rep.n_triples_after == ref.n_triples_after, be
        assert rep.pct_savings_triples == ref.pct_savings_triples, be
        for cid, det in ref.detections.items():
            other = rep.detections[cid]
            assert set(other.props) == set(det.props), be
            assert other.edges == det.edges, be
            assert other.evaluations == det.evaluations, be


_MESH_PARITY = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import numpy as np, jax
sys.path.insert(0, "src")
from repro.api import Compactor, ShardedBackend
from repro.data.synthetic import SensorGraphSpec, generate
from repro.launch.mesh import make_mesh

store = generate(SensorGraphSpec(n_observations=403, seed=2))
cid = store.dict.lookup("ssn:Observation")
host = Compactor(detector="gfsp", backend="host").detect(store, cid)
mesh = make_mesh((4, 2), ("data", "model"))
be = ShardedBackend(mesh=mesh)
assert be.plan.dp_axes == ("data",), be.plan.dp_axes   # tp axis excluded
sh = Compactor(detector="gfsp", backend=be).detect(store, cid)
print(json.dumps([sorted(host.props), host.edges, host.evaluations,
                  sorted(sh.props), sh.edges, sh.evaluations]))
'''


def test_sharded_backend_real_mesh_parity():
    """Detection on a real 4x2 (data, model) mesh == host result.

    Regression: the implicit GSPMD lowering of the sort-based sweep
    miscounts distinct rows on multi-axis meshes (latent in the seed's
    gfsp_distributed, which only ever ran with mesh=None); the sharded
    backend must use the explicit ami_bucketed collective schedule."""
    import json
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", _MESH_PARITY],
                       capture_output=True, text=True, timeout=600,
                       cwd=str(__import__("pathlib").Path(__file__).parent.parent))
    assert r.returncode == 0, r.stderr[-1500:]
    hp, he, hev, sp, se, sev = json.loads(r.stdout.strip().splitlines()[-1])
    assert hp == sp and he == se and hev == sev


def test_evaluation_count_parity_early_single_pattern():
    """Seed bug: the host loop broke early on an AMI == 1 child (charging
    fewer evaluations than the device sweep's len(SP)).  Counts now agree
    even when the single-pattern child is the FIRST candidate."""
    pytest.importorskip("jax")
    # dropping property a (lowest id -> first candidate) leaves {b, c}
    # shared by all entities: AMI == 1 on the first child of sweep 1
    t = []
    for i in range(4):
        e = f"e{i}"
        t += [(e, "a", f"u{i}"), (e, "b", "y"), (e, "c", "z"),
              (e, "rdf:type", "C")]
    store = TripleStore.from_triples(t)
    C = store.dict.lookup("C")
    host = Compactor(detector="gfsp", backend="host").detect(store, C)
    dev = Compactor(detector="gfsp", backend="device").detect(store, C)
    assert host.ami == 1 and set(host.props) == set(dev.props)
    # 1 (initial S) + 3 (full first sweep, no early break) = 4
    assert host.evaluations == dev.evaluations == 4


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_registry_resolution_and_errors():
    assert get_backend("host").name == "host"
    b = get_backend("device", use_kernel=False)
    assert b.use_kernel is False
    assert get_backend(b) is b           # instances pass through
    with pytest.raises(KeyError, match="unknown execution backend"):
        get_backend("tpu-v9")
    with pytest.raises(KeyError, match="unknown detector"):
        get_detector("magic")
    with pytest.raises(TypeError):
        get_backend(42)


def test_register_custom_detector():
    class Fixed:
        name = "fixed"

        def __init__(self, props=()):
            self.props = props

        def detect(self, store, class_id, *, backend=None, props=None):
            from repro.api.backends import HostBackend
            from repro.api.detectors import _result
            import time
            t0 = time.perf_counter()
            stats = store.class_stats(class_id)
            best = HostBackend().evaluate(
                store, class_id, tuple(self.props),
                int(stats.properties.shape[0]), stats.n_instances)
            return _result(store, class_id, best, stats.n_instances, 1, 1, t0)

    register_detector("fixed", Fixed)
    store = figure1_graph()
    C = store.dict.lookup("C")
    p1, p2 = store.dict.lookup("p1"), store.dict.lookup("p2")
    res = Compactor(detector="fixed",
                    detector_opts={"props": (p1, p2)}).detect(store, C)
    assert set(res.props) == {p1, p2}


def test_gspan_baseline_agrees_with_efsp():
    store = figure1_graph()
    C = store.dict.lookup("C")
    e = Compactor(detector="efsp").detect(store, C)
    g = Compactor(detector="gspan").detect(store, C)
    assert set(g.props) == set(e.props)
    assert g.edges == e.edges
    # gspan scores only mined subsets; efsp scans every combination
    assert g.evaluations <= e.evaluations


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def test_planner_ranks_classes_by_predicted_savings():
    store = _sensor(600, seed=8, n_sensors=10)
    plan = Compactor().plan(store)
    assert len(plan) == 2
    savings = [e.predicted_savings for e in plan]
    assert savings == sorted(savings, reverse=True)
    assert all(s > 0 for s in savings)
    by_class = {store.dict.term(e.class_id): e for e in plan}
    _, a5 = property_set_ids(store, "A5")
    _, a8 = property_set_ids(store, "A8")
    assert set(by_class["ssn:Observation"].props) == set(a5)
    assert set(by_class["ssn:Measurement"].props) == set(a8)


def test_planner_skips_overhead_class():
    """Fig. 7b: every entity its own pattern -> factorization only adds
    edges; the planner must refuse to execute it."""
    store = figure7b_graph()
    comp = Compactor()
    plan = comp.plan(store)
    assert len(plan) == 0
    report = comp.run(store)
    assert report.graph.n_triples == store.n_triples
    assert report.pct_savings_triples == 0.0


def test_explicit_plan_keeps_order_and_matches_core():
    store = _sensor(300, seed=4)
    cid, a8 = property_set_ids(store, "A8")
    rep = Compactor().execute(store,
                              CompactionPlan.explicit([(cid, a8)]))
    assert len(rep.factorizations) == 1
    res = rep.factorizations[0]
    from repro.core.factorize import _factorize
    ref = _factorize(store, cid, a8)
    assert res.nle_before == ref.nle_before
    assert res.nle_after == ref.nle_after
    assert res.pct_savings_nle == ref.pct_savings_nle


def test_execute_is_transactional_input_untouched():
    store = _sensor(200, seed=6)
    before = store.spo.copy()
    rep = Compactor().run(store)
    assert rep.n_triples_after < rep.n_triples_before
    np.testing.assert_array_equal(store.spo, before)


# ---------------------------------------------------------------------------
# overlapping classes (satellite: factorize_classes coverage)
# ---------------------------------------------------------------------------

def _overlap_graph():
    """e0..e2 are BOTH Observation-like (A) and Measurement-like (B);
    e3, e4 are B only.  A-props p1/p2 shared, B-props q1/q2 shared."""
    t = []
    for i in range(3):
        e = f"e{i}"
        t += [(e, "rdf:type", "A"), (e, "rdf:type", "B"),
              (e, "p1", "x"), (e, "p2", "y"),
              (e, "q1", "v"), (e, "q2", "w")]
    for i in range(3, 5):
        e = f"e{i}"
        t += [(e, "rdf:type", "B"), (e, "q1", "v"), (e, "q2", "w")]
    return TripleStore.from_triples(t)


def test_factorize_classes_overlapping_entities_lossless():
    store = _overlap_graph()
    A, B = store.dict.lookup("A"), store.dict.lookup("B")
    pa = [store.dict.lookup(k) for k in ("p1", "p2")]
    pb = [store.dict.lookup(k) for k in ("q1", "q2")]
    g, results = factorize_classes(store, [(A, pa), (B, pb)])
    assert len(results) == 2
    # class A factorization absorbed e0..e2 (one shared star pattern);
    # their type-B edges stay raw (only the class under factorization
    # moves to the surrogate), so B then factorizes ALL five entities
    assert len(results[0].surrogates) == 1
    assert len(results[1].surrogates) == 1
    # overlapping entities carry one instanceOf pointer per class
    e0 = store.dict.lookup("e0")
    inst = g.spo[(g.spo[:, 0] == e0) & (g.spo[:, 1] == g.INSTANCE_OF)]
    assert inst.shape[0] == 2
    a = semantic_triples(store)
    b = semantic_triples(g)
    assert a.shape == b.shape and (a == b).all()


def test_compactor_run_overlapping_classes_lossless():
    store = _overlap_graph()
    rep = Compactor(min_predicted_savings=-10_000).run(store)
    a = semantic_triples(store)
    b = semantic_triples(rep.graph)
    assert a.shape == b.shape and (a == b).all()


# ---------------------------------------------------------------------------
# incremental updates
# ---------------------------------------------------------------------------

def _obs_triples(name, phenom="Temperature", sensor="sensor/1", t="time/9"):
    return [(name, "rdf:type", "ssn:Observation"),
            (name, "ssn:observedProperty", f"phenom/{phenom}"),
            (name, "ssn:procedure", sensor),
            (name, "ssn:generatedBy", sensor)]


def test_update_requires_prior_run():
    with pytest.raises(RuntimeError):
        Compactor().update([])


def test_update_reuses_existing_surrogate():
    store = _sensor(400, seed=9, include_result_links=False, n_sensors=10)
    comp = Compactor()
    rep = comp.run(store)
    n_before = comp.graph.n_triples
    # clone an existing observation's detected-SP tuple -> link, not mint
    obs_cid = store.dict.lookup("ssn:Observation")
    sp = sorted(rep.detections[obs_cid].props)
    ents, objmat = store.object_matrix(obs_cid, sp)
    row = {p: int(o) for p, o in zip(sp, objmat[0])}
    term = store.dict.term
    up = comp.update(
        [("obs/clone", "rdf:type", "ssn:Observation")] +
        [("obs/clone", term(p), term(o)) for p, o in row.items()])
    assert up.n_entities_absorbed == 1
    assert up.n_new_surrogates == 0
    assert up.n_surrogates_reused == 1
    # absorbed entity carries ONE instanceOf edge and no direct SP edges
    g = comp.graph
    e = g.dict.lookup("obs/clone")
    mine = g.spo[g.spo[:, 0] == e]
    assert mine.shape[0] == 1 and mine[0, 1] == g.INSTANCE_OF
    # the only new triple in G' is that pointer edge
    assert g.n_triples == n_before + 1


def test_update_novel_pattern_mints_then_reuses():
    store = _sensor(300, seed=12, include_result_links=False)
    comp = Compactor()
    comp.run(store)
    novel = _obs_triples("obs/n0", sensor="sensor/brand-new") + \
        [("obs/n0", "ssn:samplingTime", "time/0")]
    up1 = comp.update(novel)
    assert up1.n_new_surrogates == 1 and up1.n_surrogates_reused == 0
    # a second entity with the same novel tuple reuses the fresh surrogate
    up2 = comp.update(_obs_triples("obs/n1", sensor="sensor/brand-new") +
                      [("obs/n1", "ssn:samplingTime", "time/1")])
    assert up2.n_new_surrogates == 0 and up2.n_surrogates_reused == 1


def test_update_incomplete_molecule_stays_raw_until_completed():
    store = _sensor(300, seed=14, include_result_links=False)
    comp = Compactor()
    comp.run(store)
    # batch 1: type + one A5 property only -> molecule incomplete
    up1 = comp.update([("obs/p", "rdf:type", "ssn:Observation"),
                       ("obs/p", "ssn:observedProperty",
                        "phenom/Temperature")])
    assert up1.n_entities_absorbed == 0
    e = comp.graph.dict.lookup("obs/p")
    assert (comp.graph.spo[:, 0] == e).sum() == 2     # still raw
    # batch 2 completes the molecule -> absorbed now
    up2 = comp.update([("obs/p", "ssn:procedure", "sensor/2"),
                       ("obs/p", "ssn:generatedBy", "sensor/2")])
    assert up2.n_entities_absorbed == 1


def test_update_closure_equals_full_recompute():
    """Incrementally updated G' and a from-scratch factorization of
    G + inserts have the same semantic closure (Def. 4.10/4.11)."""
    store = _sensor(350, seed=17, include_result_links=False)
    comp = Compactor()
    comp.run(store)
    batch = (_obs_triples("obs/u0", sensor="sensor/0") +
             [("obs/u0", "ssn:samplingTime", "time/2")] +
             _obs_triples("obs/u1", sensor="sensor/xx") +
             [("obs/u1", "ssn:samplingTime", "time/3"),
              ("meas/u0", "rdf:type", "ssn:Measurement"),
              ("meas/u0", "ssn:value", "val/0"),
              ("meas/u0", "ssn:unit", "unit/Temperature")])
    comp.update(batch)
    # reference: the full graph with the same inserts applied raw
    full = store.copy()
    d = full.dict
    full.add_ids(np.asarray([[d.id(s), d.id(p), d.id(o)]
                             for s, p, o in batch], np.int32))
    a = semantic_triples(full)
    b = semantic_triples(comp.graph)
    assert a.shape == b.shape and (a == b).all()
    # and a fresh Compactor over the full graph compacts at least as well,
    # but the incremental graph must stay strictly smaller than raw
    assert comp.graph.n_triples < full.n_triples


# ---------------------------------------------------------------------------
# deprecated shims + bulk minting
# ---------------------------------------------------------------------------

def test_deprecated_wrappers_warn_and_agree():
    from repro.core import efsp, factorize, gfsp
    store = figure1_graph()
    C = store.dict.lookup("C")
    with pytest.warns(DeprecationWarning):
        g = gfsp(store, C)
    with pytest.warns(DeprecationWarning):
        e = efsp(store, C)
    assert set(g.props) == set(e.props) and g.edges == e.edges
    with pytest.warns(DeprecationWarning):
        f = factorize(store, C, g.props)
    assert f.n_triples_after < f.n_triples_before


def test_deprecated_shims_identical_to_compactor_path():
    """The core.gfsp/efsp/factorize free functions must warn AND return
    results identical to the Compactor pipeline they shim over."""
    from repro.core import efsp as efsp_fn, factorize as fact_fn, \
        gfsp as gfsp_fn
    store = _sensor(250, seed=19)
    cid = store.dict.lookup("ssn:Observation")

    ref = Compactor(detector="gfsp", backend="host").detect(store, cid)
    with pytest.warns(DeprecationWarning):
        old = gfsp_fn(store, cid)
    assert (old.props, old.edges, old.ami, old.am, old.iterations,
            old.evaluations) == (ref.props, ref.edges, ref.ami, ref.am,
                                 ref.iterations, ref.evaluations)

    pytest.importorskip("jax")
    dev_ref = Compactor(detector="gfsp", backend="device").detect(store, cid)
    with pytest.warns(DeprecationWarning):
        dev_old = gfsp_fn(store, cid, device_sweep=True)
    assert (dev_old.props, dev_old.edges, dev_old.evaluations) == \
        (dev_ref.props, dev_ref.edges, dev_ref.evaluations)

    e_ref = Compactor(detector="efsp").detect(store, cid)
    with pytest.warns(DeprecationWarning):
        e_old = efsp_fn(store, cid)
    assert (e_old.props, e_old.edges, e_old.ami) == \
        (e_ref.props, e_ref.edges, e_ref.ami)

    f_ref = Compactor().execute(
        store, CompactionPlan.explicit([(cid, ref.props)]))
    with pytest.warns(DeprecationWarning):
        f_old = fact_fn(store, cid, ref.props)
    assert f_old.n_triples_after == f_ref.n_triples_after
    np.testing.assert_array_equal(f_old.graph.spo, f_ref.graph.spo)


def test_termdict_ids_bulk_matches_sequential():
    seq, bulk = TermDict(), TermDict()
    terms = [f"t/{i}" for i in range(50)]
    seq_ids = [seq.id(t) for t in terms]
    np.testing.assert_array_equal(bulk.ids(terms), seq_ids)
    # mixed seen/unseen + duplicates inside one batch
    mixed = ["t/3", "new/a", "t/7", "new/a", "new/b"]
    got = bulk.ids(mixed)
    assert got[0] == 3 and got[2] == 7
    assert got[1] == got[3] == 50        # duplicate minted once
    assert got[4] == 51
    assert [seq.id(t) for t in mixed] == got.tolist()
