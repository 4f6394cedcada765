"""Scenario registry + sweep runner: determinism, CSV replay, comm cache."""
import json
import random

import pytest

from repro.configs import ARCHS
from repro.core import (ClusterTopology, CommModel, load_csv_trace,
                        make_batch_trace, make_bursty_trace,
                        make_mixed_trace, save_csv_trace)
from repro.core.topology import Placement
from repro.experiments import (SCENARIOS, ContentionSchedule, Scenario,
                               SimOverrides, artifact_json, get_scenario,
                               run_one, scenario_from_csv)
from repro.experiments.sweep import sweep

ARCHS_L = list(ARCHS.values())


# -- scenario registry -------------------------------------------------------

def test_registry_covers_paper_and_new_regimes():
    for name in ("paper-batch", "paper-poisson", "hetero-racks",
                 "contended-network", "bursty-diurnal", "flash-crowd",
                 "datacenter-mix", "straggler", "smoke", "csv-replay",
                 "congested-spine", "oversubscribed-uplinks",
                 "consolidate-vs-scatter"):
        assert name in SCENARIOS


@pytest.mark.parametrize(
    "name", sorted(n for n in SCENARIOS  # csv kinds need a csv_path
                   if not SCENARIOS[n].trace.endswith("csv")))
def test_every_scenario_builds(name):
    sc = get_scenario(name).with_overrides(n_jobs=6)
    cluster = sc.build_cluster()
    assert cluster.total_gpus > 0
    jobs = sc.build_trace(ARCHS_L, seed=0)
    assert len(jobs) == 6
    assert all(jobs[i].arrival <= jobs[i + 1].arrival
               for i in range(len(jobs) - 1))


def test_unknown_scenario_is_a_clear_error():
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("nope")


def test_csv_scenario_requires_path():
    with pytest.raises(ValueError, match="csv_path"):
        get_scenario("csv-replay").build_trace(ARCHS_L, seed=0)


def test_contended_network_scales_bandwidth():
    base = get_scenario("paper-batch").build_comm(ARCHS_L)
    cont = get_scenario("contended-network").build_comm(ARCHS_L)
    pl = Placement(((0, 4), (9, 4)))  # spans racks -> network tier
    assert (cont.allreduce_time("yi-9b", pl, 8, 8)
            > base.allreduce_time("yi-9b", pl, 8, 8))


def test_heterogeneous_rack_topology():
    cl = ClusterTopology(rack_sizes=(8, 4, 2), gpus_per_machine=8)
    assert cl.total_gpus == (8 + 4 + 2) * 8
    assert cl.max_rack_capacity == 64
    assert cl.rack_free(1) == 32 and cl.rack_free(2) == 16
    # a rack-level allocation bigger than the small racks lands in rack 0
    p = cl.allocate(40, "rack")
    assert p is not None and p.tier(cl.machines_per_rack) == "rack"
    assert all(m < cl.machines_per_rack for m in p.machines())
    cl.release(p)
    # ghost machine slots (missing machines of short racks) are never used
    big = cl.allocate(cl.total_gpus, "network")
    assert big.n_gpus == cl.total_gpus and cl.free_gpus() == 0
    cl.release(big)
    assert cl.free_gpus() == cl.total_gpus


# -- single-cell runner ------------------------------------------------------

def test_run_one_artifact_schema_and_determinism():
    ov = SimOverrides(n_jobs=20)
    art1 = run_one("smoke", policy="dally", seed=0, overrides=ov)
    art2 = run_one("smoke", policy="dally", seed=0, overrides=ov)
    assert art1["schema"].startswith("repro.experiments.artifact/")
    for key in ("scenario", "policy", "seed", "config", "metrics"):
        assert key in art1
    assert artifact_json(art1) == artifact_json(art2)
    assert art1["metrics"]["n_finished"] == 20
    # volatile timing never leaks into the canonical serialization
    art1["wall_s"] = 123.0
    assert artifact_json(art1) == artifact_json(art2)


def test_run_one_scenario_overrides():
    art = run_one("paper-batch", policy="gandiva", seed=1,
                  overrides=SimOverrides(n_jobs=15, n_racks=2))
    assert art["config"]["n_jobs"] == 15
    assert art["config"]["n_racks"] == 2
    assert art["metrics"]["n_finished"] == 15


def test_n_racks_override_beats_rack_sizes():
    """Regression: --racks on a heterogeneous scenario must actually change
    the simulated cluster (and the recorded provenance), not be silently
    swallowed by rack_sizes."""
    sc = get_scenario("hetero-racks").with_overrides(n_racks=2)
    cluster = sc.build_cluster()
    assert cluster.n_racks == 2
    assert cluster.total_gpus == 2 * 8 * 8
    assert sc.config_dict()["rack_sizes"] is None


def test_contention_only_hits_real_machines():
    """Regression: contention windows must land on machines that hold GPUs,
    not on the empty stride slots of heterogeneous topologies."""
    sc = Scenario("t-cont", rack_sizes=(8, 2), trace="batch", n_jobs=4,
                  contention=ContentionSchedule(scope=0.5,
                                                horizon=24 * 3600.0))
    cluster = sc.build_cluster()
    real = {m for m in range(cluster.n_machines) if cluster.free[m] > 0}
    events = sc.contention.events(sorted(real), seed=0)
    assert events
    assert {m for _, m, _ in events} <= real
    assert max(1, int(0.5 * len(real))) == len(
        {m for t, m, f in events if t == 0.0 and f != 1.0})


def test_slowdown_schedule_does_not_extend_timeline():
    """Regression: pending SLOWDOWN events after the last completion must
    not keep the round clock (and idle timeline samples) running, which
    diluted avg_utilization for short contended runs."""
    far = [(t * 3600.0, 0, 2.0) for t in range(1, 14 * 24)]
    sc = Scenario("t-slow", n_racks=1, trace="batch", n_jobs=3,
                  slowdown_events=tuple(far))
    art_slow = run_one(sc, policy="dally", seed=0)
    sc_ref = Scenario("t-ref", n_racks=1, trace="batch", n_jobs=3)
    art_ref = run_one(sc_ref, policy="dally", seed=0)
    m_slow, m_ref = art_slow["metrics"], art_ref["metrics"]
    assert m_slow["n_finished"] == 3
    # the timeline ends near the makespan, not at the 14-day event horizon
    assert m_slow["timeline"]["t"][-1] <= m_slow["makespan"] + 2 * 300.0
    assert m_slow["avg_utilization"] == pytest.approx(
        m_ref["avg_utilization"], rel=0.5)


# -- parallel sweep ----------------------------------------------------------

def _sweep_files(out_dir):
    return sorted(p for p in out_dir.iterdir() if "seed" in p.name)


def test_sweep_deterministic_across_worker_counts(tmp_path):
    """Same seeds -> byte-identical artifacts at any worker count."""
    kw = dict(n_jobs=15)
    idx1 = sweep(["smoke"], ["dally", "gandiva"], [0, 1], workers=1,
                 out_dir=tmp_path / "w1", **kw)
    idx2 = sweep(["smoke"], ["dally", "gandiva"], [0, 1], workers=2,
                 out_dir=tmp_path / "w2", **kw)
    f1 = _sweep_files(tmp_path / "w1")
    f2 = _sweep_files(tmp_path / "w2")
    assert [p.name for p in f1] == [p.name for p in f2]
    assert len(f1) == 4
    for a, b in zip(f1, f2):
        assert a.read_bytes() == b.read_bytes()
    assert len(idx1["runs"]) == len(idx2["runs"]) == 4
    # distinct seeds genuinely vary the workload
    arts = [json.loads(p.read_text()) for p in f1]
    dally = [a for a in arts if a["policy"] == "dally"]
    assert dally[0]["metrics"]["makespan"] != dally[1]["metrics"]["makespan"]


def test_sweep_contention_override_emits_v2(tmp_path):
    """--contention fair-share flips every cell to a schema-v2 artifact and
    is recorded in the index provenance."""
    idx = sweep(["smoke"], ["dally"], [0], workers=1, out_dir=tmp_path,
                n_jobs=10, contention="fair-share")
    art = json.loads((tmp_path / idx["runs"][0]["file"]).read_text())
    assert art["schema"] == "repro.experiments.artifact/v2"
    assert art["config"]["contention_mode"] == "fair-share"
    assert idx["overrides"]["contention"] == "fair-share"


def test_sweep_index_headlines_match_artifacts(tmp_path):
    sweep(["smoke"], ["dally"], [0], workers=1, out_dir=tmp_path,
          n_jobs=12)
    idx = json.loads((tmp_path / "sweep.json").read_text())
    run = idx["runs"][0]
    art = json.loads((tmp_path / run["file"]).read_text())
    assert run["makespan"] == art["metrics"]["makespan"]
    assert run["n_finished"] == art["metrics"]["n_finished"] == 12


# -- CSV trace replay --------------------------------------------------------

def test_csv_trace_round_trip(tmp_path):
    jobs = make_batch_trace(ARCHS_L, n_jobs=25, seed=4)
    path = tmp_path / "trace.csv"
    save_csv_trace(jobs, path)
    loaded = load_csv_trace(path, ARCHS_L)
    assert len(loaded) == len(jobs)
    for a, b in zip(jobs, loaded):
        assert (a.job_id, a.model, a.n_gpus, a.total_iters) == \
               (b.job_id, b.model, b.n_gpus, b.total_iters)
        assert a.compute_time_per_iter == b.compute_time_per_iter
        assert a.arrival == b.arrival and a.skew == b.skew


def test_csv_philly_style_columns(tmp_path):
    path = tmp_path / "philly.csv"
    path.write_text("jobid,submit_time,num_gpus,duration\n"
                    "7,0,8,7200\n3,60,16,3600\n")
    jobs = load_csv_trace(path, ARCHS_L)
    assert [j.job_id for j in jobs] == [7, 3]
    assert [j.n_gpus for j in jobs] == [8, 16]
    for j in jobs:
        assert j.total_iters > 0 and j.compute_time_per_iter > 0
        assert j.model in ARCHS  # deterministically assigned an arch


def test_csv_real_philly_ids_and_datetimes(tmp_path):
    """Regression: real Philly traces use application_... job ids and
    'YYYY-mm-dd HH:MM:SS' submit times; both must parse, with arrivals
    shifted so the first submission is t=0."""
    path = tmp_path / "philly_real.csv"
    path.write_text(
        "jobid,submit_time,num_gpus,duration\n"
        "application_1506638472019_10258,2017-10-03 05:51:56,8,7200\n"
        "application_1506638472019_10270,2017-10-03 06:21:56,4,600\n")
    jobs = load_csv_trace(path, ARCHS_L)
    assert [j.arrival for j in jobs] == [0.0, 30 * 60.0]
    assert [j.job_id for j in jobs] == [0, 1]  # row-index fallback
    assert [j.n_gpus for j in jobs] == [8, 4]


def test_csv_foreign_model_names_are_remapped(tmp_path):
    """Regression: a CSV naming models outside our arch zoo must not
    KeyError inside CommModel mid-simulation — jobs get renamed to the
    deterministically assigned architecture."""
    path = tmp_path / "foreign.csv"
    path.write_text("jobid,submit_time,num_gpus,duration,model\n"
                    "1,0,4,3600,resnet50\n2,10,8,7200,vgg16\n")
    jobs = load_csv_trace(path, ARCHS_L)
    assert all(j.model in ARCHS for j in jobs)
    art = run_one(scenario_from_csv(str(path)), policy="dally", seed=0,
                  overrides=SimOverrides(n_racks=2))
    assert art["metrics"]["n_finished"] == 2


def test_csv_colliding_job_ids_are_renumbered(tmp_path):
    """Regression: a numeric id colliding with a row-index fallback (or
    duplicate ids in the file) would corrupt the simulator's job table."""
    path = tmp_path / "collide.csv"
    path.write_text("jobid,submit_time,num_gpus,duration\n"
                    "1,0,2,3600\napplication_xyz,10,2,3600\n")
    jobs = load_csv_trace(path, ARCHS_L)
    ids = [j.job_id for j in jobs]
    assert len(set(ids)) == len(ids) == 2


def test_oversized_job_rejected_not_wedged():
    """Regression: a job demanding more GPUs than the whole cluster must be
    rejected up front — admitting it wedges the round loop forever."""
    from repro.core import ClusterSimulator, ClusterTopology, CommModel
    from repro.core.policies import make_policy
    from repro.core.job import Job
    sim = ClusterSimulator(ClusterTopology(n_racks=1),
                           make_policy("dally"),
                           CommModel.from_configs(ARCHS_L))
    sim.submit(Job(job_id=0, model="yi-9b", n_gpus=128, total_iters=10,
                   compute_time_per_iter=0.1))
    sim.submit(Job(job_id=1, model="yi-9b", n_gpus=8, total_iters=10,
                   compute_time_per_iter=0.1))
    res = sim.run()  # must terminate
    assert res["n_rejected"] == 1
    assert res["n_finished"] == 1


def test_csv_scenario_end_to_end(tmp_path):
    jobs = make_batch_trace(ARCHS_L, n_jobs=12, seed=2)
    path = tmp_path / "replay.csv"
    save_csv_trace(jobs, path)
    art = run_one(scenario_from_csv(str(path)), policy="dally", seed=0)
    assert art["metrics"]["n_finished"] == 12


# -- new trace generators ----------------------------------------------------

def test_bursty_trace_flash_crowds_cluster_arrivals():
    jobs = make_bursty_trace(ARCHS_L, n_jobs=60, seed=5, flash_crowds=2,
                             flash_fraction=0.5, flash_window=600.0)
    arrivals = sorted(j.arrival for j in jobs)
    assert len(jobs) == 60
    # at least one 600s window holds >= 15 jobs (a flash crowd)
    burst = max(sum(1 for a in arrivals if t <= a <= t + 600.0)
                for t in arrivals)
    assert burst >= 15


def test_mixed_trace_has_both_classes():
    jobs = make_mixed_trace(ARCHS_L, n_jobs=120, seed=6)
    small = [j for j in jobs if j.n_gpus <= 8]
    large = [j for j in jobs if j.n_gpus >= 16]
    assert small and large
    assert len(small) > len(large)  # datacenter-style skew
    assert max(j.n_gpus for j in jobs) <= 128


# -- comm-model cache --------------------------------------------------------

def test_comm_cache_matches_uncached():
    """Memoized iteration_time must equal the uncached computation across
    random placements, models, and calibrations."""
    cached = CommModel.from_configs(ARCHS_L)
    uncached = CommModel.from_configs(ARCHS_L, cache_size=0)
    rng = random.Random(0)
    names = sorted(n for n in ARCHS)
    for _ in range(200):
        name = rng.choice(names)
        n_machines = rng.randint(1, 6)
        ms = rng.sample(range(24), n_machines)
        alloc = tuple(sorted((m, rng.randint(1, 8)) for m in ms))
        pl = Placement(alloc)
        compute = rng.uniform(0.01, 2.0)
        assert (cached.iteration_time(name, compute, pl, 8, 8)
                == uncached.iteration_time(name, compute, pl, 8, 8))
    assert cached.cache_hits > 0 and uncached.cache_hits == 0


def test_comm_cache_invalidated_by_calibration():
    """A calibration given at construction reaches the memoized latencies:
    cached equals a cache-less twin with and without one, and only the
    calibrated arch's cross-rack latency moves."""
    cal = {"yi-9b": 2.0}
    plain = CommModel.from_configs(ARCHS_L)
    scaled = CommModel.from_configs(ARCHS_L, calibration=cal)
    twins = ((plain, CommModel.from_configs(ARCHS_L, cache_size=0)),
             (scaled, CommModel.from_configs(ARCHS_L, calibration=cal,
                                             cache_size=0)))
    placements = [Placement(((0, 4), (1, 4))), Placement(((0, 8),)),
                  Placement(((0, 4), (9, 4)))]  # machine 9 is in rack 1
    for _ in range(2):  # the second pass reads the memo
        for cached, uncached in twins:
            for name in ("yi-9b", "qwen3-1.7b"):
                for pl in placements:
                    assert (cached.allreduce_time(name, pl, 8, 8)
                            == uncached.allreduce_time(name, pl, 8, 8))
                    assert (cached.iteration_time(name, 0.5, pl, 8, 8)
                            == uncached.iteration_time(name, 0.5, pl, 8, 8))
    assert plain.cache_hits > 0 and scaled.cache_hits > 0
    cross = placements[2]
    assert cross.tier(8) == "network"
    assert (scaled.allreduce_time("yi-9b", cross, 8, 8)
            > plain.allreduce_time("yi-9b", cross, 8, 8))
    assert (scaled.allreduce_time("qwen3-1.7b", cross, 8, 8)
            == plain.allreduce_time("qwen3-1.7b", cross, 8, 8))
