"""The copied generators: fingerprints for two seeds, and agreement
with the program's own generators they were copied from."""
import hashlib
import json

import pytest

import hadar_bench_path  # noqa: F401  (benchmarks/ on the path)

from hadar_bench import registry, traffic
from hadar_bench.traffic import mtbf, philly


def _fingerprint(dep) -> str:
    rows = [(j.job_id, j.arrival, j.n_workers, j.epochs, j.iters_per_epoch,
             sorted(j.throughput.items()), j.model) for j in dep.jobs]
    nodes = [(n.node_id, sorted(n.gpus.items())) for n in dep.cluster.nodes]
    blob = json.dumps([rows, nodes, dep.faults, dep.max_queue],
                      sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


FINGERPRINTS = {
    ("fig5-2048.storm", 7): "2498f84e16aa0808",
    ("fig5-2048.storm", 2 ** 31 + 11): "93a3b0602f8b7cfb",
    ("sim-60.arrivals", 7): "eaa4bb2d0d8abd1d",
    ("sim-60.arrivals", 2 ** 31 + 11): "eabbe4848b312bf3",
    ("sim-60.hadare-faults", 7): "c5c3917defc5e611",
    ("sim-60.hadare-faults", 2 ** 31 + 11): "d889f9a01ed59b45",
}


def _dep(cell_name, seed):
    bench = registry.benchmark()
    cell = registry.cell(bench, cell_name)
    return traffic.build(registry.config(cell["config"]),
                         registry.mix(cell["traffic"]), seed)


@pytest.mark.parametrize("cell_name,seed", sorted(FINGERPRINTS))
def test_fingerprint(cell_name, seed):
    assert _fingerprint(_dep(cell_name, seed)) == \
        FINGERPRINTS[(cell_name, seed)]


@pytest.mark.parametrize("cell_name", ["fig5-2048.storm", "sim-60.arrivals",
                                       "sim-60.hadare-faults"])
def test_seeds_permute_one_population(cell_name):
    a, b = _dep(cell_name, 7), _dep(cell_name, 8)
    key = lambda j: (j.n_workers, j.epochs, j.model)   # noqa: E731
    assert sorted(map(key, a.jobs)) == sorted(map(key, b.jobs))
    assert sorted(j.arrival for j in a.jobs) == \
        sorted(j.arrival for j in b.jobs)
    assert [key(j) for j in a.jobs] != [key(j) for j in b.jobs]
    if a.faults:
        assert sorted(w[1:] for w in a.faults) == \
            sorted(w[1:] for w in b.faults)


def test_each_replay_takes_its_own_order():
    dep = _dep("sim-60.arrivals", 7)
    key = lambda j: (j.job_id, j.arrival, j.n_workers, j.epochs,  # noqa
                     j.model)
    first, second = dep.replay_jobs(0), dep.replay_jobs(1)
    assert list(map(key, first)) == list(map(key, dep.jobs))
    assert list(map(key, second)) == list(map(key, dep.replay_jobs(1)))
    assert list(map(key, second)) != list(map(key, first))
    assert sorted(k[2:] for k in map(key, second)) == \
        sorted(k[2:] for k in map(key, first))
    assert [j.arrival for j in second] == [j.arrival for j in first]


def test_philly_matches_program_generator():
    from repro.core.trace import philly_trace
    types = ["v100", "p100", "k80"]
    for seed in (0, 3):
        ours = philly.philly_jobs(64, seed, types, all_at_start=False)
        theirs = philly_trace(64, seed=seed, types=types,
                              all_at_start=False)
        assert [(m, w, e, ipe, tp, a) for m, _, w, e, ipe, tp, a in ours] \
            == [(j.model, j.n_workers, j.epochs, j.iters_per_epoch,
                 j.throughput, j.arrival) for j in theirs]


def test_clusters_match_program():
    from repro.core.trace import simulation_cluster
    sim = simulation_cluster()
    assert philly.simulation_nodes() == [(n.node_id, n.gpus)
                                         for n in sim.nodes]
    grown = philly.grown_nodes(2048)
    assert len(grown) == 256 and sum(sum(g.values()) for _, g in grown) \
        == 1024


def test_mtbf_matches_program_failure_model():
    from repro.core.trace import simulation_cluster
    from repro.sim.faults import FailureModel
    cl = simulation_cluster()
    ours = mtbf.mtbf_windows([n.node_id for n in cl.nodes], 12.0, 1800.0,
                             72 * 3600.0, seed=4)
    theirs = FailureModel(mtbf_hours=12.0, recovery_s=1800.0, seed=4,
                          horizon=72 * 3600.0).sample(cl)
    assert ours == [(w.node_id, w.fail_time, w.recover_time)
                    for w in theirs]
    assert mtbf.max_down([(0, 0.0, 2.0), (1, 1.0, 3.0), (2, 3.0, 4.0)]) == 2


def test_large_and_negative_seeds():
    assert traffic.derive_seed(2 ** 40 + 5, "x") != \
        traffic.derive_seed(5, "x")
    assert 0 <= traffic.derive_seed(-3, "x") < 2 ** 32
