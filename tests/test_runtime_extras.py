"""Additional runtime/system coverage: API options,
object-store locality, BSP/hybrid executors, wait edge cases, DES elastic
scaling, simulator latency percentiles."""
import time

import pytest

from repro import core
from repro.core.executors import BSPExecutor, SerialExecutor
from repro.core.simulator import ClusterSim, SimTask


@pytest.fixture()
def cluster():
    c = core.init(num_nodes=3, workers_per_node=2)
    yield c
    core.shutdown()


def test_options_override_resources(cluster):
    @core.remote
    def f():
        return 1
    g = f.options(resources={"cpu": 2.0})
    assert core.get(g.submit()) == 1
    assert g.resources == {"cpu": 2.0}
    assert f.resources == {"cpu": 1.0}


def test_multiple_returns(cluster):
    @core.remote(num_returns=3)
    def three():
        return 1, 2, 3
    a, b, c = three.submit()
    assert core.get([a, b, c]) == [1, 2, 3]


def test_wait_num_returns_capped(cluster):
    @core.remote
    def one():
        return 1
    refs = [one.submit() for _ in range(3)]
    done, pending = core.wait(refs, num_returns=10, timeout=5.0)
    assert len(done) == 3 and not pending


def test_put_get_roundtrip_objects(cluster):
    import numpy as np
    arr = np.arange(1000)
    ref = core.put(arr)
    out = core.get(ref)
    assert (out == arr).all()


def test_object_locality_transfer(cluster):
    """get() from a worker on another node transfers + registers a copy."""
    @core.remote
    def make():
        return list(range(100))

    @core.remote
    def consume(x):
        return sum(x)

    ref = make.submit()
    core.get(ref)
    out = core.get(consume.submit(ref))
    assert out == sum(range(100))
    # after consumption the object may be resident on >= 1 node
    assert len(cluster.gcs.locations(ref.id)) >= 1


def test_bsp_executor_barrier_semantics():
    ex = BSPExecutor(num_workers=4, driver_overhead_s=0.0)
    out = ex.map_stage(lambda x: x * 2, list(range(10)))
    assert out == [x * 2 for x in range(10)]
    ex.shutdown()


def test_serial_executor():
    assert SerialExecutor().map_stage(lambda x: x + 1, [1, 2]) == [2, 3]


def test_des_elastic_add_increases_throughput():
    def run(nodes_late):
        sim = ClusterSim(4, workers_per_node=2, seed=0)
        for i in range(800):
            sim.submit(SimTask(i, 5e-3, i % 4), at=0.0)
        if nodes_late:
            for _ in range(12):
                sim.add_node(2, at=0.05)
        sim.run()
        return max(t.finish_t for t in sim.finished)

    assert run(True) < run(False)


def test_des_latency_percentiles_present():
    sim = ClusterSim(4, workers_per_node=2, seed=0)
    for i in range(100):
        sim.submit(SimTask(i, 1e-3, i % 4), at=0.0)
    sim.run()
    p = sim.latency_percentiles()
    assert set(p) == {"p50", "p90", "p99"} and p["p99"] >= p["p50"]


def test_driver_roundrobin_spreads_nodes(cluster):
    @core.remote
    def where():
        from repro.core.worker import current_node
        time.sleep(0.01)
        return current_node().node_id
    refs = [where.submit() for _ in range(12)]
    assert len(set(core.get(refs))) >= 2
