"""PR 9 compute plane: device-typed placement, kernel tasks, sharded
ParamSet lifecycle, unschedulable sealing, DES heterogeneous fleet."""
import threading
import time

import numpy as np
import pytest

from repro import core
from repro.compute import (ParamSet, UnschedulableTaskError, device_keys,
                           kernel_task)
from repro.core import profiler
from repro.core.simulator import SimCosts, heterogeneous_fleet


@pytest.fixture()
def hetero():
    """One gpu-typed node + two cpu-only nodes, explicit topology
    (strict placement: impossible requests seal, they don't park)."""
    c = core.init(node_resources=[{"cpu": 2.0, "gpu": 1.0},
                                  {"cpu": 2.0}, {"cpu": 2.0}])
    yield c
    core.shutdown()


@core.remote(resources={"gpu": 1.0})
def where_am_i():
    from repro.core.worker import current_node
    return current_node().node_id, threading.current_thread().name


@core.remote
def cpu_where():
    from repro.core.worker import current_node
    return current_node().node_id


# ------------------------------------------------------------ placement

def test_gpu_task_lands_only_on_gpu_node(hetero):
    ids = {core.get(where_am_i.submit(), timeout=30)[0]
           for _ in range(8)}
    assert ids == {0}        # node 0 is the only gpu-typed node


def test_gpu_task_runs_on_device_lane(hetero):
    _, thread = core.get(where_am_i.submit(), timeout=30)
    assert thread.startswith("lane-gpu")


def test_cpu_tasks_spread_while_gpu_pinned(hetero):
    refs = [cpu_where.submit() for _ in range(24)]
    nodes = set(core.get(refs, timeout=30))
    assert len(nodes) > 1    # the cpu stream is not funneled to node 0


def test_capacity_released_on_completion(hetero):
    # gpu capacity is 1.0: 6 sequentially-completing tasks all fit only
    # if every completion releases its grant
    refs = [where_am_i.submit() for _ in range(6)]
    assert {n for n, _ in core.get(refs, timeout=60)} == {0}
    node = hetero.nodes[0]
    assert node._avail["gpu"] == pytest.approx(node.capacity["gpu"])


def test_capacity_released_on_failure(hetero):
    @core.remote(resources={"gpu": 1.0}, max_retries=0)
    def boom():
        raise ValueError("kernel exploded")

    for _ in range(3):
        with pytest.raises(core.TaskError):
            core.get(boom.submit(), timeout=30)
    node = hetero.nodes[0]
    assert node._avail["gpu"] == pytest.approx(node.capacity["gpu"])
    # the device is still usable after failures
    assert core.get(where_am_i.submit(), timeout=30)[0] == 0


def test_unschedulable_seals_promptly(hetero):
    # regression: a request no declared node can ever satisfy must seal
    # with a typed error at placement time, not park forever
    @core.remote(resources={"tpu": 4.0})
    def never():
        return 1

    t0 = time.perf_counter()
    with pytest.raises(UnschedulableTaskError):
        core.get(never.submit(), timeout=30)
    assert time.perf_counter() - t0 < 5.0
    stats = profiler.summarize(hetero.gcs)
    assert stats["tasks_unschedulable"] >= 1


def test_elastic_cluster_still_parks():
    # without an explicit topology the old contract holds: park, then
    # drain when a capable node joins
    c = core.init(num_nodes=1, workers_per_node=2)
    try:
        r = where_am_i.submit()
        done, _ = core.wait([r], timeout=0.3)
        assert not done                       # parked, not sealed
        c.add_node({"cpu": 2.0, "gpu": 1.0})
        nid, _ = core.get(r, timeout=30)
        assert nid == 1
    finally:
        core.shutdown()


def test_device_keys_helper():
    assert device_keys({"cpu": 4.0, "gpu": 1.0}) == ("gpu",)
    assert device_keys({"cpu": 4.0, "gpu": 0.0}) == ()
    assert device_keys({"tpu": 2.0, "accel": 1.0}) == ("tpu", "accel")


@pytest.mark.parametrize("declare", ["init", "add_node"])
def test_process_backend_refuses_device_capacity(declare):
    """A chip belongs to one process: device capacity on the process
    backend is a typed error, raised before any worker is spawned."""
    if declare == "init":
        with pytest.raises(core.ProcessBackendDeviceError):
            core.init(node_resources=[{"cpu": 1.0}, {"cpu": 1.0, "tpu": 1.0}],
                      backend="process")
        assert core.api._global["cluster"] is None
        return
    c = core.init(num_nodes=1, workers_per_node=1, backend="process")
    try:
        with pytest.raises(core.ProcessBackendDeviceError):
            c.add_node({"cpu": 1.0, "gpu": 1.0})
        assert len(c.nodes) == 1
    finally:
        core.shutdown()


# ---------------------------------------------------------- kernel tasks

def test_kernel_task_runs_and_profiles(hetero):
    jnp = pytest.importorskip("jax.numpy")

    def mm(x):
        return jnp.tanh(x @ x.T)

    x = np.random.default_rng(0).standard_normal((16, 16)).astype(
        np.float32)
    kt = kernel_task(mm, warmup_args=(jnp.asarray(x),))
    out = core.get(kt.submit(x), timeout=60)
    np.testing.assert_allclose(np.asarray(out), np.tanh(x @ x.T),
                               rtol=1e-5)
    stats = profiler.summarize(hetero.gcs)
    assert stats["kernel_tasks"] >= 1
    assert stats["kernel_task_ms_mean"] > 0


def test_kernel_task_decorator_defaults():
    @kernel_task
    def double(x):
        return x * 2

    assert double.resources == {"gpu": 1.0}


# ------------------------------------------------------------- ParamSet

def _make_params(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {"emb": rng.standard_normal((64, 32)).astype(np.float32),
            "groups": tuple(
                {"w": (scale * rng.standard_normal((32, 32))
                       ).astype(np.float32),
                 "b": np.zeros(32, np.float32)}
                for _ in range(3))}


def test_paramset_publish_fetch_roundtrip(hetero):
    params = _make_params()
    ps = ParamSet.publish("m", params, num_shards=2)
    assert ps.version == 1 and len(ps.shard_ids) == 2

    got = ParamSet.latest("m").fetch()
    np.testing.assert_array_equal(got["emb"], params["emb"])
    assert isinstance(got["groups"], tuple) and len(got["groups"]) == 3
    for a, b in zip(got["groups"], params["groups"]):
        np.testing.assert_array_equal(a["w"], b["w"])


def test_paramset_fetch_is_zero_copy(hetero):
    ps = ParamSet.publish("z", _make_params(), num_shards=1)
    fresh = ParamSet.latest("z")
    got = fresh.fetch()
    buf = fresh._shard(0, timeout=10)
    assert np.shares_memory(got["emb"], buf)


def test_paramset_version_swap_and_gc(hetero):
    ps1 = ParamSet.publish("v", _make_params(seed=1), num_shards=2)
    old_shards = ps1.shard_ids
    ps2 = ParamSet.publish("v", _make_params(seed=2, scale=2.0),
                           num_shards=2)
    assert ps2.version == ps1.version + 1
    assert ParamSet.latest("v").version == ps2.version
    # republish dropped the v1 owning refs: old shards must actually
    # reclaim (refcount zero -> MemoryManager eviction)
    for sid in old_shards:
        assert hetero.memory.wait_reclaimed(sid, timeout=10.0)
    # the new version still fetches after the old one is gone
    got = ParamSet.latest("v").fetch()
    assert got["emb"].shape == (64, 32)


def test_paramset_drop_reclaims(hetero):
    ps = ParamSet.publish("d", _make_params(), num_shards=2)
    ParamSet.drop("d")
    assert ParamSet.latest("d") is None
    for sid in ps.shard_ids:
        assert hetero.memory.wait_reclaimed(sid, timeout=10.0)


def test_paramset_profiler_counters(hetero):
    ParamSet.publish("p", _make_params(), num_shards=1)
    stats = profiler.summarize(hetero.gcs)
    assert stats["param_publishes"] == 1
    assert stats["param_bytes"] > 0


def test_paramset_shard_ref_feeds_tasks(hetero):
    @core.remote
    def nbytes(buf):
        return int(np.asarray(buf).nbytes)

    ps = ParamSet.publish("s", _make_params(), num_shards=2)
    sizes = core.get([nbytes.submit(ps.shard_ref(i)) for i in range(2)],
                     timeout=30)
    assert sum(sizes) == ps.total_bytes


# ------------------------------------------------------------------ DES

def test_des_heterogeneous_zero_misplaced():
    r = heterogeneous_fleet(num_cpu=10, num_gpu=3, num_tasks=400,
                            seed=7, costs=SimCosts())
    assert r["finished"] == 400
    assert r["device_misplaced"] == 0
    assert r["kernel_tasks"] > 0


def test_simcosts_kernel_calibration(tmp_path):
    core_p = tmp_path / "core.json"
    comp_p = tmp_path / "compute.json"
    comp_p.write_text(
        '{"runs": {"pr9": {"kernel_task_e2e": {"p50_us": 1234.0}}},'
        ' "speedup_run": "pr9"}')
    costs = SimCosts.from_microbench(str(core_p),
                                     compute_path=str(comp_p))
    assert costs.kernel_step_s == pytest.approx(1234e-6)
