"""Infrastructure tests: checkpointing (atomicity, async, elastic restore),
trainer resume, optimizer, data prefetcher, HLO analyzer, sharding rules."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (8, 4)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(10, t)
    out = ck.restore(jax.eval_shape(lambda: t))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ck.latest_step() == 10


def test_checkpoint_async_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(s), blocking=False)
        ck.wait()
    assert ck.steps() == [3, 4]


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(5, _tree())
    assert not any(p.name.endswith(".tmp") for p in tmp_path.iterdir())


def test_checkpoint_elastic_restore_resharded(tmp_path):
    """Restore onto a different device layout (elastic scaling)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(1, t)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    out = ck.restore(jax.eval_shape(lambda: t), shardings=sh)
    assert jax.tree.leaves(out)[0].sharding == NamedSharding(mesh, P())


def test_adamw_decreases_quadratic():
    w = {"w": jnp.ones((16,)) * 5.0}
    opt = adamw_init(w)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    for _ in range(200):
        g = {"w": 2 * w["w"]}
        w, opt, _ = adamw_update(cfg, g, opt, w)
    assert float(jnp.max(jnp.abs(w["w"]))) < 0.3


def test_grad_clip():
    from repro.optim.adamw import clip_by_global_norm, global_norm
    t = {"a": jnp.full((4,), 100.0)}
    clipped, norm = clip_by_global_norm(t, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_prefetcher_overlaps_and_orders():
    from repro.data.pipeline import DataConfig, Prefetcher, batch_for_step
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
    pf = Prefetcher(cfg, start_step=3)
    try:
        b0 = pf.next()
        np.testing.assert_array_equal(b0["tokens"],
                                      batch_for_step(cfg, 3)["tokens"])
        b1 = pf.next()
        np.testing.assert_array_equal(b1["tokens"],
                                      batch_for_step(cfg, 4)["tokens"])
    finally:
        pf.close()


def test_hlo_analyzer_loop_awareness():
    """The analyzer must multiply while-body flops by the trip count."""
    from repro.analysis.hlo import analyze_hlo

    def body(h, w):
        return jnp.tanh(h @ w), None

    def f(h, ws):
        h, _ = jax.lax.scan(body, h, ws)
        return h

    h = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    c = jax.jit(f).lower(h, ws).compile()
    res = analyze_hlo(c.as_text())
    expected_dot = 2 * 64 * 64 * 64 * 8  # 8 iterations
    assert res.dot_flops == pytest.approx(expected_dot, rel=0.01)
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older JAX returns [dict]
        ca = ca[0]
    raw = ca["flops"]
    assert res.dot_flops > raw  # XLA counted the body once


def test_hlo_analyzer_collectives():
    from repro.analysis.hlo import analyze_hlo
    from jax.sharding import NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run under dryrun env)")


def test_sharding_rules_divisibility_never_invalid():
    """Every generated spec must divide the dim it shards."""
    from repro.configs.base import TRAIN_4K
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.parallel.sharding import make_rules
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        rules = make_rules(FakeMesh(), cfg, TRAIN_4K)
        # exercise the parameter rules against real shapes
        from repro.models import build_model
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

        def check(path, leaf):
            pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            spec = rules._param_spec(pstr, leaf.shape)
            for dim, axes in zip(leaf.shape, spec):
                if axes is None:
                    continue
                axes = (axes,) if isinstance(axes, str) else axes
                n = 1
                for a in axes:
                    n *= FakeMesh.shape[a]
                assert dim % n == 0, (arch, pstr, leaf.shape, spec)
            return leaf

        jax.tree_util.tree_map_with_path(check, shapes)


@pytest.mark.parametrize("tp, batch, want", [
    (2, 8, ("data", "model", None)),     # kv heads divide TP: Kv*hd sharded
    (16, 8, ("data", None, "model")),    # they don't: positions instead
    (2, 1, (None, "model", "data")),     # one lane: positions over data
])
def test_cache_spec_follows_kv_layout(tp, batch, want):
    """K/V cache leaves are (L, B, Kv*hd, cap): `_cache_spec` puts TP on
    the kv heads when they divide it, on the positions otherwise."""
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import get_config
    from repro.parallel.sharding import make_rules

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": tp}

    cfg = get_config("mistral-large-123b")           # 8 kv heads of 128
    rules = make_rules(FakeMesh(), cfg, ShapeConfig("d", "decode", 4096,
                                                    batch))
    shape = (cfg.num_groups, batch, cfg.num_kv_heads * cfg.head_dim, 4096)
    for leaf in ("k", "v"):
        assert rules._cache_spec(f"groups/0/{leaf}", shape) == P(None, *want)


def test_compressing_train_step_converges():
    from repro.configs.registry import get_smoke_config
    from repro.models import build_model
    from repro.parallel.compression import (init_error_feedback,
                                            make_compressing_train_step)
    cfg = get_smoke_config("stablelm-1.6b").scaled(param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    efb = init_error_feedback(params)
    step = jax.jit(make_compressing_train_step(model, AdamWConfig(lr=2e-3),
                                               threshold_elems=0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                          cfg.vocab_size)}
    losses = []
    for _ in range(20):
        params, opt, efb, m = step(params, opt, efb, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_serving_engine_greedy_decode():
    from repro.configs.registry import get_smoke_config
    from repro.models import build_model
    from repro.serving import Request, ServingEngine
    cfg = get_smoke_config("internvl2-2b").scaled(param_dtype="float32",
                                                  input_mode="tokens",
                                                  num_image_tokens=0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_seq=64)
    reqs = [Request(i, np.random.default_rng(i).integers(
        1, 200, size=(16,)).astype(np.int32), max_new_tokens=4)
        for i in range(3)]
    resp = eng.serve(reqs)
    assert sorted(r.request_id for r in resp) == [0, 1, 2]
    assert all(len(r.tokens) == 4 for r in resp)
    # greedy decode is deterministic
    resp2 = eng.serve(reqs)
    assert all(a.tokens == b.tokens for a, b in
               zip(sorted(resp, key=lambda r: r.request_id),
                   sorted(resp2, key=lambda r: r.request_id)))


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from a table keyed by `device_kind`; an unknown kind is
    an error, never the v5e numbers by default."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "benchmarks" / "roofline.py"
    spec = importlib.util.spec_from_file_location("roofline", path)
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    assert roofline.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        roofline.peaks("TPU v4")
    with pytest.raises(KeyError):
        roofline.roofline_terms({"devices": 1, "device_kind": "cpu",
                                 "hlo_flops": 1.0, "hlo_bytes": 1.0,
                                 "collective_bytes_total": 0.0})


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, where set, places the cache and no
    directory is set in code; otherwise the fixed <checkout>/.jax_cache."""
    from pathlib import Path
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = enable_compile_cache()
        if env_dir is None:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        # every program is kept, the quick compiles too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
