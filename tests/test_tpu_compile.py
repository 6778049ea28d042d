"""Ahead-of-time compiles for a described TPU v5e (`v5e:2x2`), with no chip
attached: the main path's programs at published widths must pass the
chip's own compiler and fit its memory.

Only one process at a time may load the TPU library, and it keeps it
until it exits. So the topology is described inside a module fixture of
this one file, never while a module is imported: every xdist worker then
collects the same tests, and only the worker given this file loads it.
"""
import dataclasses
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.kernels import flash_attention, int8_matmul, mlstm_scan, ssm_scan
from repro.launch import train as train_launcher
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serving import ServingEngine
from test_models import DECODER_KINDS

HBM_BYTES = 16e9            # one v5e chip
SERVE_ARCH = "stablelm-1.6b"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no TPU library here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serving_engine_compiles_for_one_chip(one_chip, program):
    """The ServingEngine's own jitted programs, stablelm-1.6b in bf16:
    batch 4, prompt 512, max_seq 1024."""
    cfg = get_config(SERVE_ARCH)
    model = build_model(cfg)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(model, params, max_seq=1024)
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    if program == "prefill":
        lowered = eng._prefill.lower(params, {"tokens": i32((4, 512))})
    else:
        cache = _on(one_chip,
                    jax.eval_shape(lambda: model.init_cache(4, 1024)))
        lowered = eng._decode.lower(params, cache, i32((4, 1)), i32(()))
    assert _device_bytes(lowered.compile()) < HBM_BYTES


def _decode_for_one_chip(one_chip, cfg, width: int, max_seq: int):
    """The ServingEngine's decode step compiled for one chip, and the
    shapes of the cache it takes."""
    model = build_model(cfg)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(model, params, max_seq=max_seq)
    cache = _on(one_chip,
                jax.eval_shape(lambda: model.init_cache(width, max_seq)))
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = eng._decode.lower(params, cache, i32((width, 1)),
                                 i32(())).compile()
    return compiled, cache


def _cache_bytes(cache) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


def _big_copies(hlo: str, elements: int):
    """The `copy` instructions of an optimised HLO text whose result has
    at least `elements` elements."""
    out = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(", hlo):
        n = math.prod(int(d) for d in m.group(1).split(",") if d)
        if n >= elements:
            out.append(m.group(0))
    return out


@pytest.mark.parametrize("width", [1, 8])
def test_decode_updates_kv_cache_in_place(one_chip, width):
    """stablelm-1.6b's decode step at max_seq 2048: the whole cache is
    aliased from input to output, the temporaries stay under one layer's
    K/V, and no copy is as large as one lane's layer of K or V."""
    cfg = get_config(SERVE_ARCH)
    compiled, cache = _decode_for_one_chip(one_chip, cfg, width, 2048)
    m = compiled.memory_analysis()
    kv_width = cfg.num_kv_heads * cfg.head_dim
    layer_kv = 2 * width * kv_width * 2048 * 2           # K and V, bf16
    assert m.alias_size_in_bytes == _cache_bytes(cache)
    assert m.temp_size_in_bytes < layer_kv
    assert not _big_copies(compiled.as_text(), kv_width * 2048)


@pytest.mark.parametrize("kind", DECODER_KINDS)
def test_decode_cache_kinds_in_place(one_chip, kind):
    """Every kind of decode cache, at a tiny registry config: the decode
    step aliases the cache and holds less than one layer of it besides."""
    compiled, cache = _decode_for_one_chip(
        one_chip, get_smoke_config(DECODER_KINDS[kind]), 2, 2048)
    m = compiled.memory_analysis()
    layer = sum(a.size // a.shape[0] * a.dtype.itemsize
                for a in jax.tree.leaves(cache["groups"]))
    assert m.alias_size_in_bytes >= _cache_bytes(cache)
    assert m.temp_size_in_bytes < layer


def _deepseek_cell():
    """deepseek-v2-236b as the benchmark cell cuts it: 6 layers, an eighth
    of the vocabulary, experts 0-7 of 160 held; every width published."""
    base = get_config("deepseek-v2-236b")
    return base.scaled(num_layers=6, vocab_size=12_800,
                       moe=dataclasses.replace(base.moe, num_experts_held=8))


@pytest.fixture(scope="module")
def deepseek_prefill(one_chip):
    """The cell's prefill of a wave of 8 prompts of 7,168 tokens into a
    cache of 8,192, compiled."""
    model = build_model(_deepseek_cell())
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(model, params, max_seq=8192)
    tokens = jax.ShapeDtypeStruct((8, 7168), jnp.int32, sharding=one_chip)
    return eng._prefill.lower(params, {"tokens": tokens}).compile()


def test_deepseek_prefill_of_a_full_wave_fits_one_chip(deepseek_prefill):
    """The prefill's temporaries (one lane's expanded K/V and score
    blocks, one lane's sorted expert rows) fit beside the 4.8 GB of
    weights."""
    assert _device_bytes(deepseek_prefill) < 10e9 < HBM_BYTES


def test_deepseek_prefill_skips_masked_blocks_inside_the_kv_loop(
        deepseek_prefill):
    """Each latent-attention layer program (the dense first layer, the
    scanned group) computes a block under a conditional inside its KV
    loop, whose result is the running max, sum and float32 accumulator of
    a chunk of 1,024 queries over 128 heads; the accumulator is not
    copied besides."""
    lines = deepseek_prefill.as_text().splitlines()
    acc = "f32[1,128,1,1024,128]"
    conds = [ln for ln in lines if " conditional(" in ln]
    assert len(conds) == 2
    for ln in conds:
        op = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert acc in ln.split(" conditional(")[0] and "/mla/" in op
        assert op.endswith("/while/body/closed_call/cond")
        assert op.count("while/body") >= 3      # lanes, query chunks, KV
    copies = [ln.split(" copy")[0] for ln in lines
              if re.search(r" copy(-start)?\(", ln)]
    assert copies and not [c for c in copies if acc in c]


def test_deepseek_decode_updates_latent_cache_in_place(one_chip):
    """The decode step at width 8 over 8,192 positions: the latent cache
    (c_kv, k_rope) is aliased whole, nothing as large as one lane's layer
    of it is copied, and the temporaries stay under 10 MB (no layer of
    cache or expert weights is copied out)."""
    cfg = _deepseek_cell()
    compiled, cache = _decode_for_one_chip(one_chip, cfg, 8, 8192)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _cache_bytes(cache)
    assert m.temp_size_in_bytes < 10e6
    assert not _big_copies(compiled.as_text(), 8192 * cfg.mla.kv_lora_rank)
    assert _device_bytes(compiled) < HBM_BYTES


def test_launcher_train_step_compiles_on_2x2(topo):
    """repro.launch.train's sharded init and step, stablelm-1.6b at seq
    1024, batch 8, on a 2x2 data x model mesh: the Auto-axis mesh must
    trace, and the state must spread over the four chips."""
    cfg = get_config(SERVE_ARCH)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    init_fn, step_fn, specs = train_launcher.build(
        cfg, mesh, ShapeConfig("t", "train", 1024, 8))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    init_c = init_fn.lower(key).compile()
    params, opt_state = jax.eval_shape(init_fn, key)
    step_c = step_fn.lower(params, opt_state, specs).compile()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((params, opt_state)))
    # each chip holds about a quarter of params + AdamW state
    assert init_c.memory_analysis().output_size_in_bytes < state_bytes / 3
    assert _device_bytes(step_c) < HBM_BYTES
    hlo = step_c.as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo


def _kernel_case(name):
    """(op, args) of one Pallas kernel at a registry model's width."""
    sds = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":            # stablelm-1.6b attention
        cfg = get_config("stablelm-1.6b")
        hd = cfg.d_model // cfg.num_heads
        qkv = sds((1, cfg.num_heads, 1024, hd), bf16)
        return partial(flash_attention, backend="pallas"), (qkv, qkv, qkv)
    if name == "int8_matmul":                # stablelm-1.6b FFN up
        cfg = get_config("stablelm-1.6b")
        return (partial(int8_matmul, backend="pallas"),
                (sds((512, cfg.d_model), bf16),
                 sds((cfg.d_model, cfg.d_ff), jnp.int8),
                 sds((cfg.d_ff,), f32)))
    if name == "mlstm_scan":                 # xlstm-125m mLSTM cell
        cfg = get_config("xlstm-125m")
        di = int(cfg.xlstm.proj_factor_mlstm * cfg.d_model)
        h = cfg.num_heads
        qkv = sds((1, h, 1024, di // h), bf16)
        gate = sds((1, h, 1024), f32)
        return (partial(mlstm_scan, backend="pallas"),
                (qkv, qkv, qkv, gate, gate))
    cfg = get_config("jamba-1.5-large-398b")  # jamba Mamba layer
    di, ds = cfg.mamba.expand * cfg.d_model, cfg.mamba.d_state
    return (partial(ssm_scan, backend="pallas"),
            (sds((1, 1024, di), bf16), sds((1, 1024, di), bf16),
             sds((1, 1024, ds), bf16), sds((1, 1024, ds), bf16),
             sds((di, ds), f32), sds((di,), f32)))


@pytest.mark.parametrize("name", ["flash_attention", "int8_matmul",
                                  "mlstm_scan", "ssm_scan"])
def test_pallas_kernel_compiles_for_one_chip(one_chip, name):
    op, args = _kernel_case(name)
    compiled = jax.jit(op).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
