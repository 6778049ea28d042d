"""Distribution tests: a reduced-config multi-device lower+compile in a
subprocess (8 placeholder host devices, (2,2,2) pod mesh), validating the
whole dryrun path — shardings accepted, memory/cost analysis present,
collectives parsed — without the 512-device production sweep (that runs
via `python -m repro.launch.dryrun --all`)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from functools import partial
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import get_smoke_config
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.parallel.sharding import make_rules
    from repro.train.train_step import make_train_step
    from repro.analysis.hlo import analyze_hlo
    from repro.launch.mesh import make_mesh

    arch = %(arch)r
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = ShapeConfig("t", %(kind)r, %(seq)d, %(batch)d)
    cfg = get_smoke_config(arch).scaled(train_microbatch=0)
    rules = make_rules(mesh, cfg, shape)
    model = build_model(cfg, rules)
    specs = model.input_specs(shape)
    in_sh = rules.input_shardings(specs)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = rules.param_shardings(params_shapes)
    if shape.kind == "train":
        opt_shapes = jax.eval_shape(partial(adamw_init, state_dtype=cfg.opt_state_dtype), params_shapes)
        o_sh = rules.opt_shardings(opt_shapes)
        o_sh["step"] = rules.scalar_sharding()
        fn = jax.jit(make_train_step(model, AdamWConfig()),
                     in_shardings=(p_sh, o_sh, in_sh),
                     out_shardings=(p_sh, o_sh, None))
        lowered = fn.lower(params_shapes, opt_shapes, specs)
    else:
        cache_shapes = jax.eval_shape(lambda: model.init_cache(shape.global_batch, shape.seq_len))
        c_sh = rules.cache_shardings(cache_shapes)
        fn = jax.jit(model.decode_step,
                     in_shardings=(p_sh, c_sh, in_sh["tokens"], rules.scalar_sharding()),
                     out_shardings=(None, c_sh))
        lowered = fn.lower(params_shapes, cache_shapes, specs["tokens"],
                           jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = analyze_hlo(compiled.as_text(), total_devices=8)
    print(json.dumps({
        "ok": True,
        "temp": mem.temp_size_in_bytes,
        "args": mem.argument_size_in_bytes,
        "flops": hlo.flops,
        "coll": hlo.collective_bytes(),
        "kinds": hlo.by_kind(),
    }))
""")


def _run(arch, kind="train", seq=64, batch=8):
    code = SCRIPT % {"arch": arch, "kind": kind, "seq": seq, "batch": batch}
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b"])
def test_multipod_train_compiles_with_collectives(arch):
    res = _run(arch, "train")
    assert res["ok"]
    assert res["flops"] > 0
    # data-parallel training must all-reduce (or reduce-scatter) gradients
    assert res["coll"] > 0, res["kinds"]


def test_multipod_decode_compiles():
    res = _run("stablelm-1.6b", kind="decode", seq=64, batch=8)
    assert res["ok"] and res["args"] > 0
