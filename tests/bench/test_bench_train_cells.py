"""Whole runs of a tiny training cell on the CPU, through `bench/run.py`'s
own `main` with the look for a chip skipped: the result line, and
`correct` coming out false when the step is broken underneath (its state
left unchanged, half of its batch left out) or the control stands in."""
import time

import pytest

from conftest import last_json

from bench import run
from bench.harness import Context
from bench.spec import load_cell


def _run(root, capsys, seed=2 ** 32 + 3):
    rc = run.main(["--workload", "tiny-train", "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"],
                  require_tpu=False, root=root)
    assert rc == 0
    return last_json(capsys.readouterr().out)


def test_result_line_of_a_training_cell(tiny_root, capsys):
    res = _run(tiny_root, capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(res["checks"]) == {"loss_rel_gap", "grad_norm_gap",
                                  "change_norm_gap"}


def _break(monkeypatch, how):
    import train_lm
    real = train_lm.build_step_fns

    def broken(model, opt_cfg):
        grad, red, apply = real(model, opt_cfg)
        if how == "unchanged":
            return grad, red, lambda p, o, g: (p, o)
        half = lambda p, b: grad(p, {k: v[: v.shape[0] // 2]
                                     for k, v in b.items()})
        return half, red, apply
    monkeypatch.setattr(train_lm, "build_step_fns", broken)


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_a_broken_step_fails(tiny_root, capsys, monkeypatch, how):
    _break(monkeypatch, how)
    res = _run(tiny_root, capsys)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_fails_a_number(tiny_root):
    """The reference with float8 products (one step below the bfloat16
    products the configuration states) in the program's place fails at
    least one compared number."""
    c = load_cell("tiny-train", tiny_root)
    drv = c.driver()
    ctx = Context(c, 2 ** 31 + 9, 0.0, False, started=time.time())
    ref = drv.reference_readings(ctx, ctx.seed)
    ctrl = drv.reference_readings(ctx, ctx.seed, low=ctx.control)
    nums = drv.compare(ctrl, ref)
    assert any(v > c.traffic["limits"][k] for k, v in nums.items())


def test_traced_training_run_reports_its_layer_metrics(tiny_root, capsys,
                                                       monkeypatch):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    rc = run.main(["--workload", "tiny-train", "--seed", "12", "--seconds",
                   "2", "--trace", "1"], require_tpu=False, root=tiny_root)
    assert rc == 0
    res = last_json(capsys.readouterr().out)
    assert res["correct"] is True
    got = res["metrics"]
    assert got["runtime.execute_ms.train"]["value"] > 0
    assert 0 < got["mfu.train"]["value"] < 100
    assert "device_idle_share.train" in got
