"""Whole runs of tiny serving cells on the CPU, through `bench/run.py`'s
own `main` with the look for a chip skipped: the result line, the
per-layer readers, a cell found by name, and `correct` coming out false
when the timed path is broken or the control stands in its place."""
import subprocess
import sys

import pytest

from conftest import ROOT, last_json

from bench import peaks, run
from bench.spec import load_cell


def _run(root, cell, capsys, seed=2 ** 31 + 77, trace=0, seconds=2):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_tpu=False, root=root)
    assert rc == 0
    out = capsys.readouterr()
    return last_json(out.out), out.err


def test_no_tpu_exits_nonzero_and_prints_no_result():
    """With no TPU the command fails and prints no metric."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "serve-stablelm-1.6b-short", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_result_line_of_a_served_cell(tiny_root, capsys):
    res, err = _run(tiny_root, "tiny-lm-chat", capsys)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 8
    assert set(res["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1
    assert err.strip().splitlines()[-1].startswith(
        "check requests_never_resolved")


def test_traced_run_reports_the_layer_metrics(tiny_root, capsys,
                                              monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    res, _ = _run(tiny_root, "tiny-xlstm-chat", capsys, trace=1)
    assert res["correct"] is True
    got = res["metrics"]
    # the CPU trace has no device plane: the device readers find nothing
    # to read except the idle share, and the counters still read
    assert got["frontdoor.wave_size_mean"]["value"] >= 1
    assert 0 < got["mfu.serve"]["value"] < 100
    assert "decode_roofline" not in got
    # the whole window is traced
    assert abs(res["device"]["window_s"] - 2.0) < 0.2
    assert "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_token_altered_where_it_is_produced_fails(tiny_root, capsys,
                                                     monkeypatch):
    """The engine's greedy pick altered: the served token is not the best,
    and the widest logit gap exceeds its limit."""
    from repro.serving import engine
    real = engine.ServingEngine._greedy

    def altered(self, logits):
        return (real(self, logits) + 1) % self.model.cfg.vocab_size
    monkeypatch.setattr(engine.ServingEngine, "_greedy", altered)
    res, err = _run(tiny_root, "tiny-lm-chat", capsys)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
    assert "FAILED" in err


@pytest.mark.parametrize("cell", ["tiny-lm-chat", "tiny-xlstm-chat"])
def test_control_reads_far_above_the_program(tiny_root, cell):
    """The reference one precision below the configuration's products
    (float8), in the program's place, reads at least three times the
    program's gaps, and the cell's own comparison finds it not correct."""
    import time
    from bench.harness import Context
    from bench.load import serve_schedule
    c = load_cell(cell, tiny_root)
    ctx = Context(c, 2 ** 33 + 5, 2.0, False, started=time.time())
    drv = c.driver()
    srv = drv.set_up(ctx)
    try:
        w = drv.drive(srv, serve_schedule(c.traffic, 2.0, ctx.seed,
                                          ctx.model["vocab_size"]), 2.0,
                      60.0, ctx.window)
    finally:
        drv.tear_down(srv)
    picked = drv.sample(w.records, ctx.seed, 64)
    gaps = drv.reference_gaps(ctx.family, ctx.model, ctx.seed,
                              [w.records[i] for i in picked],
                              c.config["serving"]["max_seq"],
                              control=ctx.control)
    for k in ("max_logit_gap", "mean_logit_gap"):
        assert gaps["control_" + k] > 3 * max(gaps[k], 1e-4)
    limits = c.traffic["limits"]
    assert all(x.ok for x in drv.checks(gaps, limits))
    assert not all(x.ok for x in drv.checks(gaps, limits, "control_"))
