"""chip_smoke.py's phases at smoke sizes on the CPU: each phase's result
passes its check, the checks fire on corrupted results, and `main()`
refuses to run anywhere but on a TPU."""
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import pytest

from repro.configs.registry import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod   # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    res = smoke.serve_phase(get_smoke_config("stablelm-1.6b"),
                            n_requests=4, lengths=(8, 16), max_new=4,
                            max_seq=32)
    smoke.check_serve(res)
    return res


def test_serve_phase_passes_its_check(smoke, served):
    assert len(served.tokens) == 4
    assert served.tokens[0] == served.reference
    assert served.weight_bytes > 0


@pytest.mark.parametrize("corrupt", ["first_mismatch", "short",
                                     "out_of_vocab"])
def test_serve_check_fires_on_corrupted_result(smoke, served, corrupt):
    tokens = [list(t) for t in served.tokens]
    if corrupt == "first_mismatch":
        tokens[0][-1] = (tokens[0][-1] + 1) % served.vocab_size
    elif corrupt == "short":
        tokens[2] = tokens[2][:-1]
    else:
        tokens[1][0] = served.vocab_size
    with pytest.raises(smoke.SmokeCheckError):
        smoke.check_serve(dataclasses.replace(served, tokens=tokens))


def test_peak_memory_check(smoke):
    smoke.check_peak_memory(100, 100)
    for peak in (None, 99):
        with pytest.raises(smoke.SmokeCheckError):
            smoke.check_peak_memory(peak, 100)


def test_train_phase_losses_and_check(smoke):
    losses = smoke.train_phase(["--steps", "2", "--shards", "1",
                                "--seq-len", "16", "--batch", "2",
                                "--publish-every", "0"])
    smoke.check_losses(losses, 2)
    with pytest.raises(smoke.SmokeCheckError):
        smoke.check_losses(losses[:1], 2)
    with pytest.raises(smoke.SmokeCheckError):
        smoke.check_losses([losses[0], math.nan], 2)


def test_four_chip_check_fires(smoke):
    good = smoke.FourChipResult(11.5, [11.51, 11.2, 10.9],
                                {0: 10, 1: 10, 2: 10, 3: 10})
    smoke.check_four_chip(good, min_device_bytes=5)
    bad = [dataclasses.replace(good, losses=[11.8, 11.2, 10.9]),
           dataclasses.replace(good, losses=[11.51, math.inf, 10.9]),
           dataclasses.replace(good, param_bytes={0: 40}),
           dataclasses.replace(good, param_bytes={0: 37, 1: 1, 2: 1, 3: 1})]
    for r in bad:
        with pytest.raises(smoke.SmokeCheckError):
            smoke.check_four_chip(r, min_device_bytes=5)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok": true' not in out
    for line in out.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")
