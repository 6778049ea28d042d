"""Shared pieces of the benchmark's CPU tests.

`tiny_root` lays out a benchmark of its own in a temporary directory:
`BENCHMARK.json`, configuration files cut from the real ones to a few
layers and a small width, and traffic files for them. The harness finds
all of it by name, with no edit to its code.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src", ROOT / "examples"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

BENCH = ROOT / "bench"

TINY_LM = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=4,
               head_dim=32, d_ff=256, vocab_size=512)
TINY_XLSTM = dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                  head_dim=32, vocab_size=256)


def _cut(name, sizes, **extra):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c["model"].update(sizes, **extra)
    c["reduced"] = sorted(set(c["reduced"]) | set(sizes) | set(extra))
    return c


def write_tiny(root: Path) -> Path:
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    cfgs = {"tiny-lm": _cut("stablelm-1.6b", TINY_LM),
            "tiny-xlstm": _cut("xlstm-125m", TINY_XLSTM),
            "tiny-xlstm-f32": _cut("xlstm-125m-f32", TINY_XLSTM)}
    cfgs["tiny-lm"]["serving"]["max_seq"] = 256
    cfgs["tiny-xlstm"]["serving"]["max_seq"] = 256
    chat = dict(driver="serve", why="t", rate_hz=4.0,
                prompt_lens=[16, 32, 64], prompt_weights=[.5, .3, .2],
                output_lens=[4, 8, 16], output_weights=[.5, .3, .2],
                sample_tokens=32, limits={"max_logit_gap": 0.05},
                schedule_seed=7)
    train = json.loads((BENCH / "traffic" / "graph-2k.json").read_text())
    train.update(seq_len=64, batch=4, reference_rows=2,
                 limits={"loss_rel_gap": 1e-3, "grad_norm_gap": 1e-3,
                         "change_norm_gap": 1e-3})
    for name, c in cfgs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(c))
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(chat))
    (root / "bench" / "traffic" / "tiny-train.json").write_text(
        json.dumps(train))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    serve, trains = ["tiny-lm-chat", "tiny-xlstm-chat"], ["tiny-train"]
    bench["configs"] = [dict(name=n, source="https://example.org", reduced=[],
                             file=f"bench/configs/{n}.json", why="t")
                        for n in cfgs]
    bench["workloads"] = [
        dict(name="tiny-lm-chat", config="tiny-lm", traffic="tiny-chat",
             chips=1, why="t"),
        dict(name="tiny-xlstm-chat", config="tiny-xlstm",
             traffic="tiny-chat", chips=1, why="t"),
        dict(name="tiny-train", config="tiny-xlstm-f32",
             traffic="tiny-train", chips=1, why="t")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = serve
    # the training cell's metrics, as a training cell in BENCHMARK.json
    # would list them
    bench["end_to_end"].insert(0, dict(
        name="train_tokens_per_s", unit="tokens/s", better="higher",
        bound=0.25, source="host_clock", workloads=trains))
    bench["per_layer"] += [
        dict(name=n, unit=u, better=b, source="host_clock", layer="t",
             moves="train_tokens_per_s", workloads=trains)
        for n, u, b in (("runtime.execute_ms.train", "ms", "lower"),
                        ("mfu.train", "%", "higher"),
                        ("device_idle_share.train", "%", "lower"))]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("tiny"))


def last_json(text: str):
    return json.loads(text.strip().splitlines()[-1])
