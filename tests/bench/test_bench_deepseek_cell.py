"""The DeepSeek-V2 family in the benchmark: a tiny cell of it run whole on
the CPU through `bench/run.py`'s own `main`, and its counts of operations
and bytes at the published widths against the hand arithmetic."""
import json
import math

import pytest

from conftest import BENCH, ROOT, last_json

from bench import peaks, run
from bench.spec import load_cell, load_module

CELL = "tiny-deepseek-doc"
CONFIG = json.loads(
    (BENCH / "configs" / "deepseek-v2-236b-ep20.json").read_text())
FAM = load_module(BENCH / "models" / "deepseek_v2.py", "models_deepseek_v2")
M = CONFIG["model"]

#: every mechanism at small widths: YaRN ramping inside 8 rope pairs,
#: 4 of 8 groups kept, 4 of 16 experts held from expert 4. In float32:
#: at these widths bfloat16 rounding flips near-tied router choices, and
#: with gates of score x 16 one flip moves a logit by tenths
TINY = dict(
    param_dtype="float32", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=16, d_ff=32, dense_d_ff=128, vocab_size=512,
    rope_scaling=dict(M["rope_scaling"], original_max_position_embeddings=64),
    mla=dict(M["mla"], kv_lora_rank=32, q_lora_rank=48, rope_head_dim=16,
             nope_head_dim=16, v_head_dim=16),
    moe=dict(M["moe"], num_experts=16, top_k=4, d_ff_expert=32,
             num_shared_experts=1, n_group=8, topk_group=4,
             num_experts_held=4, first_expert=4))


@pytest.fixture(scope="module")
def deepseek_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny-deepseek")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    c = dict(CONFIG, model=dict(M, **TINY))
    c["reduced"] = sorted(set(c["reduced"]) | set(TINY))
    c["serving"] = dict(c["serving"], max_seq=128)
    (root / "bench" / "configs" / "tiny-deepseek.json").write_text(
        json.dumps(c))
    traffic = json.loads(
        (BENCH / "traffic" / "poisson-doc-7k.json").read_text())
    traffic.update(rate_hz=4.0, prompt_lens=[48], output_lens=[4, 8, 16],
                   output_weights=[.5, .3, .2], sample_tokens=32,
                   limits={"max_logit_gap": 0.05})
    (root / "bench" / "traffic" / "tiny-doc.json").write_text(
        json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(name="tiny-deepseek", source="https://x.org",
                             reduced=c["reduced"], why="t",
                             file="bench/configs/tiny-deepseek.json")]
    bench["workloads"] = [dict(name=CELL, config="tiny-deepseek",
                               traffic="tiny-doc", chips=1, why="t")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, capsys, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 91),
                   "--seconds", "2", "--trace", str(trace)],
                  require_tpu=False, root=root)
    assert rc == 0
    out = capsys.readouterr()
    return last_json(out.out), out.err


def test_tiny_cell_is_correct_and_reports_its_layers(deepseek_root, capsys,
                                                     monkeypatch):
    """Served through FrontDoor, replica, engine and model, the tiny cell
    agrees with the reference; traced, the expert counter reads (the CPU
    trace has no device plane, so the rooflines read nothing)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    res, _ = _run(deepseek_root, capsys, trace=1)
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert got["moe.tokens_per_expert"]["value"] > 0
    assert 0 < got["mfu.serve"]["value"] < 100
    assert "prefill_roofline" not in got and "decode_roofline" not in got


def test_a_token_altered_where_it_is_produced_fails(deepseek_root, capsys,
                                                     monkeypatch):
    from repro.serving import engine
    real = engine.ServingEngine._greedy

    def altered(self, logits):
        return (real(self, logits) + 1) % self.model.cfg.vocab_size
    monkeypatch.setattr(engine.ServingEngine, "_greedy", altered)
    res, err = _run(deepseek_root, capsys)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_counts_at_published_widths_match_the_hand_arithmetic():
    """4.81 GB of weights on the chip: MLA 298 MB a layer, 8 held experts
    378 MB and the shared ones 94 MB a MoE layer; the latent cache 1,152 B
    a token and layer (512 latent + 64 rope, bf16)."""
    assert FAM.param_bytes(M) == 4_806_174_720
    assert FAM.latent_bytes_per_token(M) == 1152
    lay = FAM.layout(M)
    size = lambda p: math.prod(lay[p].shape) * 2
    mla = sum(size(p) for p in lay if p.startswith("first/0/mixer/"))
    assert round(mla / 1e6) == 298
    experts = sum(size("groups/0/ffn/" + w) for w in
                  ("w_gate", "w_up", "w_down")) / 5
    assert experts == 8 * 3 * 5120 * 1536 * 2            # 377.5 MB
    shared = sum(size("groups/0/ffn/shared/" + w) for w in
                 ("w_gate", "w_up", "w_down")) / 5
    assert round(shared / 1e6) == 94
    # a prefill of 7,168 tokens: 1.26e13 in expanded attention, 3.3e13 all
    attn = 6 * 2 * 128 * (128 + 64 + 128) * 7168 * 7169 / 2
    assert attn == pytest.approx(1.263e13, rel=1e-3)
    assert FAM.prefill_flops(M, 7168) == pytest.approx(3.31e13, rel=1e-2)
    # decode: 2.9-3.7 GB of least bytes at widths 1-8 with 7.3k contexts
    for width, lo, hi in ((1, 2.8e9, 3.0e9), (8, 3.6e9, 3.8e9)):
        _, b = FAM.decode_cost(M, [7300] * width, width)
        assert lo < b < hi, (width, b)
    assert FAM.experts_touched(M, 1) == pytest.approx(8 * 6 / 160)


@pytest.mark.parametrize("width", [1, 8])
def test_roofline_least_time_is_bounded_by_both_peaks(width):
    """The least time of a prefill and of a decode step is the larger of
    operations over peak FLOP/s and bytes over peak bandwidth, so it is
    never under either: prefill is bound by operations, decode by bytes."""
    pk = peaks.PEAKS["TPU v5 lite"]
    reader = load_module(BENCH / "metrics" / "prefill_roofline.py", "pr")
    assert reader.PROGRAM == "prefill"
    f, b = FAM.prefill_cost(M, 7168, width)
    assert f / pk["bf16_flops"] > b / pk["hbm_bw"]
    assert f / pk["bf16_flops"] > 0.16 * width
    f, b = FAM.decode_cost(M, [7200] * width, width)
    assert b / pk["hbm_bw"] > f / pk["bf16_flops"]
    assert 3.4e-3 < b / pk["hbm_bw"] < 4.6e-3


def test_the_cell_is_found_by_name():
    c = load_cell("serve-deepseek-v2-doc-7k")
    assert c.family() is FAM or c.family().prefill_cost
    assert {m["name"] for m in c.per_layer} >= {
        "prefill_roofline", "moe.tokens_per_expert", "decode_roofline"}
