"""The reader of the blockwise prefill attention's block counters, on
hand-built `engine.wave` spans."""
import pytest

from conftest import BENCH

from bench.spec import load_module

READER = load_module(BENCH / "metrics" / "attention.prefill_blocks_run_share.py",
                     "metrics_attention_prefill_blocks_run_share")


def wave(**counters):
    return (1.0, "span", "s1", "t", dict(counters, name="engine.wave",
                                         end=2.0, parent=None))


@pytest.mark.parametrize("events,want", [
    # the DeepSeek cell: waves of 3 and 2 lanes, 6 layers of 7 x 7 blocks
    # each, of which 28 are computed
    ([wave(prefill_attn_blocks=3 * 294, prefill_attn_blocks_run=3 * 168),
      wave(prefill_attn_blocks=2 * 294, prefill_attn_blocks_run=2 * 168)],
     100 * 28 / 49),
    # short prompts form no block and add nothing
    ([wave(prefill_attn_blocks=294, prefill_attn_blocks_run=168),
      wave(prefill_attn_blocks=0, prefill_attn_blocks_run=0)],
     100 * 28 / 49),
    # only short prompts; a program that keeps no such counter; no wave
    ([wave(prefill_attn_blocks=0, prefill_attn_blocks_run=0)], None),
    ([wave(steps=3, lane_steps=6, live_lane_steps=5)], None),
    ([], None),
])
def test_reader_on_known_events(events, want):
    got = READER.read({"events": events})
    assert got == (pytest.approx(want) if want is not None else None)
    if want is not None:
        assert round(got, 1) == 57.1
