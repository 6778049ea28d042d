"""Operation and byte counts from shapes, against hand counts at small
shapes; and weights from a seed, the same for program and reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import stablelm, xlstm
from bench.weights import Leaf, program_weights, reference_weights

LM = dict(num_layers=2, d_model=8, num_heads=2, num_kv_heads=2, head_dim=4,
          d_ff=16, vocab_size=32, param_dtype="bfloat16")
XL = dict(num_layers=2, d_model=8, num_heads=2, vocab_size=32,
          param_dtype="bfloat16",
          xlstm=dict(proj_factor_mlstm=2.0, proj_factor_slstm=2.0,
                     conv1d_kernel=4, num_heads_slstm=2))


def test_stablelm_counts():
    # per layer: q,k,v,o 4*8*8, gate/up/down 3*8*16; head 8*32
    assert stablelm.matmul_params(LM) == 2 * (4 * 64 + 3 * 128) + 256
    # 2 per weight, and QK plus PV: 4 * layers * heads * head_dim per
    # attended position
    assert stablelm.token_flops(LM, 5) == 2 * 1536 + 4 * 2 * 2 * 4 * 5
    assert stablelm.prefill_flops(LM, 3) == 3 * 3072 + 64 * (1 + 2 + 3)
    assert stablelm.train_flops_per_token(LM, 3) == 3 * 9600 / 3
    # K and V, 2 layers, 2 heads of 4, 2 bytes
    assert stablelm.kv_bytes_per_token(LM) == 2 * 2 * 2 * 4 * 2
    flops, nbytes = stablelm.decode_cost(LM, [5, 3], width=4)
    assert flops == 3392 + 3264
    # weights once (matrices, 4 embedding rows, 5 norm scales), then
    # 5 + 3 positions of K/V (4 + 2 read, 2 written)
    assert nbytes == (1536 + 4 * 8 + 5 * 8) * 2 + 8 * 64


def test_xlstm_counts():
    # sLSTM: w_in 8x32, r_rec 2x4x16, up 8x32, down 16x8
    # mLSTM: up 8x32, q k v 16x16, w_if 16x4, down 16x8; head 8x32 (tied)
    assert xlstm.matmul_params(XL) == (256 + 128 + 256 + 128) \
        + (256 + 768 + 64 + 128) + 256
    # mLSTM state: 2 heads of 8x8, six operations an entry
    assert xlstm.token_flops(XL) == 2 * 2240 + 2 * 6 * 64
    assert xlstm.train_flops_per_token(XL, 64) == 3 * 5248
    # mLSTM C, n, m (f32) and conv tail (bf16); sLSTM c, n, m, h and tail
    assert xlstm.state_bytes_per_lane(XL) == \
        (128 + 16 + 2) * 4 + 3 * 16 * 2 + 4 * 8 * 4 + 3 * 8 * 2
    flops, nbytes = xlstm.decode_cost(XL, [9, 9], width=2)
    assert flops == 2 * 5248
    assert nbytes == (484 * 4 + (1792 + 16) * 2) + 2 * 2 * 856


@pytest.mark.parametrize("fam,m", [(stablelm, LM), (xlstm, XL)])
def test_roofline_least_time_is_bounded_by_both_peaks(fam, m):
    f, b = fam.decode_cost(m, [4, 4, 4], width=4)
    assert f > 0 and b > 0


def test_weights_match_between_program_and_reference():
    layout = {"a/w": Leaf((4, 6), "bfloat16", 0.5),
              "b": Leaf((6,), "float32", 0.1, ((3, 0.0), (3, 3.0)))}
    shapes = {"a": {"w": jax.ShapeDtypeStruct((4, 6), jnp.bfloat16)},
              "b": jax.ShapeDtypeStruct((6,), jnp.float32)}
    seed = 2 ** 35 + 17
    prog = program_weights(seed, layout, shapes)
    ref = reference_weights(seed, layout)
    assert prog["a"]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(prog["a"]["w"], np.float32),
                                  np.asarray(ref["a/w"]))
    np.testing.assert_array_equal(np.asarray(prog["b"]), np.asarray(ref["b"]))
    assert np.asarray(ref["b"])[3:].mean() > 2.5
    other = reference_weights(seed + 1, layout)
    assert not np.array_equal(np.asarray(other["a/w"]),
                              np.asarray(ref["a/w"]))
