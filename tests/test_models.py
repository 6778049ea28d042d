"""Per-architecture smoke tests (reduced configs): one train step + prefill
+ decode on CPU, asserting shapes and finiteness. Plus layer-level
consistency checks (prefill-vs-decode equivalence, mixers vs oracles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models import build_model, padded_vocab
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serving import ServingEngine
from repro.train.train_step import make_train_step


def _batch(cfg, b=2, s=64, seed=0):
    rng = jax.random.PRNGKey(seed)
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}
    if cfg.input_mode == "frames":
        batch["frames"] = jax.random.normal(
            rng, (b, s, cfg.d_model), jnp.dtype(cfg.param_dtype))
    elif cfg.input_mode == "tokens+image":
        batch["image_embeds"] = jax.random.normal(
            rng, (b, cfg.num_image_tokens, cfg.d_model),
            jnp.dtype(cfg.param_dtype))
        batch["tokens"] = batch["tokens"][:, :s - cfg.num_image_tokens]
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    cfg = get_smoke_config(arch).scaled(train_microbatch=0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params, cfg.opt_state_dtype)
    step = make_train_step(model, AdamWConfig(lr=1e-3))
    batch = _batch(cfg)
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert int(opt["step"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    s = batch["tokens"].shape[1] + (cfg.num_image_tokens
                                    if cfg.input_mode == "tokens+image" else 0)
    logits, cache = model.prefill(params, batch, max_seq=s + 8)
    assert logits.shape[:2] == (2, 1)
    assert logits.shape[-1] == padded_vocab(cfg)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(3):
        logits, cache = model.decode_step(params, cache, tok,
                                          jnp.int32(s + i))
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "gemma3-12b",
                                  "xlstm-125m", "jamba-1.5-large-398b",
                                  "deepseek-v2-236b"])
def test_prefill_decode_matches_forward(arch):
    """Decoding token t with a prefilled cache must reproduce the full
    forward logits at position t (fp32 params for a tight bound)."""
    cfg = get_smoke_config(arch).scaled(param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    batch = _batch(cfg, b, s, seed=1)
    full_logits, _ = model.forward(params, batch)

    split = s - 4 if cfg.input_mode != "tokens+image" else None
    if split is None:
        pytest.skip("vlm prefix handled in full-forward smoke")
    pre = {"tokens": batch["tokens"][:, :split]}
    if cfg.input_mode == "frames":
        pre["frames"] = batch["frames"]
    _, cache = model.prefill(params, pre, max_seq=s)
    for t in range(split, s):
        logits, cache = model.decode_step(
            params, cache, batch["tokens"][:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=2e-3, atol=2e-3, err_msg=f"{arch} pos {t}")


#: one registry model per kind of decode cache
DECODER_KINDS = {"attn": "stablelm-1.6b", "swa": "gemma3-12b",
                 "mla": "deepseek-v2-236b", "mamba": "jamba-1.5-large-398b",
                 "xlstm": "xlstm-125m", "cross": "seamless-m4t-medium"}


@pytest.mark.parametrize("kind", DECODER_KINDS)
def test_decode_step_updates_donated_cache_in_place(kind):
    """The engine's decode program aliases every cache byte to its output
    and consumes the cache passed in; a step changes only the new token's
    slot of each attention layer (the K/V column, its `slot_pos`)."""
    cfg = get_smoke_config(DECODER_KINDS[kind])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_seq=16)
    b, pos = 2, 21                                 # swa rings wrap: slot 5

    def filled():
        return jax.tree.map(lambda a: a + 1, model.init_cache(b, 16))

    # a NumPy view would pin the cache's buffers and refuse the donation
    before = jax.tree.map(np.asarray, filled())
    cache = filled()
    tok = jnp.ones((b, 1), jnp.int32)
    compiled = eng._decode.lower(params, cache, tok, jnp.int32(pos)).compile()
    nbytes = sum(a.nbytes for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes

    _, after = eng._decode(params, cache, tok, jnp.int32(pos))
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    for c0, c1 in zip(before["groups"], after["groups"]):
        assert {n: (a.shape, a.dtype) for n, a in c0.items()} == \
            {n: (a.shape, a.dtype) for n, a in c1.items()}
        if "k" in c0:                              # (L, B, Kv*hd, cap)
            assert c0["k"].shape[2] == cfg.num_kv_heads * cfg.head_dim
            slot = pos % c0["k"].shape[-1]
            for n in ("k", "v"):
                kept = np.delete(np.asarray(c1[n]), slot, axis=-1)
                np.testing.assert_array_equal(
                    kept, np.delete(c0[n], slot, axis=-1))
                assert np.any(np.asarray(c1[n])[..., slot] != c0[n][..., slot])
            sp = np.asarray(c1["slot_pos"])
            assert (sp[:, slot] == pos).all()
            np.testing.assert_array_equal(np.delete(sp, slot, axis=-1),
                                          np.delete(c0["slot_pos"], slot,
                                                    axis=-1))
        for n in ("cross_k", "cross_v"):
            if n in c0:
                np.testing.assert_array_equal(np.asarray(c1[n]), c0[n])


def test_loss_decreases_when_training():
    cfg = get_smoke_config("stablelm-1.6b").scaled(param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=2e-3)))
    batch = _batch(cfg, 4, 64)
    losses = []
    for _ in range(25):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::6]


def test_moe_dispatch_modes_agree():
    """dropping/ragged dispatch must match dense compute (cap high enough
    that nothing drops)."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_apply, moe_init
    cfg = get_smoke_config("mixtral-8x22b").scaled(
        param_dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                      capacity_factor=8.0, dispatch="dense"))
    rng = jax.random.PRNGKey(0)
    params = moe_init(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y_dense, _ = moe_apply(params, cfg, x)
    cfg_drop = cfg.scaled(moe=cfg.moe.__class__(
        num_experts=4, top_k=2, d_ff_expert=64, capacity_factor=8.0,
        dispatch="dropping"))
    y_drop, _ = moe_apply(params, cfg_drop, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_drop),
                               rtol=1e-4, atol=1e-4)
    cfg_rag = cfg.scaled(moe=cfg.moe.__class__(
        num_experts=4, top_k=2, d_ff_expert=64, capacity_factor=8.0,
        dispatch="ragged"))
    y_rag, _ = moe_apply(params, cfg_rag, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_rag),
                               rtol=1e-4, atol=1e-4)


def test_blockwise_attention_grads_match_naive():
    from repro.models.attention import blockwise_sdpa, naive_sdpa
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, Kv, G, hd = 2, 128, 2, 2, 16
    q = jax.random.normal(ks[0], (B, S, Kv, G, hd))
    k = jax.random.normal(ks[1], (B, S, Kv, hd))
    v = jax.random.normal(ks[2], (B, S, Kv, hd))
    pos = jnp.arange(S)

    def lb(q, k, v):
        return jnp.sum(jnp.sin(blockwise_sdpa(q, k, v, pos, pos, 0, True,
                                              0.0, 32, 32)))

    def ln(q, k, v):
        return jnp.sum(jnp.sin(naive_sdpa(q, k, v, pos, pos, causal=True)))

    gb = jax.grad(lb, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(ln, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_mlstm_parallel_matches_recurrent_decode():
    """Chunkwise-parallel train form vs step-by-step decode: same outputs."""
    from repro.models.xlstm import (init_mlstm_cache, mlstm_decode,
                                    mlstm_init, mlstm_mix)
    cfg = get_smoke_config("xlstm-125m").scaled(param_dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = mlstm_init(rng, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model)) * 0.5
    y_par, _ = mlstm_mix(params, cfg, x, chunk=8)
    cache = init_mlstm_cache(cfg, 1, dtype=jnp.float32)
    ys = []
    for t in range(16):
        y_t, cache = mlstm_decode(params, cfg, x[:, t:t + 1], cache)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=5e-4, atol=5e-4)


def test_mamba_chunked_matches_decode():
    from repro.models.ssm import (init_mamba_cache, mamba_decode, mamba_init,
                                  mamba_mix)
    cfg = get_smoke_config("jamba-1.5-large-398b").scaled(
        param_dtype="float32")
    params = mamba_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model)) * 0.5
    y_par, _ = mamba_mix(params, cfg, x, chunk=4)
    cache = init_mamba_cache(cfg, 1, dtype=jnp.float32)
    ys = []
    for t in range(16):
        y_t, cache = mamba_decode(params, cfg, x[:, t:t + 1], cache)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.slow  # ~90 s: full-vocab logits materialization
def test_chunked_loss_matches_full():
    """Vocab-chunked loss (never materializes (B,S,V) logits) must match
    the full-logits loss in value and gradients."""
    cfg = get_smoke_config("gemma3-12b").scaled(param_dtype="float32")
    model_full = build_model(cfg)
    params = model_full.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 2048),
                                          0, cfg.vocab_size)}

    class Chunked(type(model_full)):
        CHUNKED_LOSS_VOCAB = 1

    model_chunk = Chunked(cfg)
    l_full, _ = model_full.loss_fn(params, batch)
    l_chunk, _ = model_chunk.loss_fn(params, batch)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-5)
    g1 = jax.grad(lambda p: model_full.loss_fn(p, batch)[0])(params)
    g2 = jax.grad(lambda p: model_chunk.loss_fn(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
