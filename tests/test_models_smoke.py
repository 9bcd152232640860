"""Per-architecture smoke tests (reduced configs, CPU): one forward +
gradient step asserting output shapes and finiteness, plus decode-vs-full
consistency for the cache paths of each family."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import synthetic_batch
from repro.models import decode_step, forward, init_caches, init_params, lm_loss

B, S = 2, 32


def reduced_f32(arch):
    return dataclasses.replace(get_reduced(arch), dtype="float32")


def batch_for(cfg):
    b = synthetic_batch(cfg, B, S, cursor=7)
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad_finite(arch):
    cfg = reduced_f32(arch)
    params = init_params(cfg, jax.random.key(0))
    batch = batch_for(cfg)

    logits, _ = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(lambda p, b: lm_loss(p, cfg, b), has_aux=True)
    )(params, batch)
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert bool(jnp.isfinite(g).all()), f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_remat_matches_no_remat(arch):
    cfg = reduced_f32(arch)
    params = init_params(cfg, jax.random.key(1))
    batch = batch_for(cfg)
    l1, _ = lm_loss(params, cfg, batch, remat=False)
    l2, _ = lm_loss(params, cfg, batch, remat=True)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


@pytest.mark.parametrize("arch", ["tinyllama_1_1b", "mamba2_130m",
                                  "recurrentgemma_9b", "deepseek_v2_236b",
                                  "phi35_moe_42b"])
def test_decode_matches_full_forward(arch):
    """Step-by-step decode through the caches must reproduce the full
    causal forward — validates KV caches, ring buffers, recurrent states,
    the MLA absorbed path, and per-token MoE routing.

    MoE capacity_factor is raised so no tokens are dropped: capacity
    dropping is train-batch-size dependent (correct but not decode-
    comparable); drop behavior is asserted separately below."""
    cfg = dataclasses.replace(reduced_f32(arch), capacity_factor=8.0)
    params = init_params(cfg, jax.random.key(2))
    n = 12
    toks = jax.random.randint(jax.random.key(3), (B, n), 0, cfg.vocab_size)
    full_logits, _ = forward(params, cfg, {"tokens": toks})

    caches = init_caches(cfg, B, max_len=n)
    outs = []
    for t in range(n):
        logits, caches = decode_step(
            params, cfg, toks[:, t : t + 1], caches, jnp.int32(t))
        outs.append(logits[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4)


def test_windowed_chunked_attention_matches_dense():
    """The O(S·2w) chunked band attention must equal the dense masked
    implementation (recurrentgemma's sub-quadratic path)."""
    cfg = dataclasses.replace(reduced_f32("recurrentgemma_9b"), window=16)
    params = init_params(cfg, jax.random.key(4))
    toks = jax.random.randint(jax.random.key(5), (B, 64), 0, cfg.vocab_size)
    chunked, _ = forward(params, cfg, {"tokens": toks})  # 64 % 16 == 0 → chunked
    cfg_dense = dataclasses.replace(cfg, window=0)
    # emulate dense sliding window by comparing against explicit windowed mask
    # path: S == window → dense branch
    cfg_dense2 = dataclasses.replace(cfg, window=64)
    # instead: directly test attention module
    from repro.models.attention import gqa_apply, gqa_init
    p = gqa_init(jax.random.key(6), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(7), (B, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (B, 64))
    out_chunked, _ = gqa_apply(p, x, cfg=cfg, positions=pos, causal=True,
                               window=16)
    # dense path: pad sequence length so S % window != 0 → dense masked
    out_dense, _ = gqa_apply(p, x, cfg=dataclasses.replace(cfg, window=16),
                             positions=pos, causal=True, window=17)
    # window 17 isn't the same math — use the internal dense route instead:
    from repro.models import attention as att
    import math
    # call dense branch by using S % window != 0 via window=16 but S=64? S%16==0.
    # Temporarily force dense: window > S disables chunking
    out_dense2, _ = gqa_apply(p, x[:, :63], cfg=cfg,
                              positions=pos[:, :63], causal=True, window=16)
    # compare chunked vs dense on the overlapping prefix
    np.testing.assert_allclose(np.asarray(out_chunked[:, :63]),
                               np.asarray(out_dense2), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_instantiates_abstractly(arch):
    """FULL configs are exercised via eval_shape only (no allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    nbytes = sum(np.prod(s.shape) * s.dtype.itemsize
                 for s in jax.tree.leaves(shapes))
    assert nbytes > 1e8, f"{arch}: implausibly small parameter footprint"


def test_param_counts_match_published():
    """Analytic param counts are within tolerance of the published sizes."""
    expect = {
        "recurrentgemma_9b": (9e9, 0.35),
        "phi35_moe_42b": (42e9, 0.15),
        "deepseek_v2_236b": (236e9, 0.15),
        "tinyllama_1_1b": (1.1e9, 0.15),
        "stablelm_12b": (12.1e9, 0.15),
        "codeqwen15_7b": (7.3e9, 0.15),
        "deepseek_coder_33b": (33e9, 0.15),
        "mamba2_130m": (130e6, 0.35),
        "qwen2_vl_7b": (7.6e9, 0.15),
        "whisper_large_v3": (1.55e9, 0.25),
    }
    for arch, (target, tol) in expect.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, (
            f"{arch}: {n/1e9:.2f}B vs published {target/1e9:.2f}B")


def test_moe_activated_params():
    cfg = get_config("deepseek_v2_236b")
    active = cfg.active_param_count()
    assert active < 0.2 * cfg.param_count()  # 21B active of 236B


def test_moe_capacity_dropping_is_deterministic():
    """With a tight capacity factor, overloaded experts drop tokens — the
    output changes but stays finite and deterministic."""
    import jax.numpy as jnp
    from repro.models.moe import moe_apply, moe_init
    cfg = dataclasses.replace(reduced_f32("phi35_moe_42b"), capacity_factor=0.5)
    cfg_full = dataclasses.replace(cfg, capacity_factor=8.0)
    p = moe_init(jax.random.key(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    y_tight = moe_apply(p, x, cfg)
    y_tight2 = moe_apply(p, x, cfg)
    y_full = moe_apply(p, x, cfg_full)
    assert bool(jnp.isfinite(y_tight).all())
    np.testing.assert_array_equal(np.asarray(y_tight), np.asarray(y_tight2))
    assert not np.allclose(np.asarray(y_tight), np.asarray(y_full))


def test_flash_attention_matches_dense():
    """Online-softmax chunked attention == dense masked attention, for
    causal GQA, non-causal (encoder/cross), and the MLA flash path."""
    import jax.numpy as jnp
    from repro.models import attention as att
    cfg = reduced_f32("tinyllama_1_1b")
    p = jax.random.normal(jax.random.key(0), (2, 256, 4, 2, 32))
    q = p
    k = jax.random.normal(jax.random.key(1), (2, 256, 4, 32))
    v = jax.random.normal(jax.random.key(2), (2, 256, 4, 32))
    for causal in (True, False):
        out_f = att._attend_flash(q, k, v, causal=causal, scale=0.2, k_chunk=64)
        qpos = jnp.arange(256)[:, None]
        kpos = jnp.arange(256)[None, :]
        m = (kpos <= qpos) if causal else jnp.ones((256, 256), bool)
        mask = jnp.broadcast_to(m[None, None, None], (2, 4, 2, 256, 256))
        out_d = att._attend(q, k, v, mask, 0.2)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                                   rtol=2e-5, atol=2e-5)


def test_mla_flash_matches_dense():
    import dataclasses as dc
    import jax.numpy as jnp
    from repro.models import attention as att
    cfg = reduced_f32("deepseek_v2_236b")
    p = att.mla_init(jax.random.key(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 128, cfg.d_model)) * 0.3
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    out_dense, _ = att.mla_apply(p, x, cfg=cfg, positions=pos)
    old = att.FLASH_THRESHOLD
    try:
        att.FLASH_THRESHOLD = 32   # force the flash path
        out_flash, _ = att.mla_apply(p, x, cfg=cfg, positions=pos)
    finally:
        att.FLASH_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               rtol=2e-4, atol=2e-4)


def _ssd_sequential(p, x_in, cfg):
    """Plain reference: the selective-SSM recurrence one position at a time,
    in float32 at full matmul precision."""
    from repro.models.ssd import _conv_causal, _dims
    H, P, di, N = _dims(cfg)
    B, S, _ = x_in.shape
    with jax.default_matmul_precision("highest"):
        proj = x_in @ p["w_in"]
        z, xBC, dt_raw = jnp.split(proj, [di, 2 * di + 2 * N], axis=-1)
        xBC, _ = _conv_causal(xBC, p["conv_w"], p["conv_b"], None)
        x, B_, C_ = jnp.split(xBC, [di, di + N], axis=-1)
        x = x.reshape(B, S, H, P)
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        h = jnp.zeros((B, H, P, N))
        ys = []
        for t in range(S):
            h = (jnp.exp(dt[:, t] * A)[..., None, None] * h
                 + jnp.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B_[:, t]))
            ys.append(jnp.einsum("bn,bhpn->bhp", C_[:, t], h)
                      + p["D_skip"][:, None] * x[:, t])
        y = jnp.stack(ys, axis=1).reshape(B, S, di)
        from repro.models.layers import rmsnorm
        out = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
        return out @ p["w_out"]


def test_ssd_chunk_with_fast_decay_matches_the_recurrence():
    """Within a chunk, exp(cum[q] - cum[t]) for q < t grows without bound
    when dt·A is large (a long chunk of a fast-decaying head): the chunked
    scan must mask those terms before exp, not after, or inf · 0 turns
    the output and its gradient into NaN."""
    from repro.models.ssd import ssd_apply, ssd_init
    cfg = dataclasses.replace(get_reduced("mamba2_130m"), dtype="float32",
                              chunk=64)
    p = ssd_init(jax.random.key(3), cfg, dtype=jnp.float32)
    p["A_log"] = jnp.full_like(p["A_log"], np.log(16.0))
    p["dt_bias"] = jnp.full_like(p["dt_bias"], np.log(np.expm1(2.0)))
    x = jax.random.normal(jax.random.key(4), (2, 64, cfg.d_model))

    def run(p):
        with jax.default_matmul_precision("highest"):
            return ssd_apply(p, x, cfg=cfg)[0]

    got = run(p)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ssd_sequential(p, x, cfg)),
                               rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda p: jnp.sum(run(p) ** 2))(p)
    for g in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(g).all())
