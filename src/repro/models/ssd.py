"""Mamba-2 block via SSD (state-space duality, arXiv:2405.21060).

The selective SSM  h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t,  y_t = C_t·h_t
is computed with the chunked SSD algorithm: within chunks of length Q the
recurrence is materialized as a masked quadratic form (MXU-friendly),
between chunks only the (H, P, N) states are passed through a scan —
O(S·Q + S·N·P) work, sub-quadratic in S, constant-memory decode.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init, rmsnorm, rmsnorm_init


def _dims(cfg):
    H = cfg.padded_ssm_heads
    P = cfg.ssm_head_dim
    return H, P, H * P, cfg.ssm_state


def ssd_init(key, cfg, *, dtype) -> Params:
    H, P, di, N = _dims(cfg)
    D = cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        # fused input projection: [z (gate), x, B, C, dt]
        "w_in": dense_init(ks[0], D, 2 * di + 2 * N + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_kernel, di + 2 * N)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((di + 2 * N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01))).astype(jnp.float32),
        "norm": rmsnorm_init(di, dtype),
        "w_out": dense_init(ks[2], di, D, dtype, scale=1.0 / math.sqrt(di)),
    }


def _conv_causal(u, w, b, state):
    B, S, C = u.shape
    k = w.shape[0]
    pad = state if state is not None else jnp.zeros((B, k - 1, C), u.dtype)
    full = jnp.concatenate([pad, u], axis=1)
    out = jnp.zeros_like(u)
    for j in range(k):
        out = out + full[:, j : j + S, :] * w[j]
    return jax.nn.silu(out + b), full[:, -(k - 1):, :]


def ssd_apply(
    p: Params,
    x_in: jax.Array,
    *,
    cfg,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """state = {"h": (B,H,P,N) f32, "conv": (B,k-1,di+2N)}."""
    B, S, D = x_in.shape
    H, P, di, N = _dims(cfg)
    proj = x_in @ p["w_in"]
    z, xBC, dt_raw = jnp.split(proj, [di, 2 * di + 2 * N], axis=-1)
    conv_state = state["conv"] if state is not None else None
    xBC, new_conv = _conv_causal(xBC, p["conv_w"], p["conv_b"], conv_state)
    x, B_, C_ = jnp.split(xBC, [di, di + N], axis=-1)
    x = x.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)
    dA = dt * A                                                       # (B,S,H) ≤ 0
    Bx = B_.astype(jnp.float32)
    Cx = C_.astype(jnp.float32)
    xf = x.astype(jnp.float32)

    h0 = state["h"] if state is not None else None
    if S == 1 and h0 is not None:
        # ------------------------- decode step ---------------------------
        decay = jnp.exp(dA[:, 0])                                     # (B,H)
        h = decay[..., None, None] * h0 + jnp.einsum(
            "bh,bhp,bn->bhpn", dt[:, 0], xf[:, 0], Bx[:, 0])
        y = jnp.einsum("bn,bhpn->bhp", Cx[:, 0], h)
        y = y + p["D_skip"][:, None] * xf[:, 0]
        ys = y.reshape(B, 1, di)
    else:
        # ---------------------- chunked SSD scan -------------------------
        Q = min(cfg.chunk, S)
        assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
        nc = S // Q
        dAc = dA.reshape(B, nc, Q, H)
        cum = jnp.cumsum(dAc, axis=2)                                 # (B,c,Q,H)
        total = cum[:, :, -1]                                         # (B,c,H)
        xc = xf.reshape(B, nc, Q, H, P)
        Bc = Bx.reshape(B, nc, Q, N)
        Cc = Cx.reshape(B, nc, Q, N)
        dtc = dt.reshape(B, nc, Q, H)

        # intra-chunk quadratic form
        scores = jnp.einsum("bcqn,bctn->bcqt", Cc, Bc)                # (B,c,Q,Q)
        causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
        # mask BEFORE exp: for q < t, cum[q] - cum[t] > 0 grows with the
        # chunk's decay and overflows to inf, and inf · 0 is NaN
        seg = jnp.where(causal[None, None, :, :, None],
                        cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf)
        decay_qt = jnp.exp(seg)                                       # (B,c,Q,Q,H)
        w_qt = scores[..., None] * decay_qt * dtc[:, :, None]
        y_intra = jnp.einsum("bcqth,bcthp->bcqhp", w_qt, xc)

        # chunk end-states
        endw = jnp.exp(total[:, :, None] - cum) * dtc                 # (B,c,Q,H)
        chunk_state = jnp.einsum("bcqh,bcqhp,bcqn->bchpn", endw, xc, Bc)

        # inter-chunk recurrence over nc chunks
        decay_chunk = jnp.exp(total)                                  # (B,c,H)
        def combine(l, r):
            al, sl = l
            ar, sr = r
            return al * ar, sl * ar[..., None, None] + sr
        _, states = jax.lax.associative_scan(
            combine, (decay_chunk, chunk_state), axis=1)              # zero-init
        if h0 is not None:
            cumdecay = jnp.cumprod(decay_chunk, axis=1)               # (B,c,H)
            states = states + cumdecay[..., None, None] * h0[:, None]
        first = (h0[:, None] if h0 is not None
                 else jnp.zeros((B, 1, H, P, N)))
        prev = jnp.concatenate([first, states[:, :-1]], axis=1)      # (B,c,H,P,N)
        y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                             Cc, jnp.exp(cum), prev)
        y = (y_intra + y_inter).reshape(B, S, H, P)
        y = y + p["D_skip"][None, None, :, None] * xf
        ys = y.reshape(B, S, di)
        h = states[:, -1]

    out = ys.astype(x_in.dtype) * jax.nn.silu(z)
    out = rmsnorm(out, p["norm"], cfg.norm_eps)
    return out @ p["w_out"], {"h": h, "conv": new_conv}


def ssd_state_init(cfg, batch: int, dtype) -> Dict[str, jax.Array]:
    H, P, di, N = _dims(cfg)
    return {
        "h": jnp.zeros((batch, H, P, N), dtype=jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, di + 2 * N), dtype=dtype),
    }
