"""Plain reference of the Mamba-2 language model the benchmark trains.

Written from the Mamba-2 paper (Dao & Gu, arXiv:2405.21060) in straight
``jax.numpy`` at float32 with every matrix product at ``highest``
precision, for the checks that decide a run's ``correct``. It imports
nothing of the program under test. It also makes the weights both sides
train from, so neither takes anything the other made.

One layer, for a residual stream ``u`` of width ``d_model``:

    h            = rmsnorm(u) * norm1
    z, xBC, dt   = split(h @ w_in)                 # widths di, di+2N, H
    xBC          = silu(causal_depthwise_conv(xBC, conv_w) + conv_b)
    x, B, C      = split(xBC)                      # widths di, N, N
    dt           = softplus(dt + dt_bias);  a = -exp(A_log)
    s_t          = exp(dt_t a) s_{t-1} + dt_t x_t B_t^T     # per head (P, N)
    y_t          = s_t C_t + D_skip x_t
    u           += (rmsnorm(y * silu(z)) * norm) @ w_out

then ``logits = rmsnorm(u) * final_norm @ embed^T`` over the padded
vocabulary, and the loss is the mean cross-entropy over the tokens whose
label is not -1. The recurrence runs one chunk at a time: inside a chunk as
the masked quadratic form, between chunks by carrying ``s`` in a
sequential ``lax.scan`` (the test checks it against the step-by-step
recurrence). Heads are ``nheads`` of ``headdim``, and the vocabulary is
padded to ``pad_vocab_size_multiple``, as the configuration file states.

AdamW follows the configuration's ``optimizer``: global-norm clipping,
bias-corrected moments in float32, decoupled weight decay on every leaf,
and a learning rate scaled by linear warm-up then cosine decay. A leaf
kept in bfloat16 is updated in float32 and rounded back to bfloat16, as
mixed-precision training stores it.

The harness's knowledge of the architecture lives here too, so that it
names no key of one model: ``PROGRAM`` (the program's model as the file
states it, and what the built model is held to), ``train_flops_per_token``
(the yardstick of ``mfu.*``) and ``REDUCED`` (the CPU rehearsal's sizes).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

def dims(cfg: Dict) -> Dict[str, int]:
    """Sizes derived from a configuration file's model keys."""
    H, P, N = cfg["nheads"], cfg["headdim"], cfg["d_state"]
    di = H * P
    mult = cfg["pad_vocab_size_multiple"]
    return dict(D=cfg["d_model"], L=cfg["n_layer"], H=H, P=P, N=N, di=di,
                C=di + 2 * N, E=2 * di + 2 * N + H, K=cfg["d_conv"],
                Q=cfg["chunk_size"], V=-(-cfg["vocab_size"] // mult) * mult)


# ------------------------------------------------ the harness's questions

#: the CPU rehearsal's sizes, the program's ``mamba2_130m.reduced()``
REDUCED = dict(d_model=64, n_layer=4, d_state=16, nheads=4, headdim=16,
               vocab_size=512, pad_vocab_size_multiple=16, chunk_size=16)

#: each field of the program's ``ModelConfig`` that the file states: the
#: file's key that gives it, and the attribute of the built model that is
#: held to that key (None where the built model has none of its own)
PROGRAM = {
    "num_layers": ("n_layer", "num_layers"),
    "d_model": ("d_model", "d_model"),
    "ssm_state": ("d_state", "ssm_state"),
    "ssm_heads": ("nheads", "padded_ssm_heads"),
    "ssm_head_dim": ("headdim", "ssm_head_dim"),
    "expand": ("expand", "expand"),
    "conv_kernel": ("d_conv", "conv_kernel"),
    "chunk": ("chunk_size", "chunk"),
    "vocab_size": ("vocab_size", "vocab_size"),
    "vocab_pad": ("pad_vocab_size_multiple", None),
    "tie_embeddings": ("tie_embeddings", "tie_embeddings"),
    "norm_eps": ("rms_norm_eps", "norm_eps"),
    "dtype": ("dtype", "dtype"),
}


def train_flops_per_token(cfg: Dict) -> float:
    """FLOPs of one training step per token as the configuration runs it:
    forward and backward (twice the forward), with nothing recomputed
    counted.

    Per layer and token, the forward's matrix products are the input
    projection (2 D E), the output projection (2 di D), and the chunked
    SSD scan with chunk length Q: C B^T inside the chunk (2 Q N), the
    decay-weighted sum over the chunk (2 Q H P), the chunk's end state
    (2 H P N) and the inter-chunk output (2 H P N); the depthwise
    convolution adds 2 K (di + 2 N). The tied output projection over the
    padded vocabulary adds 2 D V per token."""
    d = dims(cfg)
    D, L, H, P, N, di, E, K, V = (
        d[k] for k in "D L H P N di E K V".split())
    Q = min(d["Q"], cfg["seq"])
    layer = (2 * D * E + 2 * di * D + 2 * Q * N + 2 * Q * H * P
             + 4 * H * P * N + 2 * K * (di + 2 * N))
    return 3.0 * (L * layer + 2 * D * V)


# ------------------------------------------------------------------ weights

def _init(cfg: Dict, key: jax.Array):
    d = dims(cfg)
    D, L, H, di, C, E, K, V = (d[k] for k in "D L H di C E K V".split())
    wdt = jnp.dtype(cfg["dtype"])
    ks = jax.random.split(key, 8)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(wdt)

    dt0 = jnp.exp(jax.random.uniform(ks[5], (L, H), jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    layer = {
        "norm1": jnp.ones((L, D), wdt),
        "ssd": {
            "w_in": normal(ks[1], (L, D, E), 1.0 / math.sqrt(D)),
            "conv_w": normal(ks[2], (L, K, C), 0.1),
            "conv_b": normal(ks[3], (L, C), 0.01),
            "A_log": jnp.log(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                                1.0, 16.0)),
            "D_skip": jnp.ones((L, H), jnp.float32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),   # softplus^-1(dt0)
            "norm": jnp.ones((L, di), wdt),
            "w_out": normal(ks[6], (L, di, D), 1.0 / math.sqrt(di)),
        },
    }
    return {"embed": normal(ks[0], (V, D), 0.02),
            "final_norm": jnp.ones((D,), wdt),
            "decoder": {"seg0": {"b0": layer}}}


def make_weights(cfg: Dict, seed: int):
    """The model's weights from ``seed``, made on the default device in one
    jitted call, each leaf in the type it is trained in."""
    init = jax.jit(_init, static_argnums=0)
    return init(_Frozen(cfg), jax.random.key(seed))


class _Frozen(dict):
    """A configuration dict that ``jit`` can take as a static argument."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))


# ------------------------------------------------------------------ forward

def _mm(lo):
    """Matrix product whose operands are first rounded to ``lo`` (None =
    float32 as is). A rounded operand is scaled by its largest magnitude
    first, as a low-precision path scales each tensor, so that the
    rounding and not the type's range makes the difference."""
    if lo is None:
        return jnp.matmul, jnp.einsum

    def rnd(x):
        top = jnp.float32(float(jnp.finfo(lo).max))
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / s).astype(lo).astype(jnp.float32) * s

    return (lambda a, b: jnp.matmul(rnd(a), rnd(b)),
            lambda spec, *ops: jnp.einsum(spec, *map(rnd, ops)))


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def ssd_chunked(x, dt, a, B, C, Q: int, lo=None):
    """The selective scan ``s_t = exp(dt_t a) s_{t-1} + dt_t x_t B_t^T``,
    ``y_t = s_t C_t`` for one sequence: x (S, H, P), dt (S, H), a (H,),
    B and C (S, N). Returns y (S, H, P)."""
    mm, ein = _mm(lo)
    S, H, P = x.shape
    N = B.shape[-1]
    Q = min(Q, S)
    nc = S // Q
    xs = (x.reshape(nc, Q, H, P), dt.reshape(nc, Q, H),
          B.reshape(nc, Q, N), C.reshape(nc, Q, N))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk(s, inp):
        xq, dq, bq, cq = inp
        la = jnp.cumsum(dq * a, axis=0)                      # (Q, H) log decay
        # decay from step t to step q (q >= t); masked to 0 above
        seg = la[:, None, :] - la[None, :, :]                # (Q, Q, H)
        w = jnp.where(causal[..., None], jnp.exp(jnp.where(
            causal[..., None], seg, 0.0)), 0.0)
        cb = mm(cq, bq.T)                                    # (Q, Q)
        y = ein("qt,qth,th,thp->qhp", cb, w, dq, xq)
        y = y + ein("qn,qh,hpn->qhp", cq, jnp.exp(la), s)
        tail = jnp.exp(la[-1][None, :] - la) * dq            # (Q, H)
        s = (jnp.exp(la[-1])[:, None, None] * s
             + ein("th,thp,tn->hpn", tail, xq, bq))
        return s, y

    s0 = jnp.zeros((H, P, N), jnp.float32)
    _, y = jax.lax.scan(chunk, s0, xs)
    return y.reshape(S, H, P)


def ssd_sequential(x, dt, a, B, C):
    """The same recurrence one step at a time (for the tests)."""
    H, P = x.shape[1:]
    N = B.shape[-1]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * a)[:, None, None] * s + jnp.einsum(
            "h,hp,n->hpn", dtt, xt, bt)
        return s, jnp.einsum("hpn,n->hp", s, ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C))
    return y


def _layer(cfg: Dict, u, p, lo=None):
    mm, _ = _mm(lo)
    d = dims(cfg)
    H, P, N, di, K = d["H"], d["P"], d["N"], d["di"], d["K"]
    eps = cfg["rms_norm_eps"]
    S = u.shape[0]
    h = _rmsnorm(u, p["norm1"], eps)
    proj = mm(h, p["ssd"]["w_in"])
    z, xbc, dtr = proj[:, :di], proj[:, di:2 * di + 2 * N], proj[:, 2 * di + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j:j + S] * p["ssd"]["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(conv + p["ssd"]["conv_b"])
    x, B, C = xbc[:, :di], xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dtr + p["ssd"]["dt_bias"])
    a = -jnp.exp(p["ssd"]["A_log"])
    xh = x.reshape(S, H, P)
    y = ssd_chunked(xh, dt, a, B, C, cfg["chunk_size"], lo)
    y = (y + p["ssd"]["D_skip"][None, :, None] * xh).reshape(S, di)
    y = _rmsnorm(y * jax.nn.silu(z), p["ssd"]["norm"], eps)
    return u + mm(y, p["ssd"]["w_out"])


def sequence_nll(cfg: Dict, params, tokens, labels, lo=None):
    """Sum of the token losses of one sequence, and how many were counted.
    ``params`` are float32; ``lo`` as in :func:`_mm`."""
    mm, _ = _mm(lo)
    u = params["embed"][tokens]

    def body(u, p):
        return jax.checkpoint(lambda u, p: _layer(cfg, u, p, lo))(u, p), None

    u, _ = jax.lax.scan(body, u, params["decoder"]["seg0"]["b0"])
    u = _rmsnorm(u, params["final_norm"], cfg["rms_norm_eps"])
    logits = mm(u, params["embed"].T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], -1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def loss_and_grad(cfg: Dict, params, tokens, labels, lo=None):
    """Mean token loss of the batch and its gradient with respect to the
    float32 ``params``, one sequence at a time so that it fits."""
    def row(acc, tl):
        (nll, n), g = jax.value_and_grad(
            lambda p: sequence_nll(cfg, p, *tl, lo), has_aux=True)(params)
        tot, cnt, gs = acc
        return (tot + nll, cnt + n, jax.tree.map(jnp.add, gs, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (tot, cnt, g), _ = jax.lax.scan(row, (0.0, 0.0, zero), (tokens, labels))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt, jax.tree.map(lambda x: x / cnt, g)


# ---------------------------------------------------------------- optimizer

def lr_scale(opt: Dict, count):
    count = jnp.asarray(count, jnp.float32)
    warm = jnp.minimum(count / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = jnp.clip((count - opt["warmup_steps"]) / span, 0.0, 1.0)
    floor = opt["lr_floor"]
    return warm * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))


def adamw(opt: Dict, params, grads, m, v, count):
    """One AdamW step. ``params`` in their stored types; returns the new
    params (same types), moments, and the clipped gradient it applied."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    g = jax.tree.map(lambda x: x * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    t = count + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    lr = opt["lr"] * lr_scale(opt, count)

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        return (p32 - lr * (step + opt["weight_decay"] * p32)).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v, g


# -------------------------------------------------------------- the check

def leaf_norms(tree) -> Dict[str, float]:
    """Float32 norm of every leaf, keyed by its path as 'a/b/c'."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = jnp.linalg.norm(leaf.astype(jnp.float32).reshape(-1))
    return jax.device_get(out)


def train_readings(cfg: Dict, seed: int, batches: List[Tuple], *,
                   compute_dtype=None) -> Dict:
    """Train ``len(batches)`` steps from the seed's weights and return what
    the check compares: each step's loss, the first step's clipped
    gradient norm per leaf, and each leaf's change over all the steps.

    ``compute_dtype`` (None = float32) rounds both operands of every
    matrix product to that type: the control, which computes below the
    precision the configuration states."""
    opt = cfg["optimizer"]
    params = make_weights(cfg, seed)
    start = params
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    @jax.jit
    def step(params, m, v, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            loss, g = loss_and_grad(cfg, p32, tokens, labels, compute_dtype)
            params, m, v, gc = adamw(opt, params, g, m, v, count)
        return params, m, v, loss, gc

    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        params, m, v, loss, gc = step(params, m, v, jnp.int32(i),
                                      jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(loss))
        if i == 0:
            first_grad = leaf_norms(gc)
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, start))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}
