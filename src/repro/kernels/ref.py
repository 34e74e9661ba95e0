"""Pure-jnp oracles for every Pallas kernel (the contract each kernel must
match under assert_allclose across shape/dtype sweeps — see tests/test_kernels)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scaled_update_ref(p, m, g, d, *, gamma, beta1, alpha, squared=True):
    m_new = beta1 * m + g
    mag = jnp.sqrt(d) if squared else jnp.abs(d)
    dhat = jnp.maximum(alpha, mag)
    return p - gamma * m_new / dhat, m_new


def debias_beta(t, *, kind, beta2, schedule, update_d):
    """Per-client debias factor β_t (M,) for step counters ``t`` (M,) i32,
    or None when the step uses no step counter (global D, the const
    schedule, AdaGrad, identity).

    ``preconditioner.beta_t`` itself — the expression the tree path
    evaluates — run in XLA outside the fused kernel, which takes the result
    as a scalar-prefetch operand (Mosaic has no ``powf``)."""
    if not (update_d and schedule == "debias"
            and kind not in ("adagrad", "identity")):
        return None
    if t is None:
        raise ValueError("debias schedule needs per-client t")
    from repro.core import preconditioner as PC
    cfg = PC.PrecondConfig(kind=kind, beta2=beta2, beta_schedule="debias")
    return PC.beta_t(cfg, t)


def fused_step_math(p, m, g, d, h, b, s, *, gamma, beta1, weight_decay,
                    alpha, beta2, kind, clip, schedule, update_d):
    """One generic-scaling local step — the paper's unified Assumption-4 rule.

    The single source of truth for the fused flat-buffer kernel
    (``scaled_update.fused_step_flat`` runs this per block; DESIGN.md §7).
    The D math itself is NOT re-implemented: this delegates to
    ``preconditioner.ema``/``dhat`` on the bare buffers, so the fused kernel
    and the engine's unfused tree path share one copy of the Assumption-4
    formulas — which is what makes the trajectories agree bitwise in fp32,
    and keeps a future rule/schedule change from silently diverging.

    ``d``/``h``/``b``/``s`` may be None when the mode doesn't use them
    (identity kind; in-kernel grad² stat; const schedule or AdaGrad; no grad
    clip). ``b`` is the debias β_t (``debias_beta``); ``b``/``s`` must
    already broadcast against ``p`` (``(M, 1)``). Returns ``(p', m', d')``
    with ``d'`` None unless ``update_d``.
    """
    from repro.core import preconditioner as PC
    cfg = PC.PrecondConfig(kind=kind, beta2=beta2, alpha=alpha, clip=clip,
                           beta_schedule=schedule)
    if s is not None:
        g = g * s                       # engine._clip's per-client scale
    d_new = None
    if update_d:                        # local scaling: D advances every step
        stat = (g ** 2) if h is None else h   # grad_stat | external Hutchinson
        if b is None:                   # const / AdaGrad: no step counter
            b = PC.beta_t(cfg, None)
        d_new = PC.ema(cfg, b, d, stat)
        d = d_new
    if weight_decay:
        g = g + weight_decay * p
    m_new = beta1 * m + g
    if kind == "identity":
        p_new = p - gamma * m_new
    else:
        p_new = p - gamma * (m_new / PC.dhat(cfg, None, leaf_of=d))
    return p_new, m_new, d_new


def fused_step_ref(p, m, g, d=None, h=None, t=None, s=None, *, gamma, beta1,
                   weight_decay=0.0, alpha, beta2=0.999, kind, clip="max",
                   schedule="const", update_d=False):
    """(M, n) reference for the fused kernel: per-row β_t/s broadcast over n."""
    b = debias_beta(t, kind=kind, beta2=beta2, schedule=schedule,
                    update_d=update_d)
    b = None if b is None else b[:, None]
    s2 = None if s is None else s[:, None]
    return fused_step_math(p, m, g, d, h, b, s2, gamma=gamma, beta1=beta1,
                           weight_decay=weight_decay, alpha=alpha, beta2=beta2,
                           kind=kind, clip=clip, schedule=schedule,
                           update_d=update_d)


def quantize_update_ref(x, u, scale):
    """Stochastic int8 QDQ: q = clip(floor(x/s + u), ±127), dec = q·s."""
    s = jnp.broadcast_to(scale, x.shape).astype(jnp.float32)
    safe = jnp.where(s > 0, s, 1.0)
    v = jnp.where(s > 0, x.astype(jnp.float32) / safe, 0.0)
    qf = jnp.clip(jnp.floor(v + u), -127.0, 127.0)
    return qf.astype(jnp.int8), (qf * s).astype(x.dtype)


def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """q (B,H,S,D), k/v (B,Hk,S,D) -> (B,H,S,D). Dense fp32 softmax."""
    B, H, S, D = q.shape
    Hk = k.shape[1]
    rep = H // Hk
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * D**-0.5, kf)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    i = jnp.arange(S)
    mask = i[None, :] <= i[:, None]
    if window:
        mask &= (i[:, None] - i[None, :]) < window
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vf)
    return out.astype(q.dtype)


def decode_attention_math(q, k, v, bias, softcap):
    """Single-query decode attention for one (batch-slot, kv-head) cell.

    q (..., R, D) query heads sharing one kv head; k (..., C, D),
    v (..., C, Dv); bias (..., C) additive fp32 mask (causal/window/ring
    validity, from models.layers._mask_bias). The single source of truth for
    ``decode_step.decode_attention``: contractions are elementwise-mul +
    axis-sum (not dot_general) so the per-cell kernel blocks and the batched
    oracle accumulate in the same order — fused == unfused *bitwise*.
    """
    qf = q.astype(jnp.float32) * (q.shape[-1] ** -0.5)
    kf = k.astype(jnp.float32)
    s = (qf[..., :, None, :] * kf[..., None, :, :]).sum(-1)       # (..., R, C)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias[..., None, :].astype(jnp.float32)
    w = jax.nn.softmax(s, axis=-1)
    vf = v.astype(jnp.float32)
    return (w[..., :, :, None] * vf[..., None, :, :]).sum(-2)     # (..., R, Dv)


def decode_attention_ref(q, k, v, bias, *, softcap=0.0):
    """q (B,H,D), k/v (B,C,Hk,D/Dv) cache layout, bias (B,C) -> (B,H,Dv)."""
    B, H, D = q.shape
    Hk = k.shape[2]
    rep = H // Hk
    qr = q.reshape(B, Hk, rep, D)
    kr = k.transpose(0, 2, 1, 3)                                  # (B,Hk,C,D)
    vr = v.transpose(0, 2, 1, 3)
    out = decode_attention_math(qr, kr, vr, bias[:, None, :], softcap)
    return out.reshape(B, H, -1)


def decode_sample_math(y, table, noise, scale):
    """One vocab-block logit tile: (y·table_v)*scale + noise.

    y (B,d), table (blk,d), noise (B,blk) -> (B,blk) fp32. Mul+sum
    contraction for the same bitwise reason as ``decode_attention_math``.
    """
    s = (y.astype(jnp.float32)[:, None, :]
         * table.astype(jnp.float32)[None, :, :]).sum(-1)
    return s * scale + noise.astype(jnp.float32)


def decode_sample_ref(y, table, noise, *, scale, v_real, block=2048):
    """Blockwise argmax over the vocab, walking blocks in kernel order (the
    strict ``>`` running compare reproduces full-argmax first-index
    tie-breaking). Returns token ids (B,) int32."""
    V = table.shape[0]
    block = min(block, V)
    assert V % block == 0, (V, block)
    vidx = jnp.arange(V)
    best = jnp.full((y.shape[0],), -jnp.inf, jnp.float32)
    arg = jnp.zeros((y.shape[0],), jnp.int32)
    for j in range(V // block):
        sl = slice(j * block, (j + 1) * block)
        logits = decode_sample_math(y, table[sl], noise[:, sl], scale)
        logits = jnp.where(vidx[None, sl] < v_real, logits, -1e30)
        m = logits.max(axis=1)
        a = (j * block + jnp.argmax(logits, axis=1)).astype(jnp.int32)
        upd = m > best
        arg = jnp.where(upd, a, arg)
        best = jnp.where(upd, m, best)
    return arg


def ssd_ref(xh, dt, A, Bm, Cm):
    """Naive sequential SSD recurrence (see models/ssm.ssd_reference)."""
    from repro.models.ssm import ssd_reference
    return ssd_reference(xh, dt, A, Bm, Cm)
