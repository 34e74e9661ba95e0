"""Preconditioners under the paper's unified Assumption 4.

The paper analyses any diagonal scaling D̂ with ``αI ⪯ D̂ ⪯ ΓI`` built from one
of two EMA rules plus a positivity clip:

  rule (2):  (D^t)² = β_t (D^{t-1})² + (1-β_t) (H^t)²      (Adam / RMSProp /
                                                            AdaHessian / AdaGrad)
  rule (3):   D^t   = β_t  D^{t-1}   + (1-β_t)  H^t        (OASIS)
  rule (4):  (D̂)_ii = max{α, |D_ii|}   or   |D_ii| + α

with H^t one of
  * diag(g ⊙ g)                       — gradient second moment (Adam family)
  * diag(v ⊙ ∇²f v), v ~ Rademacher   — Hutchinson diagonal-Hessian estimate
                                        (OASIS / AdaHessian), computed with one
                                        extra HVP, never a full Hessian.

β_t schedules: constant (RMSProp/OASIS) or Adam's debiasing
β_t = (β - β^{t+1}) / (1 - β^{t+1}).  AdaGrad is the β_t→accumulate limit
(D² += H², no decay), included because the compared baseline [42] uses it.

All state lives in a plain dict pytree so it shards/checkpoints like params:
``{"d": tree, "t": i32}`` where ``d`` stores D (rule 3) or D² (rule 2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

KINDS = ("identity", "adam", "rmsprop", "adagrad", "oasis", "adahessian")


@dataclasses.dataclass(frozen=True)
class PrecondConfig:
    kind: str = "adam"
    beta2: float = 0.999
    alpha: float = 1e-8            # rule-(4) floor — the paper's α
    clip: str = "max"              # "max" (eq. 4) | "add"
    # β_t schedule: "const" | "debias" (Adam's (β-β^{t+1})/(1-β^{t+1}))
    beta_schedule: Optional[str] = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind {self.kind}; expected one of {KINDS}")

    @property
    def rule(self) -> str:
        # eq. (2) squared EMA vs eq. (3) linear EMA
        return "linear" if self.kind == "oasis" else "squared"

    @property
    def schedule(self) -> str:
        if self.beta_schedule:
            return self.beta_schedule
        return "debias" if self.kind in ("adam", "adahessian") else "const"

    @property
    def uses_hutchinson(self) -> bool:
        return self.kind in ("oasis", "adahessian")


def init_state(cfg: PrecondConfig, params):
    """D^0 = I (satisfies Assumption 4 with α ≤ 1 ≤ Γ)."""
    if cfg.kind == "identity":
        return {"t": jnp.int32(0)}
    d = jax.tree.map(lambda p: jnp.ones_like(p, dtype=jnp.float32), params)
    return {"d": d, "t": jnp.int32(0)}


def beta_t(cfg: PrecondConfig, t):
    """β_{t+1} for the update at step t (0-based)."""
    b = cfg.beta2
    if cfg.kind == "adagrad":
        return None  # accumulate
    if cfg.schedule == "const":
        return jnp.float32(b)
    tt = t.astype(jnp.float32) + 1.0   # 1-based update index
    return (b - b ** tt) / (1.0 - b ** tt)


def grad_stat(grads):
    """H² for the Adam family: diag(g⊙g) (returned squared)."""
    return jax.tree.map(lambda g: (g.astype(jnp.float32)) ** 2, grads)


def hutchinson_diag(loss_fn: Callable, params, batch, key):
    """diag(v ⊙ ∇²f(x) v) with Rademacher v — one HVP via jvp-of-grad."""
    leaves = jax.tree.leaves(params)
    keys = jax.random.split(key, len(leaves))
    kit = iter(keys)
    v = jax.tree.map(
        lambda p: jax.random.rademacher(next(kit), p.shape,
                                        jnp.float32).astype(p.dtype), params)
    g_fn = jax.grad(lambda p: loss_fn(p, batch))
    _, hvp = jax.jvp(g_fn, (params,), (v,))
    return jax.tree.map(lambda vi, hi: (vi.astype(jnp.float32)
                                        * hi.astype(jnp.float32)), v, hvp)


def update(cfg: PrecondConfig, state, stat):
    """One D update from a stat tree.

    ``stat`` semantics: for rule (2) kinds, ``stat`` is H² (already squared);
    for rule (3) (OASIS), ``stat`` is H itself (may be negative — the clip
    handles sign).
    """
    if cfg.kind == "identity":
        return {"t": state["t"] + 1}
    t = state["t"]
    b = beta_t(cfg, t)
    d = jax.tree.map(lambda dd, h: ema(cfg, b, dd, h), state["d"], stat)
    return {"d": d, "t": t + 1}


def ema(cfg: PrecondConfig, b, d, stat):
    """One leaf of the D update with β_{t+1} = ``b`` (None for AdaGrad).

    Rule (2) keeps D² and rule (3) keeps D, so both are the same EMA of the
    stored quantity; AdaGrad accumulates without decay."""
    if cfg.kind == "adagrad":
        return d + stat
    return b * d + (1.0 - b) * stat


def dhat(cfg: PrecondConfig, state, leaf_of=None):
    """The clipped diagonal D̂ (rule 4), as a tree (or one leaf)."""

    def one(d):
        mag = jnp.sqrt(d) if cfg.rule == "squared" or cfg.kind == "adagrad" \
            else jnp.abs(d)
        if cfg.clip == "max":
            return jnp.maximum(cfg.alpha, mag)
        return mag + cfg.alpha

    if cfg.kind == "identity":
        return None
    if leaf_of is not None:
        return one(leaf_of)
    return jax.tree.map(one, state["d"])


def precondition(cfg: PrecondConfig, state, grads):
    """D̂^{-1} g — the scaled direction of Algorithm 1."""
    if cfg.kind == "identity":
        return grads
    dh = dhat(cfg, state)
    return jax.tree.map(lambda g, d: (g.astype(jnp.float32) / d).astype(g.dtype),
                        grads, dh)


def bounds(cfg: PrecondConfig, state):
    """(min, max) eigenvalue of D̂ across the tree — Lemma 1 check (α ≤ · ≤ Γ)."""
    if cfg.kind == "identity":
        return jnp.float32(1.0), jnp.float32(1.0)
    dh = dhat(cfg, state)
    mins = jnp.stack([x.min() for x in jax.tree.leaves(dh)])
    maxs = jnp.stack([x.max() for x in jax.tree.leaves(dh)])
    return mins.min(), maxs.max()


def theory_beta_lower_bound(cfg: PrecondConfig, gamma, mu, Gamma):
    """Corollary 1's β_{t+1} lower bound keeping the norm-drift ≤ (1+γμ/2Γ)."""
    a = cfg.alpha
    if cfg.rule == "squared":
        return 1.0 - gamma * mu * a**2 / Gamma**3
    return 1.0 - gamma * mu * a / (4.0 * Gamma**2)
