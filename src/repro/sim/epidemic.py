"""An epicast-like agent/metapopulation epidemic model in JAX (Sec. 3.3).

epicast is an MPI agent-based influenza/COVID model over census tracts; this
stand-in is a stochastic SEIR metapopulation over ``n_patches`` tracts with
commuting coupling, global parameters (R0-like infectivity, latent /
infectious periods) and local parameters (seed size, compliance), plus
non-pharmaceutical-intervention scenarios (contact reduction starting at an
intervention day) — enough structure to reproduce the paper's two-phase
calibrate -> forecast cascading workflow with real dynamics.

Inputs u (6,) in [0,1]:
  0 beta        base transmission rate      [0.15, 0.60]
  1 latent      1/sigma days                [2.0, 5.0]
  2 infectious  1/gamma days                [3.0, 8.0]
  3 seed        initial exposed fraction    [1e-5, 1e-3] (log)
  4 compliance  NPI contact reduction       [0.0, 0.8]
  5 start_day   NPI start day               [5, 40]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# host constant: a device array here would start a JAX backend at import
EPI_BOUNDS = np.array([
    [0.15, 0.60],
    [2.0, 5.0],
    [3.0, 8.0],
    [-5.0, -3.0],   # log10 seed
    [0.0, 0.8],
    [5.0, 40.0],
], np.float32)

N_PATCH = 16
T_DAYS = 60


def _rescale(u):
    lo, hi = EPI_BOUNDS[:, 0], EPI_BOUNDS[:, 1]
    return lo + jnp.clip(u, 0, 1) * (hi - lo)


def seir_simulate(u, rng, t_days: int = T_DAYS):
    """u: (6,) in [0,1] -> dict with daily new cases etc."""
    x = _rescale(u)
    beta, lat, inf, lseed, comp, d0 = x[0], x[1], x[2], x[3], x[4], x[5]
    sigma, gamma = 1.0 / lat, 1.0 / inf
    seed = 10.0 ** lseed

    k1, k2, k3 = jax.random.split(rng, 3)
    pop = 2000.0 * jnp.exp(0.3 * jax.random.normal(k1, (N_PATCH,)))
    # commuting coupling: mostly local contacts, some global mixing
    mix = 0.85 * jnp.eye(N_PATCH) + 0.15 / N_PATCH
    seed_patch = jax.random.uniform(k2, (N_PATCH,)) < 0.3
    E0 = pop * seed * seed_patch
    S0 = pop - E0

    def day(state, t):
        S, E, I, R, key = state
        key, sub = jax.random.split(key)
        npi = jnp.where(t >= d0, 1.0 - comp, 1.0)
        force = beta * npi * (mix @ (I / pop))
        new_e = S * (1 - jnp.exp(-force))
        # demographic noise
        new_e = jnp.clip(new_e * (1 + 0.08 * jax.random.normal(sub, (N_PATCH,))),
                         0.0, S)
        new_i = sigma * E
        new_r = gamma * I
        S = S - new_e
        E = E + new_e - new_i
        I = I + new_i - new_r
        R = R + new_r
        return (S, E, I, R, key), new_i.sum()

    init = (S0, E0, jnp.zeros(N_PATCH), jnp.zeros(N_PATCH), k3)
    (_, _, _, R, _), daily = jax.lax.scan(day, init, jnp.arange(t_days))
    total = R.sum() + daily[-1]
    peak_day = jnp.argmax(daily).astype(jnp.float32)
    return {
        "daily_cases": daily.astype(jnp.float32),
        "attack_rate": (total / pop.sum()).astype(jnp.float32),
        "peak_day": peak_day,
        "peak_cases": daily.max().astype(jnp.float32),
        "inputs": u.astype(jnp.float32),
    }
