"""Plain reference of the seir_covid configuration.

A stochastic SEIR metapopulation of 16 patches over 60 days, written from
the configuration's description and importing nothing of the program.  One
sample at a time in ``jax.numpy`` on the host CPU, with no bucketing, mesh
or bundler.

Inputs u (6,) in [0, 1] rescale to: transmission rate [0.15, 0.60], latent
period [2, 5] days, infectious period [3, 8] days, log10 seed fraction
[-5, -3], NPI compliance [0, 0.8], NPI start day [5, 40].  Sample ``i``
splits ``jax.random.PRNGKey(i)`` into three keys: patch populations
(2000 * exp(0.3 N(0, 1))), seeded patches (U(0, 1) < 0.3), and the daily
demographic noise (the third key split once a day, N(0, 1) per patch).
Coupling is 0.85 I + 0.15 / 16 everywhere.

``dtype`` is the precision the reference computes in: float32 (the coupling
product at ``highest``) for the reference, bfloat16 for the control.
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench.compare import FLOOR, gap as _gap, one_at_a_time

LIMITS = {"nan_mismatch": 0, "value_gap": 1e-2}

BOUNDS = np.array([[0.15, 0.60], [2.0, 5.0], [3.0, 8.0], [-5.0, -3.0],
                   [0.0, 0.8], [5.0, 40.0]])
N_PATCH, T_DAYS = 16, 60


def ambiguous(u: np.ndarray) -> np.ndarray:
    """Rows whose NPI start day sits within rounding of a whole day: there
    the intervention starts on either day, and both answers are right."""
    d0 = BOUNDS[5, 0] + np.clip(u[:, 5], 0, 1) * (BOUNDS[5, 1] - BOUNDS[5, 0])
    return np.abs(d0 - np.round(d0)) < 1e-4


@functools.lru_cache(maxsize=None)
def _sample_fn(dtype: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def one(u, sid):
        lo = jnp.asarray(BOUNDS[:, 0], dt)
        hi = jnp.asarray(BOUNDS[:, 1], dt)
        x = lo + jnp.clip(u.astype(dt), 0, 1) * (hi - lo)
        beta, lat, inf, lseed, comp, d0 = (x[i] for i in range(6))
        sigma, gamma = 1.0 / lat, 1.0 / inf
        seed = 10.0 ** lseed
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sid), 3)
        pop = 2000.0 * jnp.exp(0.3 * jax.random.normal(k1, (N_PATCH,)).astype(dt))
        mix = (0.85 * jnp.eye(N_PATCH) + 0.15 / N_PATCH).astype(dt)
        seeded = jax.random.uniform(k2, (N_PATCH,)) < 0.3
        E = pop * seed * seeded.astype(dt)
        S = pop - E
        zero = jnp.zeros(N_PATCH, dt)

        def day(state, t):
            S, E, I, R, key = state
            key, sub = jax.random.split(key)
            npi = jnp.where(t >= d0, 1.0 - comp, jnp.asarray(1.0, dt))
            force = beta * npi * (mix @ (I / pop))
            new_e = S * (1 - jnp.exp(-force))
            noise = jax.random.normal(sub, (N_PATCH,)).astype(dt)
            new_e = jnp.clip(new_e * (1 + 0.08 * noise), 0.0, S)
            new_i = sigma * E
            new_r = gamma * I
            return ((S - new_e, E + new_e - new_i, I + new_i - new_r,
                     R + new_r, key), new_i.sum())

        (_, _, _, R, _), daily = jax.lax.scan(
            day, (S, E, zero, zero, k3), jnp.arange(T_DAYS))
        total = R.sum() + daily[-1]
        return {"daily_cases": daily,
                "attack_rate": total / pop.sum(),
                "peak_day": jnp.argmax(daily).astype(dt),
                "peak_cases": daily.max(),
                "inputs": u.astype(dt)}

    return jax.jit(one)


def simulate(ids: np.ndarray, u: np.ndarray, dtype: str = "float32") -> dict:
    """The model at sample ids ``ids`` with inputs ``u`` (rows of ``u``),
    one sample at a time on the host CPU."""
    return one_at_a_time(_sample_fn(dtype), ids, u)


def gap(got: dict, ref: dict, floor: float = FLOOR) -> dict:
    """As ``chipbench.compare.gap``, except that the peak day is compared by the
    reference's cases on the day each side names: two days whose cases
    tie to rounding are both the peak."""
    got, ref = dict(got), dict(ref)
    rows = np.arange(len(ref["daily_cases"]))
    day = np.clip(np.asarray(got["peak_day"]).astype(int), 0, T_DAYS - 1)
    got["peak_day"] = ref["daily_cases"][rows, day]
    ref["peak_day"] = ref["daily_cases"].max(1)
    return _gap(got, ref, floor)
