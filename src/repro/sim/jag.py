"""A JAG-like semi-analytic ICF implosion model, in JAX (paper Sec. 3.1).

The real JAG [Gaffney 2015] evolves an ICF capsule through stagnation from
2 scalar physics inputs + 3 3-D perturbations and emits scalars, time
series and hyperspectral images.  This stand-in keeps the same I/O
signature class (5-D input -> 20+ scalars, 2 time series, 4 view images)
with physically-flavored scalings (Betti-like yield ~ v^5.8 degradation
laws, Legendre-mode shape distortions), runs in microseconds under vmap,
and has a small "physics failure" region (returns failed=1, NaN yield) to
exercise the resubmission machinery exactly like JAG's 0.22% internal
failures.

Inputs (all in [0,1], rescaled internally):
  0 scale      laser drive scale            [0.85, 1.15]
  1 thickness  shell thickness perturbation [-0.10, 0.10]
  2 asym_p2    P2 drive asymmetry           [-0.08, 0.08]
  3 asym_p4    P4 drive asymmetry           [-0.08, 0.08]
  4 dopant     ablator dopant / mix seed    [0.00, 0.08]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# host constant: a device array here would start a JAX backend at import
JAG_BOUNDS = np.array([
    [0.85, 1.15],
    [-0.10, 0.10],
    [-0.08, 0.08],
    [-0.08, 0.08],
    [0.00, 0.08],
], np.float32)

N_T = 32          # time-series samples
IMG = 16          # image resolution
N_VIEWS = 4


def jag_sample_inputs(rng, n):
    """Uniform (blue-noise stand-in) sampling of the 5-D input space in [0,1]."""
    return jax.random.uniform(rng, (n, 5))


def _rescale(u):
    lo, hi = JAG_BOUNDS[:, 0], JAG_BOUNDS[:, 1]
    return lo + u * (hi - lo)


def jag_simulate(u, rng):
    """u: (5,) in [0,1]; rng: PRNGKey -> dict of scalars/series/images."""
    x = _rescale(jnp.clip(u, 0.0, 1.0))
    scale, thick, p2, p4, dop = x[0], x[1], x[2], x[3], x[4]

    # implosion dynamics (Betti-like scalings)
    vel = 340.0 * scale ** 0.6 / (1.0 + 2.0 * thick)          # km/s
    adiabat = 1.8 * (1.0 + 0.5 * jnp.abs(thick))
    mix = 0.08 * dop / 0.08 + 3.0 * (p2 ** 2 + p4 ** 2)
    shape_deg = jnp.exp(-60.0 * (p2 ** 2) - 90.0 * (p4 ** 2))
    tion = 4.2 * (vel / 340.0) ** 1.25 * (1.0 - 0.5 * mix)     # keV
    rhor = 0.9 * (1.0 + thick) * (scale ** 0.3) * shape_deg
    pressure = 280.0 * (vel / 340.0) ** 2.6 * shape_deg
    yield_ = 5.0e15 * (vel / 340.0) ** 5.8 * shape_deg ** 2 * \
        jnp.exp(-8.0 * mix) * (1.0 + thick) ** 1.5
    bang = 8.2 * (1.0 + 1.5 * thick) / (scale ** 0.45)         # ns
    burnwidth = 0.16 * (1.0 + mix) / (scale ** 0.2)

    # "physics failure" region: over-driven thin shells break the solver
    failed = jnp.logical_and(scale > 1.13, thick < -0.085)

    # time series: burn rate + ion temperature trace
    t = jnp.linspace(7.0, 10.0, N_T)
    burn = yield_ / (burnwidth * jnp.sqrt(2 * jnp.pi)) * \
        jnp.exp(-0.5 * ((t - bang) / burnwidth) ** 2)
    tion_t = tion * jnp.exp(-0.5 * ((t - bang) / (2.5 * burnwidth)) ** 2)

    # images: 4 views of the stagnated hotspot with P2/P4 shape distortion
    ang = jnp.linspace(0, jnp.pi, IMG)
    yy, xx = jnp.meshgrid(jnp.linspace(-1, 1, IMG), jnp.linspace(-1, 1, IMG),
                          indexing="ij")
    r = jnp.sqrt(xx ** 2 + yy ** 2) + 1e-6
    costh = yy / r
    # Legendre P2/P4 distorted radius, view-dependent projection factor
    views = jnp.arange(N_VIEWS) * (jnp.pi / N_VIEWS)

    def one_view(phi):
        proj2 = p2 * jnp.cos(2 * phi)
        proj4 = p4 * jnp.cos(4 * phi)
        r0 = 0.45 * (1.0 + proj2 * 0.5 * (3 * costh ** 2 - 1)
                     + proj4 * 0.125 * (35 * costh ** 4 - 30 * costh ** 2 + 3))
        emiss = jnp.exp(-0.5 * ((r - r0) / (0.12 * (1 + mix))) ** 2)
        core = jnp.exp(-0.5 * (r / (0.3 * r0)) ** 2) * (tion / 4.2)
        return (emiss + core) * (yield_ / 5.0e15) ** 0.25

    images = jax.vmap(one_view)(views)  # (4, IMG, IMG)
    noise = jax.random.normal(rng, images.shape) * 0.01
    images = images + noise

    nan = jnp.nan
    yield_out = jnp.where(failed, nan, yield_)
    return {
        "yield": yield_out,
        "tion": jnp.where(failed, nan, tion),
        "velocity": vel,
        "rhor": rhor,
        "pressure": pressure,
        "adiabat": adiabat,
        "mix": mix,
        "bang_time": bang,
        "burn_width": burnwidth,
        "shape_deg": shape_deg,
        "failed": failed.astype(jnp.float32),
        "burn_rate": burn.astype(jnp.float32),
        "tion_trace": tion_t.astype(jnp.float32),
        "images": images.astype(jnp.float32),
        "inputs": u.astype(jnp.float32),
    }
