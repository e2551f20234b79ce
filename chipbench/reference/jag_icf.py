"""Plain reference of the jag_icf configuration.

Two parts, both written from the configuration's description and importing
nothing of the program:

- ``simulate``: the JAG-like implosion model (5 inputs in [0, 1] -> 11
  scalars, two series, a stack of view images, the inputs), one sample at
  a time in ``jax.numpy`` on the host CPU, with no bucketing, mesh or
  bundler.  Sample ``i`` draws its image noise from
  ``jax.random.PRNGKey(i)``.  The series length, the number of views and
  the image size are the output shapes in the configuration of the same
  name as this file (``chipbench/configs/<name>.json``).
- ``surrogate_apply``: the served deep ensemble, a plain forward pass of
  the stacked members (x @ w + b, tanh-form GELU between layers), mean and
  population standard deviation over the members.

``dtype`` is the precision the reference computes in: float32 (matrix
products at ``highest``) for the reference, bfloat16 for the control.
"""
from __future__ import annotations

import functools
import json
import os

import numpy as np

from chipbench.compare import gap, one_at_a_time  # noqa: F401

# compared numbers and their limits; PERF.md gives the readings each was
# set from
LIMITS = {"nan_mismatch": 0, "value_gap": 1e-2, "reply_gap": 2e-2}
BOUNDS = np.array([[0.85, 1.15], [-0.10, 0.10], [-0.08, 0.08],
                   [-0.08, 0.08], [0.00, 0.08]])
_CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs", os.path.basename(__file__)[:-3] + ".json")
with open(_CONFIG) as _f:
    _OUT = json.load(_f)["outputs"]
(N_T,), (N_VIEWS, IMG, _) = _OUT["burn_rate"], _OUT["images"]


def ambiguous(u: np.ndarray) -> np.ndarray:
    """Rows whose inputs sit within rounding of the failure region's edge
    (scale 1.13, thickness -0.085): there either answer is right."""
    x = BOUNDS[:, 0] + np.clip(u, 0, 1) * (BOUNDS[:, 1] - BOUNDS[:, 0])
    return (np.abs(x[:, 0] - 1.13) < 1e-5) | (np.abs(x[:, 1] + 0.085) < 1e-5)


@functools.lru_cache(maxsize=None)
def _sample_fn(dtype: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def one(u, sid):
        lo = jnp.asarray(BOUNDS[:, 0], dt)
        hi = jnp.asarray(BOUNDS[:, 1], dt)
        u = u.astype(dt)
        x = lo + jnp.clip(u, 0, 1) * (hi - lo)
        scale, thick, p2, p4, dop = x[0], x[1], x[2], x[3], x[4]
        vel = 340.0 * scale ** 0.6 / (1.0 + 2.0 * thick)
        adiabat = 1.8 * (1.0 + 0.5 * jnp.abs(thick))
        mix = 0.08 * dop / 0.08 + 3.0 * (p2 ** 2 + p4 ** 2)
        shape = jnp.exp(-60.0 * p2 ** 2 - 90.0 * p4 ** 2)
        tion = 4.2 * (vel / 340.0) ** 1.25 * (1.0 - 0.5 * mix)
        rhor = 0.9 * (1.0 + thick) * scale ** 0.3 * shape
        pressure = 280.0 * (vel / 340.0) ** 2.6 * shape
        yld = (5.0e15 * (vel / 340.0) ** 5.8 * shape ** 2
               * jnp.exp(-8.0 * mix) * (1.0 + thick) ** 1.5)
        bang = 8.2 * (1.0 + 1.5 * thick) / scale ** 0.45
        width = 0.16 * (1.0 + mix) / scale ** 0.2
        failed = (scale > 1.13) & (thick < -0.085)

        t = jnp.linspace(7.0, 10.0, N_T).astype(dt)
        burn = (yld / (width * jnp.sqrt(2 * jnp.pi).astype(dt))
                * jnp.exp(-0.5 * ((t - bang) / width) ** 2))
        tion_t = tion * jnp.exp(-0.5 * ((t - bang) / (2.5 * width)) ** 2)

        grid = jnp.linspace(-1, 1, IMG).astype(dt)
        yy, xx = jnp.meshgrid(grid, grid, indexing="ij")
        r = jnp.sqrt(xx ** 2 + yy ** 2) + 1e-6
        cos = yy / r
        images = []
        for v in range(N_VIEWS):
            # Python floats stay weakly typed: the arithmetic keeps ``dt``
            c2 = float(np.cos(2 * v * np.pi / N_VIEWS))
            c4 = float(np.cos(4 * v * np.pi / N_VIEWS))
            r0 = 0.45 * (1.0 + p2 * c2 * 0.5 * (3 * cos ** 2 - 1)
                         + p4 * c4 * 0.125
                         * (35 * cos ** 4 - 30 * cos ** 2 + 3))
            emiss = jnp.exp(-0.5 * ((r - r0) / (0.12 * (1 + mix))) ** 2)
            core = jnp.exp(-0.5 * (r / (0.3 * r0)) ** 2) * (tion / 4.2)
            images.append((emiss + core) * (yld / 5.0e15) ** 0.25)
        noise = jax.random.normal(jax.random.PRNGKey(sid),
                                  (N_VIEWS, IMG, IMG)).astype(dt)
        images = jnp.stack(images) + noise * 0.01
        nan = jnp.asarray(jnp.nan, dt)
        return {"yield": jnp.where(failed, nan, yld),
                "tion": jnp.where(failed, nan, tion),
                "velocity": vel, "rhor": rhor, "pressure": pressure,
                "adiabat": adiabat, "mix": mix, "bang_time": bang,
                "burn_width": width, "shape_deg": shape,
                "failed": failed.astype(dt), "burn_rate": burn,
                "tion_trace": tion_t, "images": images, "inputs": u}

    return jax.jit(one)


def simulate(ids: np.ndarray, u: np.ndarray, dtype: str = "float32") -> dict:
    """The model at sample ids ``ids`` with inputs ``u`` (rows of ``u``),
    one sample at a time on the host CPU."""
    return one_at_a_time(_sample_fn(dtype), ids, u)


# ---------------------------------------------------------------------------
# the served surrogate
# ---------------------------------------------------------------------------

def surrogate_apply(layers: list, X: np.ndarray, dtype: str = "float32"):
    """Mean and population std over members of the stacked MLP ensemble.
    ``layers``: [{"w": (M, din, dout), "b": (M, dout)}, ...] as numpy."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        h = jnp.broadcast_to(jnp.asarray(X, dt)[None],
                             (layers[0]["w"].shape[0],) + np.shape(X))
        for i, layer in enumerate(layers):
            w = jnp.asarray(layer["w"], dt)
            b = jnp.asarray(layer["b"], dt)
            h = jnp.einsum("mnd,mde->mne", h, w) + b[:, None, :]
            if i < len(layers) - 1:
                h = 0.5 * h * (1.0 + jnp.tanh(
                    float(np.sqrt(2.0 / np.pi)) * (h + 0.044715 * h ** 3)))
        pred = h[..., 0]
        mu = pred.mean(0)
        sd = jnp.sqrt(((pred - mu) ** 2).mean(0))
        return np.asarray(mu, np.float64), np.asarray(sd, np.float64)
