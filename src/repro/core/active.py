"""The iterative surrogate-optimization archetype (paper Sec. 3.2).

Loop per iteration, exactly the HYDRA capsule-robustness workflow:
  simulate batch -> post-process -> collect features -> train ML surrogate
  -> constrained acquisition (maximize expected objective under constraints,
  with robustness samples around candidates) -> choose next batch
  (1/3 around best observed, 1/3 at predicted optimum, 1/3 on the line
  between them — the paper's 128/128/128 split) -> re-enqueue via a worker
  call back into ``merlin run`` (dynamic workflow).

The surrogate is a small JAX MLP ensemble (deep ensembles for cheap
uncertainty); the simulator is any vmappable f(u, rng)->dict (JAG here).

Hot-path layout (the AI half of the AI–HPC coupling):

* ``train_surrogate`` is ONE jitted ``lax.scan`` over optimizer steps,
  ``vmap``-ed over ensemble members — a single compile and a single device
  loop instead of n_members × steps eager dispatches.  Training rows are
  padded to power-of-two buckets (core/ensemble.bucket_for) with a masked
  loss, so the growing per-iteration archive re-uses compiled programs
  instead of re-tracing at every new dataset size.
* ``Surrogate.predict`` is one jitted batched apply over the stacked member
  pytree (row-padded the same way), shared process-wide across instances.
* ``OptimizationLoop`` keeps one executor per iteration (all sharing the
  process-wide simulator compile cache) and one Bundler whose cached
  ``load_all`` re-reads only bundles that appeared since the last funnel.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bundler import Bundler
from repro.core.ensemble import EnsembleExecutor, bucket_for, pad_rows
from repro.core.runtime import MerlinRuntime
from repro.core.spec import Step, StudySpec


# ---------------------------------------------------------------------------
# MLP surrogate (deep ensemble)
# ---------------------------------------------------------------------------

def _mlp_init(rng, dims):
    params = []
    for i in range(len(dims) - 1):
        rng, k = jax.random.split(rng)
        w = jax.random.normal(k, (dims[i], dims[i + 1])) * (2.0 / dims[i]) ** 0.5
        params.append({"w": w, "b": jnp.zeros(dims[i + 1])})
    return params


def _mlp_apply(params, x):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.gelu(x)
    return x[..., 0]


@jax.jit
def _ensemble_apply(stacked, X):
    """Batched deep-ensemble forward: member axis leads the stacked pytree."""
    preds = jax.vmap(_mlp_apply, in_axes=(0, None))(stacked, X)
    return preds.mean(0), preds.std(0)


@dataclasses.dataclass
class Surrogate:
    params_list: List

    @property
    def stacked(self):
        """Members stacked on a leading axis (computed once, cached)."""
        s = getattr(self, "_stacked", None)
        if s is None:
            s = jax.tree.map(lambda *ls: jnp.stack(ls), *self.params_list)
            object.__setattr__(self, "_stacked", s)
        return s

    @classmethod
    def from_stacked(cls, stacked, n_members: int) -> "Surrogate":
        members = [jax.tree.map(lambda a: a[m], stacked)
                   for m in range(n_members)]
        sur = cls(members)
        object.__setattr__(sur, "_stacked", stacked)
        return sur

    def predict(self, X) -> Tuple[np.ndarray, np.ndarray]:
        """One jitted device launch; rows padded to a bucket so repeated
        calls at drifting batch sizes hit the compile cache."""
        X = np.asarray(X, np.float32)
        n = len(X)
        mu, sd = _ensemble_apply(self.stacked,
                                 jnp.asarray(pad_rows(X, bucket_for(n))))
        return np.asarray(mu[:n]), np.asarray(sd[:n])


@functools.partial(jax.jit, static_argnames=("steps", "lr"))
def _fit_members(params0, X, y, w, steps: int, lr: float):
    """Deep-ensemble Adam fit: ``lax.scan`` over steps, members vmapped.

    ``w`` masks padded rows out of the loss (sum(w·err²)/sum(w) equals the
    unpadded mean exactly); the update rule reproduces the seed's simple
    Adam (no bias correction) so results match the eager per-member loop.
    """
    def member_loss(p):
        err = _mlp_apply(p, X) - y
        return jnp.sum(w * err ** 2) / jnp.sum(w)

    zeros = jax.tree.map(jnp.zeros_like, params0)

    def body(carry, _):
        p, mom, vel = carry
        g = jax.vmap(jax.grad(member_loss))(p)
        mom = jax.tree.map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, mom, g)
        vel = jax.tree.map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ ** 2, vel, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * m_ / (jnp.sqrt(v_) + 1e-8),
            p, mom, vel)
        return (p, mom, vel), None

    (params, _, _), _ = jax.lax.scan(body, (params0, zeros, zeros), None,
                                     length=steps)
    return params


def train_surrogate(X: np.ndarray, y: np.ndarray, n_members: int = 3,
                    hidden: int = 64, steps: int = 300, lr: float = 3e-3,
                    seed: int = 0, pad: bool = True) -> Surrogate:
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    n = len(X)
    cap = bucket_for(n) if pad else n
    w = np.zeros(cap, np.float32)
    w[:n] = 1.0
    rngs = jnp.stack([jax.random.PRNGKey(seed * 131 + m)
                      for m in range(n_members)])
    dims = (X.shape[1], hidden, hidden, 1)
    params0 = jax.vmap(lambda r: _mlp_init(r, dims))(rngs)
    params = _fit_members(params0, jnp.asarray(pad_rows(X, cap)),
                          jnp.asarray(pad_rows(y, cap)), jnp.asarray(w),
                          steps, lr)
    return Surrogate.from_stacked(params, n_members)


# ---------------------------------------------------------------------------
# serving snapshot
# ---------------------------------------------------------------------------

class SurrogateSnapshot:
    """A resident, reloadable serving view of a study's surrogate ensemble.

    The gateway tier (``repro.serve.gateway``) answers predict/calibrate/
    what-if requests against this object: it holds the trained
    :class:`Surrogate` in memory (stacked member pytree, jitted batched
    apply) and tracks the study's bundle archive through
    ``Bundler.load_since`` deltas — ``refresh()`` reads only bundles that
    appeared since the last call, appends their rows, and retrains,
    bumping ``version``.  Serving and refreshing are concurrent-safe: the
    retrain happens under the snapshot lock and the new model swaps in
    with a single attribute assignment, so in-flight ``predict`` calls
    finish on the old ensemble and the next batch picks up the new one
    (no request ever observes a half-trained model).

    ``min_new_rows`` batches refresh work: deltas accumulate until at
    least that many new rows arrived, then one retrain covers them all
    (retrains are the expensive part; padded bucket sizes keep them on
    cached compiles).
    """

    def __init__(self, root: str, objective_key: str = "yield",
                 input_key: str = "inputs", n_members: int = 3,
                 hidden: int = 64, steps: int = 300, lr: float = 3e-3,
                 seed: int = 0, min_new_rows: int = 1):
        self.bundler = Bundler(root)
        self.objective_key = objective_key
        self.input_key = input_key
        self.n_members, self.hidden = int(n_members), int(hidden)
        self.steps, self.lr, self.seed = int(steps), float(lr), int(seed)
        self.min_new_rows = max(1, int(min_new_rows))
        self._lock = threading.Lock()
        self._cursor = None
        self._X: Optional[np.ndarray] = None
        self._y: Optional[np.ndarray] = None
        self._pending_rows = 0
        self._sur: Optional[Surrogate] = None
        self.version = 0
        self.refresh()
        if self._sur is None:
            raise ValueError(
                f"no training rows under {root!r}: bundles must carry "
                f"'{input_key}' and '{objective_key}' arrays")

    @property
    def rows(self) -> int:
        X = self._X
        return 0 if X is None else len(X)

    @property
    def dims(self) -> int:
        X = self._X
        return 0 if X is None else X.shape[1]

    def refresh(self) -> bool:
        """Pull new bundles since the last refresh and retrain if at least
        ``min_new_rows`` accumulated; returns True when the served model
        changed (``version`` bumped).  Rows whose objective is not finite
        or whose ``failed`` flag is set are not trained on."""
        with self._lock:
            data, self._cursor = self.bundler.load_since(self._cursor)
            X_new = data.get(self.input_key)
            y_new = data.get(self.objective_key)
            if X_new is not None and y_new is not None and len(X_new):
                X_new = np.asarray(X_new, np.float32)
                y_new = np.asarray(y_new, np.float32).reshape(len(X_new))
                if X_new.ndim == 1:
                    X_new = X_new[:, None]
                # failed shots carry a NaN objective (sim/jag.py); one NaN
                # row would turn every prediction NaN, so fit only the rows
                # regression_dataset keeps
                ok = np.isfinite(y_new)
                if "failed" in data:
                    ok &= np.asarray(data["failed"]).reshape(len(ok)) < 0.5
                X_new, y_new = X_new[ok], y_new[ok]
                if self._X is None:
                    self._X, self._y = X_new, y_new
                else:
                    self._X = np.concatenate([self._X, X_new])
                    self._y = np.concatenate([self._y, y_new])
                self._pending_rows += len(X_new)
            if self._X is None or not len(self._X):
                return False
            if self._sur is not None and self._pending_rows < self.min_new_rows:
                return False
            self._sur = train_surrogate(
                self._X, self._y, n_members=self.n_members,
                hidden=self.hidden, steps=self.steps, lr=self.lr,
                seed=self.seed)
            self._pending_rows = 0
            self.version += 1
            return True

    def predict(self, X) -> Tuple[np.ndarray, np.ndarray]:
        """(mu, sd) over rows — lock-free: the model reference is read
        once, so a concurrent refresh never tears a batch."""
        sur = self._sur
        if sur is None:
            raise RuntimeError("snapshot has no trained model yet")
        return sur.predict(X)


# ---------------------------------------------------------------------------
# acquisition
# ---------------------------------------------------------------------------

def robust_objective(sur: Surrogate, X: np.ndarray, n_perturb: int = 16,
                     radius: float = 0.02, seed: int = 0) -> np.ndarray:
    """Expected objective under manufacturing-tolerance perturbations
    (the paper's 'expected yield under random draws about a design')."""
    rng = np.random.default_rng(seed)
    Xp = X[:, None, :] + rng.normal(0, radius, (len(X), n_perturb, X.shape[1]))
    mu, _ = sur.predict(np.clip(Xp, 0, 1).reshape(-1, X.shape[1]))
    return mu.reshape(len(X), n_perturb).mean(1)


def propose_batch(sur_obj: Surrogate, sur_con: Optional[Surrogate],
                  X_seen: np.ndarray, y_seen: np.ndarray, n: int,
                  dims: int, con_max: float = np.inf, seed: int = 0
                  ) -> np.ndarray:
    """The paper's 3-way split: around best / at predicted opt / connecting."""
    rng = np.random.default_rng(seed)
    best = X_seen[int(np.argmax(y_seen))]
    # predicted constrained optimum via random search on the surrogate
    cand = rng.uniform(0, 1, (4096, dims)).astype(np.float32)
    obj = robust_objective(sur_obj, cand, seed=seed)
    if sur_con is not None:
        cmu, _ = sur_con.predict(cand)
        obj = np.where(cmu <= con_max, obj, -np.inf)
    pred_opt = cand[int(np.argmax(obj))]
    k = n // 3
    around_best = np.clip(best + rng.normal(0, 0.04, (k, dims)), 0, 1)
    around_opt = np.clip(pred_opt + rng.normal(0, 0.04, (k, dims)), 0, 1)
    t = rng.uniform(0, 1, (n - 2 * k, 1))
    line = np.clip(best * (1 - t) + pred_opt * t
                   + rng.normal(0, 0.02, (n - 2 * k, dims)), 0, 1)
    return np.concatenate([around_best, around_opt, line]).astype(np.float32)


# ---------------------------------------------------------------------------
# the full loop as a dynamic Merlin study
# ---------------------------------------------------------------------------

class OptimizationLoop:
    """Self-re-enqueueing optimization chain (Fig. 8)."""

    def __init__(self, runtime: MerlinRuntime, simulator: Callable,
                 objective_key: str = "yield", constraint_key: str = "velocity",
                 constraint_max: float = 360.0, dims: int = 5,
                 batch_per_iter: int = 48, max_iters: int = 3, seed: int = 0):
        self.rt = runtime
        self.dims = dims
        self.batch = batch_per_iter
        self.max_iters = max_iters
        self.obj_key = objective_key
        self.con_key = constraint_key
        self.con_max = constraint_max
        self.seed = seed
        self.history: List[Dict] = []
        self.simulator = simulator
        self.root = os.path.join(runtime.workspace, "opt_results")
        # all-iteration view (load_all/crawl walk recursively); its per-file
        # cache makes each funnel's load incremental over the archive
        self.bundler = Bundler(self.root)
        # per-iteration executors live for the whole loop: jit cache and
        # bundler handles are reused across every task of an iteration (and
        # the compiled simulator is shared process-wide across iterations)
        self._executors: Dict[int, EnsembleExecutor] = {}
        self._exec_lock = threading.Lock()
        runtime.register("opt_simulate", self._sim_step)
        runtime.register("opt_analyze", self._analyze_step)

    def _executor(self, iteration: int) -> EnsembleExecutor:
        with self._exec_lock:
            ex = self._executors.get(iteration)
            if ex is None:
                # one bundler sub-tree per iteration: sample ids restart at
                # 0 each iteration, so results must not collide across them
                b = Bundler(os.path.join(self.root, f"iter{iteration:03d}"))
                ex = EnsembleExecutor(self.simulator, b)
                self._executors[iteration] = ex
            return ex

    def _sim_step(self, ctx) -> None:
        it = int(ctx.variables["ITER"])
        self._executor(it).run_bundle(ctx.lo, ctx.hi, ctx.sample_block,
                                      sub_ranges=ctx.sub_ranges)

    def _spec(self, iteration: int) -> StudySpec:
        return StudySpec(
            name=f"opt-iter{iteration}",
            steps=[
                Step(name="simulate", fn="opt_simulate"),
                Step(name="analyze", fn="opt_analyze",
                     depends=("simulate_*",), over_samples=False),
            ],
            variables={"ITER": iteration})

    def start(self, rng: Optional[np.random.Generator] = None) -> str:
        rng = rng or np.random.default_rng(self.seed)
        X0 = rng.uniform(0, 1, (self.batch, self.dims)).astype(np.float32)
        return self.rt.run(self._spec(0), X0)

    def _analyze_step(self, ctx) -> None:
        """Funnel: train surrogates, log progress, launch the next iteration
        from inside a worker task (the dynamic re-enqueue of Sec. 3.2)."""
        it = int(ctx.variables["ITER"])
        data = self.bundler.load_all()
        ok = np.isfinite(data[self.obj_key])
        X = data["inputs"][ok]
        y = np.log10(np.maximum(data[self.obj_key][ok], 1e10))
        y = (y - y.min()) / max(y.max() - y.min(), 1e-9)
        c = data[self.con_key][ok]
        sur = train_surrogate(X, y, seed=self.seed + it)
        sur_c = train_surrogate(X, c / max(abs(c).max(), 1e-9),
                                seed=self.seed + 71 + it)
        self.history.append({
            "iter": it, "n": int(ok.sum()),
            "best": float(np.nanmax(data[self.obj_key]))})
        if it + 1 < self.max_iters:
            Xn = propose_batch(sur, sur_c, X, y, self.batch, self.dims,
                               con_max=self.con_max / max(abs(c).max(), 1e-9),
                               seed=self.seed + it)
            ctx.runtime.run(self._spec(it + 1), Xn)
