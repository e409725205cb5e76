"""Full-JAX random-walk SGD trainer (paper Algorithm 1 + baselines).

Runs the *entire* T-iteration training as one ``lax.scan``: per iteration the
carried state is (model x, walk position v); the update applies the
importance-weighted stochastic gradient of the visited node's local loss
(Eq. 12), and the walk advances per the chosen method:

  method='uniform'       MH targeting uniform pi, plain gradient (w=1)
  method='importance'    MH-IS (Eq. 7), weighted gradient w(v)=L_bar/L_v
  method='mhlj'          Algorithm 1 (MH-IS + Levy jumps), weighted gradient
  method='simple'        simple random walk, plain gradient (degree-biased)
  method='heterogeneity' MH targeting the gradient-heterogeneity-optimized
                         pi of ``repro.core.heterogeneity`` (Dandi et al.,
                         arXiv:2204.06477), weighted gradient w ∝ 1/pi
  method='private'       private weighted walk on Gamma-noised weights
                         (Ayache & El Rouayheb, arXiv:2009.01790), weighted
                         gradient w ∝ 1/ŵ; ``law_kwargs={"gamma": ...}``
                         sets the privacy knob

The walk advances through :class:`repro.core.engine.WalkEngine` (the single
implementation of the MHLJ transition); non-jump methods are the engine at
p_J = 0.  The engine is built once per training run from the graph —
``Graph``, ``CSRGraph``, ``BucketedCSRGraph`` or ``RaggedCSRGraph`` — and
passed *into* the jitted scan as a pytree argument, so every layout rides
the identical training loop.

There is exactly ONE training scan: ``repro.walk_sgd.fleet.run_fleet``,
the W-walker fleet loop.  :func:`run_rw_sgd` is its W=1 case (bitwise
identical per key to the historical single-walk scan — the engine's
uniform block for one walk is the same whether the node is scalar or a
``(1,)`` batch) and :func:`run_rw_sgd_multi` is the fleet-construction
seam: it builds a :class:`repro.walk_sgd.fleet.WalkFleet` and, given a
``mesh``, shards the walker batch over the ``walker`` logical axis of
``repro.sharding.rules`` so W walks train across devices with the
periodic model average running as one collective.

This is the regression-scale trainer used for the paper's figures; the
pjit-sharded LLM engine is ``walk_sgd.llm_trainer`` (whose W-walker step
is the same fleet abstraction — ``repro.walk_sgd.fleet.make_fleet_step``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import heterogeneity as het_mod
from repro.core import transition as trans_mod
from repro.core.engine import WalkEngine
from repro.core.graphs import Graph
from repro.core.transition import MHLJParams
from repro.data.synthetic import RegressionData
from repro.models import regression as reg
from repro.walk_sgd.fleet import WalkFleet, run_fleet

__all__ = ["RWSGDResult", "MultiRWSGDResult", "run_rw_sgd", "run_rw_sgd_multi"]

METHODS = (
    "uniform", "importance", "mhlj", "simple", "heterogeneity", "private"
)


@dataclasses.dataclass
class RWSGDResult:
    mse: np.ndarray  # (T+1,) objective trace (paper Fig-3 metric)
    update_nodes: np.ndarray  # (T,)
    transitions: np.ndarray  # (T,) physical hops per update (Remark 1)
    x_final: np.ndarray
    method: str

    @property
    def transitions_per_update(self) -> float:
        return float(self.transitions.mean())


def _setup_method(
    method: str,
    graph: Graph,
    data: RegressionData,
    mhlj_params: Optional[MHLJParams],
    p_j_schedule: Optional[np.ndarray],
    num_steps: int,
    law_kwargs: Optional[dict] = None,
):
    """Shared method dispatch: padded P rows, weights, p_J schedule, (p_d, r).

    ``graph`` may be a dense :class:`~repro.core.graphs.Graph` (rows come
    from the dense transition builders, exactly as the paper's analysis
    stack computes them), a :class:`~repro.core.graphs.CSRGraph` (rows
    come from the O(E) local builders — same law, no N×N matrix), a
    :class:`~repro.core.graphs.BucketedCSRGraph` (per-degree-bucket rows,
    so hub-heavy 100k+-node topologies train without the O(n·max_deg)
    padded table) or a :class:`~repro.core.graphs.RaggedCSRGraph` (flat
    per-edge rows — the true-degree engine layout, exactly-O(E) row
    state, no padded tensor anywhere in the training loop).

    ``law_kwargs`` parameterizes the chain law itself:

    * ``method="heterogeneity"`` — ``pi`` (precomputed (n,) target; when
      absent it is measured+optimized from ``data`` via
      ``repro.core.heterogeneity.heterogeneity_pi``, whose ``floor`` /
      ``num_probes`` / ``probe_scale`` / ``seed`` / ``steps`` knobs pass
      through).
    * ``method="private"`` — ``gamma`` (privacy knob, default 0.1) and
      ``noise_seed`` (Gamma-noise draw seed, default 0).
    """
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}")
    if law_kwargs and method not in ("heterogeneity", "private"):
        raise ValueError(f"law_kwargs is not consumed by method={method!r}")
    lips = data.lipschitz
    dense = getattr(graph, "adj", None) is not None
    bucketed = hasattr(graph, "buckets")
    ragged = not (dense or bucketed) and not hasattr(graph, "neighbors")

    def pick(dense_p, padded_rows, bucket_rows, ragged_rows):
        if dense:
            return trans_mod.row_probs_padded(dense_p(), graph)
        if bucketed:
            return bucket_rows()
        return ragged_rows() if ragged else padded_rows()

    # the chain's target weight vector, for the laws whose gradient scaling
    # must undo the visit bias (w(v) = mean(target)/target(v), the Eq.-12
    # structure); None = the lipschitz default
    target_weight = None

    if method == "uniform":
        use_weights, use_jumps = False, False
        rows = pick(
            lambda: trans_mod.mh_uniform(graph),
            lambda: trans_mod.mh_uniform_rows(graph),
            lambda: trans_mod.mh_uniform_rows_bucketed(graph),
            lambda: trans_mod.mh_uniform_rows_ragged(graph),
        )
    elif method == "simple":
        use_weights, use_jumps = False, False
        rows = pick(
            lambda: trans_mod.simple_rw(graph),
            lambda: trans_mod.simple_rw_rows(graph),
            lambda: trans_mod.simple_rw_rows_bucketed(graph),
            lambda: trans_mod.simple_rw_rows_ragged(graph),
        )
    elif method == "heterogeneity":
        use_weights, use_jumps = True, False
        kw = dict(law_kwargs or {})
        pi = kw.pop("pi", None)
        if pi is None:
            pi = het_mod.heterogeneity_pi(data, **kw)
        elif kw:
            raise ValueError(
                f"unused heterogeneity law_kwargs besides pi: {sorted(kw)}"
            )
        pi = np.asarray(pi, dtype=np.float64)
        rows = pick(
            lambda: trans_mod.heterogeneity_mh(graph, pi),
            lambda: trans_mod.heterogeneity_rows(graph, pi),
            lambda: trans_mod.heterogeneity_rows_bucketed(graph, pi),
            lambda: trans_mod.heterogeneity_rows_ragged(graph, pi),
        )
        target_weight = pi
    elif method == "private":
        use_weights, use_jumps = True, False
        kw = dict(law_kwargs or {})
        priv_gamma = float(kw.pop("gamma", 0.1))
        noise_seed = int(kw.pop("noise_seed", 0))
        if kw:
            raise ValueError(f"unknown private-walk law_kwargs: {sorted(kw)}")
        rows = pick(
            lambda: trans_mod.private_weighted_mh(
                graph, lips, priv_gamma, seed=noise_seed
            ),
            lambda: trans_mod.private_weighted_rows(
                graph, lips, priv_gamma, seed=noise_seed
            ),
            lambda: trans_mod.private_weighted_rows_bucketed(
                graph, lips, priv_gamma, seed=noise_seed
            ),
            lambda: trans_mod.private_weighted_rows_ragged(
                graph, lips, priv_gamma, seed=noise_seed
            ),
        )
        # the update sees only the noised weights (they ARE the chain's
        # stationary target); true weights stay private to their nodes
        target_weight = trans_mod.private_weights(
            np.asarray(lips, dtype=np.float64), priv_gamma, seed=noise_seed
        )
    else:  # importance / mhlj share the P_IS rows; jumps sampled live
        use_weights = True
        use_jumps = method == "mhlj"
        if use_jumps:
            mhlj_params = mhlj_params or MHLJParams()
            mhlj_params.validate()
        rows = pick(
            lambda: trans_mod.mh_importance(graph, lips),
            lambda: trans_mod.mh_importance_rows(graph, lips),
            lambda: trans_mod.mh_importance_rows_bucketed(graph, lips),
            lambda: trans_mod.mh_importance_rows_ragged(graph, lips),
        )

    row_probs = rows if bucketed else jnp.asarray(rows)
    if target_weight is None:
        target_weight = np.asarray(lips, dtype=np.float64)
    weights = jnp.asarray(target_weight.mean() / target_weight, jnp.float32)

    if use_jumps:
        if p_j_schedule is not None:
            p_j_sched = jnp.asarray(p_j_schedule, jnp.float32)
            if p_j_sched.shape != (num_steps,):
                raise ValueError("p_j_schedule must have shape (num_steps,)")
        else:
            p_j_sched = jnp.full((num_steps,), mhlj_params.p_j, jnp.float32)
        p_d, r = mhlj_params.p_d, mhlj_params.r
    else:
        p_j_sched = jnp.zeros((num_steps,), jnp.float32)
        p_d, r = 0.5, 1  # engine never jumps at p_J = 0

    return row_probs, weights, p_j_sched, p_d, r, use_weights


def _build_engine(graph, p_d, r, row_probs, engine_kwargs, default_backend):
    """Engine for a training run; ``engine_kwargs`` may override backend."""
    kwargs = dict(engine_kwargs or {})
    backend = kwargs.pop("backend", default_backend)
    return WalkEngine.from_graph(
        graph, MHLJParams(p_j=0.0, p_d=p_d, r=r),
        row_probs=row_probs, backend=backend, **kwargs,
    )


def run_rw_sgd(
    method: str,
    graph: Graph,
    data: RegressionData,
    gamma: float,
    num_steps: int,
    *,
    mhlj_params: Optional[MHLJParams] = None,
    p_j_schedule: Optional[np.ndarray] = None,
    loss: str = "linear",
    x0: Optional[np.ndarray] = None,
    v0: int = 0,
    seed: int = 0,
    engine_kwargs: Optional[dict] = None,
    law_kwargs: Optional[dict] = None,
) -> RWSGDResult:
    """Run one RW-SGD training; returns the Fig-3 style MSE trace.

    The W=1 case of the fleet loop (``repro.walk_sgd.fleet.run_fleet``):
    a one-walker :class:`~repro.walk_sgd.fleet.WalkFleet` rides the same
    scan as :func:`run_rw_sgd_multi`, and the result is bitwise-identical
    per key to the historical dedicated single-walk scan
    (``tests/test_fleet.py`` pins this against a frozen oracle).

    ``graph`` may be a dense ``Graph``, an O(E) ``CSRGraph``, a
    degree-bucketed ``BucketedCSRGraph`` or a bare-core
    ``RaggedCSRGraph`` (the true-degree engine layout; its flat per-edge
    rows are built here and the engine turns them into the O(E) CDF
    buffer).  ``engine_kwargs`` forwards extra knobs to
    :meth:`WalkEngine.from_graph` (e.g. ``compact`` /
    ``capacity_factor`` for the bucketed layout's per-step walk
    compaction, or ``block_w``).
    """
    row_probs, weights, p_j_sched, p_d, r, use_weights = _setup_method(
        method, graph, data, mhlj_params, p_j_schedule, num_steps, law_kwargs
    )
    engine = _build_engine(graph, p_d, r, row_probs, engine_kwargs, "scan")
    fleet = WalkFleet.create(engine, 1, v0s=[v0])
    grad_fn = {"linear": reg.linear_grad, "logistic": reg.logistic_grad}[loss]
    x0 = jnp.zeros(data.dim, jnp.float32) if x0 is None else jnp.asarray(x0, jnp.float32)

    xs_fin, mses, _, nodes, hops, _final = run_fleet(
        jax.random.PRNGKey(seed),
        jnp.broadcast_to(x0[None], (1, data.dim)),
        jnp.asarray(data.features, jnp.float32),
        jnp.asarray(data.targets, jnp.float32),
        weights,
        fleet,
        num_steps,
        gamma,
        p_j_sched,
        use_weights,
        grad_fn,
    )
    return RWSGDResult(
        mse=np.asarray(mses[0]),
        update_nodes=np.asarray(nodes[0]),
        transitions=np.asarray(hops[0]),
        x_final=np.asarray(xs_fin[0]),
        method=method,
    )


# ---------------------------------------------------------------------------
# Batched multi-walk training (arXiv:2604.12260 regime, mesh-shardable)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MultiRWSGDResult:
    """W parallel walks trained in one scan off one batched engine step."""

    mse: np.ndarray  # (W, T+1) per-walk objective traces
    avg_mse: np.ndarray  # (T+1,) objective of the walk-averaged model
    update_nodes: np.ndarray  # (W, T) node holding each model at update t
    transitions: np.ndarray  # (W, T) physical hops (Remark 1)
    x_final: np.ndarray  # (W, dim) per-walk models
    method: str

    @property
    def x_avg(self) -> np.ndarray:
        return self.x_final.mean(axis=0)

    @property
    def transitions_per_update(self) -> float:
        return float(self.transitions.mean())


def run_rw_sgd_multi(
    method: str,
    graph: Graph,
    data: RegressionData,
    gamma: float,
    num_steps: int,
    num_walks: int,
    *,
    mhlj_params: Optional[MHLJParams] = None,
    p_j_schedule: Optional[np.ndarray] = None,
    loss: str = "linear",
    x0: Optional[np.ndarray] = None,
    v0s: Optional[Sequence[int]] = None,
    avg_every: int = 0,
    seed: int = 0,
    engine_kwargs: Optional[dict] = None,
    law_kwargs: Optional[dict] = None,
    mesh=None,
    engine: Optional[WalkEngine] = None,
) -> MultiRWSGDResult:
    """W parallel RW-SGD trainings sharing one batched engine transition.

    The fleet-construction seam: builds a
    :class:`~repro.walk_sgd.fleet.WalkFleet` (whose constructor owns the
    v0 seeding/validation shared with the LLM path) and runs it through
    the single fleet scan.  Each walk carries its own model;
    ``avg_every > 0`` averages the models across walks every that many
    updates (local-SGD style — the multi-walker regime of
    arXiv:2604.12260).  All W transitions per step are sampled by a
    single ``WalkEngine.step`` call — W-wide XLA gathers on TPU — instead
    of W independent scans.

    ``mesh`` (e.g. ``repro.launch.mesh.make_walker_mesh``) shards the
    walker batch over the ``walker`` logical axis of
    ``repro.sharding.rules``: per-walk model state and walk positions
    split across devices, graph/row state replicates, and the periodic
    average lowers to an all-reduce along the walker mesh axis.  On one
    device the sharded path is bitwise-identical to ``mesh=None``.

    ``engine_kwargs`` forwards extra knobs to
    :meth:`WalkEngine.from_graph` (bucketed compaction, ``block_w``, a
    ``backend`` override, …).

    ``engine`` injects a pre-built :class:`WalkEngine` instead of
    constructing one from ``graph`` — the dynamic-graph seam: a churned
    engine carried across graph versions by
    :meth:`WalkEngine.apply_churn` (see ``walk_sgd/graph_learning.py``)
    rides the same fleet scan without rebuilding its row state.  The
    caller owns consistency between the injected engine's rows and
    ``method`` (the method's row build is skipped); mutually exclusive
    with ``engine_kwargs``.
    """
    row_probs, weights, p_j_sched, p_d, r, use_weights = _setup_method(
        method, graph, data, mhlj_params, p_j_schedule, num_steps, law_kwargs
    )
    if engine is None:
        engine = _build_engine(graph, p_d, r, row_probs, engine_kwargs, "auto")
    elif engine_kwargs is not None:
        raise ValueError(
            "pass either a pre-built engine or engine_kwargs, not both"
        )
    fleet = WalkFleet.create(
        engine, num_walks, v0s=v0s, seed=seed, avg_every=avg_every
    )

    grad_fn = {"linear": reg.linear_grad, "logistic": reg.logistic_grad}[loss]
    x0 = jnp.zeros(data.dim, jnp.float32) if x0 is None else jnp.asarray(x0, jnp.float32)
    x0s = jnp.broadcast_to(x0[None], (num_walks, data.dim))

    xs_fin, mses, avg_mses, nodes, hops, _final = run_fleet(
        jax.random.PRNGKey(seed),
        x0s,
        jnp.asarray(data.features, jnp.float32),
        jnp.asarray(data.targets, jnp.float32),
        weights,
        fleet,
        num_steps,
        gamma,
        p_j_sched,
        use_weights,
        grad_fn,
        mesh=mesh,
    )
    return MultiRWSGDResult(
        mse=np.asarray(mses),
        avg_mse=np.asarray(avg_mses),
        update_nodes=np.asarray(nodes),
        transitions=np.asarray(hops),
        x_final=np.asarray(xs_fin),
        method=method,
    )
