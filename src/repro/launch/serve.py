"""Walk-routed serving: requests pinned to graph nodes, routed by walker fleets.

Two layers (documented in docs/serving.md):

1. :class:`ServeEngine` — slot-based continuous batching over the model's
   cached decode path, hardened for sustained traffic: a bounded admission
   queue (backpressure — a full queue sheds loudly instead of growing
   without limit), per-request deadlines (an expired request is shed
   exactly once, never silently dropped), loud rejection of prompts that
   could never fit the KV-cache budget, cache *recycling* when the shared
   write position exhausts ``cache_len`` (in-flight requests are preempted
   back to the queue front and replayed — greedy decode is deterministic —
   instead of the engine simply stopping), and per-request latency
   bookkeeping in engine ticks (p50/p95/p99 via :func:`latency_percentiles`).

2. :class:`ServeSimulator` — the heavy-traffic scenario from the ROADMAP:
   each request arrives *at a node* of a ragged-layout graph (traffic skew
   set by a per-node load vector, degree-proportional by default, so
   hub-heavy Barabasi-Albert graphs concentrate demand exactly where the
   entrapment problem lives), and a :class:`~repro.walk_sgd.fleet.WalkFleet`
   of W walkers advances one batched
   :class:`~repro.core.engine.WalkEngine` transition per tick, picking up
   pending requests at the nodes it visits and feeding them to the serve
   engine.  The routing law is selected through the *trainer* METHODS seam
   (:func:`build_route_engine` — simple / uniform / importance / mhlj /
   heterogeneity / private, with the request load vector standing in for
   the per-node Lipschitz constants), so the convergence-vs-entrapment
   trade-off each chain law makes shows up directly as a
   requests-per-second / p99-latency / visit-Herfindahl trade-off.

CPU-scale:  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
              --nodes 2000 --walkers 32 --method mhlj --ticks 200 --drain 100
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHITECTURES, get_arch, reduced
from repro.core.entrapment import occupancy_concentration
from repro.core.faults import FaultModel
from repro.core.graphs import barabasi_albert
from repro.data.synthetic import RegressionData
from repro.models.factory import build_model
from repro.walk_sgd.fleet import WalkFleet

__all__ = [
    "Request",
    "ServeEngine",
    "ServeSimulator",
    "build_engine",
    "build_parser",
    "build_route_engine",
    "build_simulator",
    "latency_percentiles",
    "load_arrival_trace",
    "save_arrival_trace",
    "main",
]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (len,) int32
    max_new_tokens: int
    node: int = -1  # graph node the request is pinned to (-1 = direct submit)
    deadline: Optional[int] = None  # last tick at which admission is allowed
    submit_tick: Optional[int] = None
    admit_tick: Optional[int] = None
    done_tick: Optional[int] = None
    generated: Optional[List[int]] = None
    done: bool = False
    shed: bool = False
    shed_reason: Optional[str] = None


def latency_percentiles(requests) -> Dict[str, float]:
    """p50/p95/p99 of ``done_tick - submit_tick`` over finished requests.

    Latency is measured in *engine ticks* (the simulator clock), not wall
    seconds, so the numbers are machine-independent.  Zero completed
    requests — every request shed, or a fault scenario that killed the
    whole serving region — returns defined zeros rather than NaN or an
    exception, so a fully-degraded leg of a sweep still serializes;
    pair the percentiles with ``completed`` to tell "instant" from
    "nothing finished".
    """
    lats = [
        r.done_tick - r.submit_tick
        for r in requests
        if r.done_tick is not None and r.submit_tick is not None
    ]
    if not lats:
        return {"p50_ticks": 0.0, "p95_ticks": 0.0, "p99_ticks": 0.0}
    arr = np.asarray(lats, np.float64)
    return {f"p{p}_ticks": float(np.percentile(arr, p)) for p in (50, 95, 99)}


def save_arrival_trace(path: str, trace) -> str:
    """Write an arrival trace — ``(tick, node, prompt_len)`` int64 rows.

    The trace is the replayable workload of a :class:`ServeSimulator`
    run (``sim.arrival_log`` after ``run()``): feeding it back through
    ``arrival_trace=`` replays the *identical* offered load, which is
    what makes fault sweeps comparable — the rescue-on and rescue-off
    legs of ``benchmarks/fault_sweep.py`` see the same requests at the
    same nodes on the same ticks, so any difference is the policy's.
    """
    arr = np.asarray(trace, dtype=np.int64)
    if arr.size == 0:
        arr = arr.reshape(0, 3)
    if arr.ndim != 2 or arr.shape[1] != 3:
        raise ValueError(
            f"arrival trace must be (k, 3) rows of (tick, node, "
            f"prompt_len); got shape {arr.shape}"
        )
    np.savez(path, tick=arr[:, 0], node=arr[:, 1], prompt_len=arr[:, 2])
    return path


def load_arrival_trace(path: str) -> np.ndarray:
    """Load :func:`save_arrival_trace` → ``(k, 3)`` int64, tick-sorted."""
    with np.load(path, allow_pickle=False) as z:
        arr = np.stack([z["tick"], z["node"], z["prompt_len"]], axis=1)
    return arr[np.argsort(arr[:, 0], kind="stable")].astype(np.int64)


class ServeEngine:
    """Slot-based continuous batching over the model's cached decode step.

    Every slot advances one token per engine step; a slot is either
    prefilling (consuming its prompt) or generating (feeding back its own
    last output).  Finished slots are refilled from the admission queue in
    the same step.  The scheduling contract on top of that core:

    * **Backpressure** — ``max_queue`` bounds the admission queue; a
      ``submit`` against a full queue sheds the request (reason
      ``"queue_full"``), returns ``False`` and counts it.  ``None`` keeps
      the queue unbounded (the standalone-demo default).
    * **Deadlines** — ``Request.deadline`` is the last tick at which the
      request may be *admitted to a slot*; an expired queue head is shed
      (reason ``"deadline"``) when slots are filled.  :meth:`shed` enforces
      the shed-exactly-once contract: a second shed of the same request is
      a ``RuntimeError``, not a double-counted statistic.
    * **Cache budget** — a request whose ``prompt + max_new_tokens``
      exceeds ``cache_len - 1`` could never finish inside one cache epoch
      and is rejected loudly at ``submit`` (``ValueError``), never queued.
    * **Cache recycling** — the decode path uses one shared cache write
      position; when it reaches ``cache_len - 1`` the engine preempts all
      in-flight requests back to the *front* of the queue, re-initializes
      the cache and replays them (greedy decode is deterministic, so the
      replayed tokens are identical).  ``cache_recycles`` counts epochs;
      the preemption penalty is visible in the latency percentiles.
    """

    def __init__(
        self,
        cfg,
        batch_size: int,
        cache_len: int,
        dtype=jnp.float32,
        seed=0,
        max_queue: Optional[int] = None,
    ):
        self.cfg = cfg
        self.model = build_model(cfg, dtype=dtype)
        if self.model.init_cache is None:
            raise ValueError(f"{cfg.name} has no decode path")
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.batch_size = batch_size
        self.cache_len = cache_len
        self.max_queue = max_queue

        def step(params, cache, tokens, pos):
            logits, cache = self.model.decode_step(params, tokens, cache, pos)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(-1), cache

        self._step = jax.jit(step, donate_argnums=(1,))
        self.reset()

    def reset(self) -> "ServeEngine":
        """Fresh serving state on the same built model + jitted decode step
        (so a sweep over routing laws pays model build/compile once)."""
        self.cache = self.model.init_cache(self.batch_size, self.cache_len)
        self.slots: List[Optional[Request]] = [None] * self.batch_size
        self.slot_pos = np.zeros(self.batch_size, np.int64)  # tokens consumed
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self.shed_requests: List[Request] = []
        self.shed_counts: Dict[str, int] = {}
        self.engine_steps = 0
        self.busy_slot_steps = 0
        self.cache_pos = 0  # shared KV write index, reset at each recycle
        self.cache_recycles = 0
        self.queue_depth_sum = 0.0
        self.queue_depth_max = 0
        # node ids currently down (set by the fault-aware simulator each
        # tick); an expiry observed while the request's node is in this
        # set sheds with reason "node_down" instead of "deadline"
        self.down_nodes: set = set()
        return self

    # -- scheduling ---------------------------------------------------------
    def submit(self, req: Request, tick: int = 0) -> bool:
        """Admit ``req`` to the queue; ``False`` = shed on backpressure."""
        plen = len(req.prompt)
        need = plen + req.max_new_tokens
        if plen == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if need > self.cache_len - 1:
            raise ValueError(
                f"request {req.rid}: prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) = {need} exceeds the cache budget "
                f"(cache_len - 1 = {self.cache_len - 1}); it could never "
                "finish within one cache epoch — split the request or raise "
                "cache_len"
            )
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.shed(req, "queue_full")
            return False
        req.generated = []
        if req.submit_tick is None:
            req.submit_tick = tick
        self.queue.append(req)
        return True

    def shed(self, req: Request, reason: str) -> None:
        """Drop ``req`` loudly, exactly once (double shed = RuntimeError)."""
        if req.shed:
            raise RuntimeError(
                f"request {req.rid} shed twice: "
                f"{req.shed_reason!r} then {reason!r}"
            )
        req.shed = True
        req.shed_reason = reason
        self.shed_counts[reason] = self.shed_counts.get(reason, 0) + 1
        self.shed_requests.append(req)

    def _fill_slots(self, tick: int = 0) -> None:
        for i in range(self.batch_size):
            if self.slots[i] is not None:
                continue
            while self.queue:
                req = self.queue.pop(0)
                if req.deadline is not None and tick > req.deadline:
                    self.shed(
                        req,
                        "node_down" if req.node in self.down_nodes
                        else "deadline",
                    )
                    continue
                req.admit_tick = tick
                self.slots[i] = req
                self.slot_pos[i] = 0
                break

    def _recycle(self, tick: int) -> None:
        """Cache epoch rollover: preempt in-flight requests to the queue
        front (they replay deterministically), re-init the KV cache."""
        inflight = [r for r in self.slots if r is not None]
        for r in inflight:
            r.generated = []
        self.queue[:0] = inflight
        self.slots = [None] * self.batch_size
        self.slot_pos[:] = 0
        self.cache = self.model.init_cache(self.batch_size, self.cache_len)
        self.cache_pos = 0
        self.cache_recycles += 1

    def _gather_tokens(self) -> np.ndarray:
        toks = np.zeros((self.batch_size, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            p = self.slot_pos[i]
            if p < len(req.prompt):
                toks[i, 0] = req.prompt[p]
            elif req.generated:
                toks[i, 0] = req.generated[-1]
            else:
                toks[i, 0] = req.prompt[-1]
        return toks

    def step(self, tick: Optional[int] = None) -> None:
        """One engine step: every occupied slot consumes/produces one token.

        ``tick`` is the external clock (the simulator's); standalone use
        defaults it to ``engine_steps`` so latency is measured in decode
        steps either way.  An all-empty step is a no-op — it burns neither
        an engine step nor a cache row.
        """
        if tick is None:
            tick = self.engine_steps
        self._fill_slots(tick)
        if all(s is None for s in self.slots):
            return
        if self.cache_pos >= self.cache_len - 1:
            self._recycle(tick)
            self._fill_slots(tick)
        tokens = jnp.asarray(self._gather_tokens())
        # single shared cache write position; slots that joined mid-epoch
        # waste cache rows but stay correct because attention masks beyond
        # pos — cache exhaustion recycles the epoch (see _recycle) instead
        # of stopping the engine
        pos = jnp.asarray(self.cache_pos, jnp.int32)
        next_tok, self.cache = self._step(self.params, self.cache, tokens, pos)
        next_tok = np.asarray(next_tok)
        self.engine_steps += 1
        self.cache_pos += 1
        self.queue_depth_sum += len(self.queue)
        self.queue_depth_max = max(self.queue_depth_max, len(self.queue))
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.busy_slot_steps += 1
            self.slot_pos[i] += 1
            if self.slot_pos[i] >= len(req.prompt):
                req.generated.append(int(next_tok[i]))
                if len(req.generated) >= req.max_new_tokens:
                    req.done = True
                    req.done_tick = tick
                    self.completed.append(req)
                    self.slots[i] = None

    def stats(self) -> dict:
        toks = sum(len(r.generated) for r in self.completed)
        return {
            "completed": len(self.completed),
            "generated_tokens": toks,
            "engine_steps": self.engine_steps,
            "slot_utilization": self.busy_slot_steps
            / max(1, self.engine_steps * self.batch_size),
            "queued": len(self.queue),
            "shed_queue_full": self.shed_counts.get("queue_full", 0),
            "shed_deadline": self.shed_counts.get("deadline", 0),
            "shed_node_down": self.shed_counts.get("node_down", 0),
            "cache_recycles": self.cache_recycles,
            "mean_queue_depth": self.queue_depth_sum / max(1, self.engine_steps),
            "max_queue_depth": self.queue_depth_max,
            **latency_percentiles(self.completed),
        }

    def run(self, max_engine_steps: int = 10_000) -> dict:
        """Standalone drain: decode until queue + slots are empty."""
        t0 = time.time()
        while (self.queue or any(s is not None for s in self.slots)) and (
            self.engine_steps < max_engine_steps
        ):
            self.step()
        dt = time.time() - t0
        out = self.stats()
        out["tokens_per_sec"] = out["generated_tokens"] / max(dt, 1e-9)
        return out


def build_route_engine(
    graph,
    method: str,
    load: np.ndarray,
    *,
    mhlj_params=None,
    law_kwargs: Optional[dict] = None,
    engine_kwargs: Optional[dict] = None,
):
    """Routing :class:`~repro.core.engine.WalkEngine` via the trainer seam.

    Any name in ``repro.walk_sgd.trainer.METHODS`` works: the per-node
    request ``load`` stands in for the Lipschitz vector the training laws
    weight by (``RegressionData.lipschitz = load`` exactly, via features
    ``sqrt(load/2)``), so ``importance``/``mhlj`` target pi ∝ load — visit
    hot nodes more — while ``uniform`` ignores the skew and ``simple``
    follows degrees.  Returns ``(engine, p_j)`` with ``p_j`` the law's
    jump probability (0 for the non-jump laws).
    """
    from repro.walk_sgd import trainer as trainer_mod

    load = np.asarray(load, np.float64)
    if load.shape != (graph.n,) or (load <= 0).any():
        raise ValueError(f"load must be a positive ({graph.n},) vector")
    data = RegressionData(
        features=np.sqrt(load / 2.0)[:, None],
        targets=np.zeros(graph.n),
        x_star=np.zeros(1),
        lipschitz=load,
        high_variance_mask=np.zeros(graph.n, bool),
    )
    row_probs, _w, p_j_sched, p_d, r, _uw = trainer_mod._setup_method(
        method, graph, data, mhlj_params, None, 1, law_kwargs
    )
    engine = trainer_mod._build_engine(
        graph, p_d, r, row_probs, engine_kwargs, "auto"
    )
    return engine, float(p_j_sched[0])


def _faulted_advance(fleet, key, p_j, fmodel, fstate):
    """One fault-aware tick transition (jitted as a whole in the sim).

    The fault process advances *first* (same per-tick ordering as the
    training fleet scan), then the fleet takes one liveness-masked step;
    the returned state carries the engine's consecutive-blocked counters
    forward so patience accrues across ticks.
    """
    akey, fkey = jax.random.split(key)
    fstate = fmodel.advance(fkey, fstate)
    new_fleet, _hops, aux = fleet.advance(akey, p_j=p_j, faults=(fmodel, fstate))
    fstate = dataclasses.replace(fstate, blocked=aux["blocked_steps"])
    return new_fleet, fstate, fmodel.live_mask(fstate), aux


class ServeSimulator:
    """Requests as nodes on the graph, walkers as the routing fabric.

    Per tick: (1) Poisson arrivals land at nodes drawn ∝ ``load`` and join
    that node's pending deque; (2) the W-walker fleet takes one batched
    engine transition (one jitted call — the fleet/engine pytree crosses
    the jit boundary like everywhere else in the repo) and its visited
    nodes are logged for the entrapment telemetry; (3) each walker picks up
    to ``pickup`` pending requests at its node and submits them to the
    serve engine (queue-full → shed, deadline-expired → shed, both exactly
    once); (4) the serve engine takes one decode step.  ``metrics()``
    reports requests/s, queue depth, slot occupancy, p50/p95/p99 latency in
    ticks, aggregate walk-steps/s and the per-node visit Herfindahl/top-k
    share (``repro.core.entrapment.occupancy_concentration`` — the same
    telemetry ``benchmarks/law_sweep.py`` attaches to training walks).

    ``method="heterogeneity"`` defaults its target pi to the normalized
    load (routing interpretation: visit mass ∝ demand) so the O(n²)
    dissimilarity measurement is never run on a serving graph; pass
    ``law_kwargs={"pi": ...}`` to override.

    **Degraded operation** (docs/faults.md): with
    ``fault_model=FaultModel(...)`` the node fault process advances once
    per tick on its own key stream, the fleet transition is
    liveness-masked (blocked walkers accrue patience and take Lévy
    rescues onto the live set), walkers parked on dead nodes pick
    nothing up, and pending requests at a node that has been down for
    ``relocate_after`` consecutive ticks are re-queued at a live node
    (arrival order preserved, counted in ``relocated_requests``).  A
    deadline expiry observed while the request's node is down sheds with
    reason ``"node_down"`` instead of ``"deadline"`` — still exactly
    once.  ``fault_model=None`` is bitwise the pre-fault simulator.

    **Trace-driven load**: ``arrival_trace`` (``(k, 3)`` int64 rows of
    ``(tick, node, prompt_len)``, see :func:`save_arrival_trace`)
    replaces the Poisson generator so two legs of a sweep face the
    identical workload; every run also records its own arrivals in
    ``self.arrival_log`` for re-play.
    """

    def __init__(
        self,
        graph,
        serve_engine: ServeEngine,
        *,
        method: str = "mhlj",
        num_walkers: int = 64,
        load: Optional[np.ndarray] = None,
        rate: float = 1.0,
        pickup: int = 4,
        deadline_ticks: Optional[int] = None,
        prompt_len=(4, 16),
        max_new_tokens: int = 8,
        mhlj_params=None,
        law_kwargs: Optional[dict] = None,
        engine_kwargs: Optional[dict] = None,
        seed: int = 0,
        fault_model: Optional[FaultModel] = None,
        relocate_after: int = 3,
        arrival_trace: Optional[np.ndarray] = None,
    ):
        self.graph = graph
        self.n = int(graph.n)
        self.engine = serve_engine
        self.method = method
        if load is None:
            load = np.asarray(graph.degrees, np.float64)
        self.load = np.asarray(load, np.float64)
        if method == "heterogeneity" and not (law_kwargs and "pi" in law_kwargs):
            law_kwargs = {**(law_kwargs or {}), "pi": self.load / self.load.sum()}
        self._pop_cdf = np.cumsum(self.load / self.load.sum())
        self.route_engine, self._p_j = build_route_engine(
            graph, method, self.load,
            mhlj_params=mhlj_params, law_kwargs=law_kwargs,
            engine_kwargs=engine_kwargs,
        )
        self.num_walkers = num_walkers
        self.fleet = WalkFleet.create(self.route_engine, num_walkers, seed=seed)
        self._advance = jax.jit(
            lambda fleet, key, p_j: fleet.advance(key, p_j=p_j)
        )
        self._base_key = jax.random.PRNGKey(seed)
        self._rng = np.random.default_rng(seed + 1)
        # fault machinery — all of it dormant (and RNG-silent) when
        # fault_model is None, so the no-fault path stays bitwise
        self.fault_model = fault_model
        self.relocate_after = int(relocate_after)
        self._fault_state = (
            None if fault_model is None
            else fault_model.init_state(self.n, num_walkers)
        )
        self._advance_faulted = (
            None if fault_model is None else jax.jit(_faulted_advance)
        )
        self._relocate_rng = np.random.default_rng(seed + 2)
        self._down_now: set = set()
        self.down_since: Dict[int, int] = {}
        self.rescues = 0
        self.blocked_steps = 0
        self.down_node_ticks = 0
        self.relocated = 0
        # trace-driven load (replaces the Poisson generator when set)
        if arrival_trace is not None:
            arr = np.asarray(arrival_trace, dtype=np.int64)
            if arr.ndim != 2 or arr.shape[1] != 3:
                raise ValueError(
                    "arrival_trace must be (k, 3) rows of (tick, node, "
                    f"prompt_len); got shape {arr.shape}"
                )
            arrival_trace = arr[np.argsort(arr[:, 0], kind="stable")]
        self._trace = arrival_trace
        self._trace_pos = 0
        self._draining = False
        self.arrival_log: List[tuple] = []
        self.rate = rate
        self.pickup = pickup
        self.deadline_ticks = deadline_ticks
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.pending: Dict[int, deque] = {}
        self.pending_count = 0
        self.visits: List[np.ndarray] = []
        self.offered = 0
        self.picked_up = 0
        self.walk_steps = 0
        self.ticks = 0
        self._next_rid = 0
        self._wall = 0.0

    # -- workload -----------------------------------------------------------
    def offer(self, req: Request) -> None:
        """Pin ``req`` to its node's pending queue (arrival, not admission)."""
        if not (0 <= req.node < self.n):
            raise ValueError(
                f"request {req.rid}: node {req.node} outside [0, {self.n})"
            )
        need = len(req.prompt) + req.max_new_tokens
        if need > self.engine.cache_len - 1:
            # mirror the engine's loud cache-budget reject at the door, so
            # an impossible request never waits for a walker first
            raise ValueError(
                f"request {req.rid}: prompt+max_new ({need}) exceeds the "
                f"cache budget (cache_len - 1 = {self.engine.cache_len - 1})"
            )
        self.pending.setdefault(req.node, deque()).append(req)
        self.pending_count += 1
        self.offered += 1

    def _offer_generated(self, t: int, node: int, plen: int) -> None:
        """One synthetic arrival: prompt tokens from the workload RNG."""
        self.offer(
            Request(
                rid=self._next_rid,
                prompt=self._rng.integers(
                    0, self.engine.cfg.vocab_size, plen
                ).astype(np.int32),
                max_new_tokens=self.max_new_tokens,
                node=node,
                deadline=(
                    None
                    if self.deadline_ticks is None
                    else t + self.deadline_ticks
                ),
                submit_tick=t,
            )
        )
        self.arrival_log.append((t, node, plen))
        self._next_rid += 1

    def _arrivals(self, t: int) -> None:
        if self._trace is not None:
            if self._draining:
                return
            tr, i = self._trace, self._trace_pos
            while i < tr.shape[0] and tr[i, 0] <= t:
                if tr[i, 0] == t:
                    self._offer_generated(t, int(tr[i, 1]), int(tr[i, 2]))
                i += 1
            self._trace_pos = i
            return
        k = int(self._rng.poisson(self.rate))
        if k == 0:
            return
        nodes = np.searchsorted(self._pop_cdf, self._rng.random(k))
        lo, hi = self.prompt_len
        for v in nodes:
            plen = int(self._rng.integers(lo, hi + 1))
            self._offer_generated(t, int(v), plen)

    # -- fault handling -----------------------------------------------------
    def _advance_faults(self, t: int, key) -> None:
        """Advance the fault process + fleet one tick, then degrade:
        update the engine's ``down_nodes`` view, track per-node downtime,
        and relocate pending work off nodes down past the backoff."""
        self.fleet, self._fault_state, live, aux = self._advance_faulted(
            self.fleet, key, self._p_j, self.fault_model, self._fault_state
        )
        live_np = np.asarray(live)
        self.rescues += int(np.asarray(aux["rescued"]).sum())
        self.blocked_steps += int(np.asarray(aux["fault_blocked"]).sum())
        self.down_node_ticks += int((~live_np).sum())
        self._down_now = set(np.nonzero(~live_np)[0].tolist())
        self.engine.down_nodes = self._down_now
        for v in [u for u in self.down_since if u not in self._down_now]:
            del self.down_since[v]
        for v in self._down_now:
            self.down_since.setdefault(v, t)
        self._relocate_pending(t, live_np)

    def _relocate_pending(self, t: int, live_np: np.ndarray) -> None:
        """Re-queue pending requests off nodes down ≥ ``relocate_after``
        ticks onto a uniformly-drawn live node (arrival order kept)."""
        live_ids = np.nonzero(live_np)[0]
        if live_ids.size == 0:
            return  # total failure: nowhere to go, requests wait or expire
        stale = [
            v for v in list(self.pending)
            if v in self._down_now
            and t - self.down_since.get(v, t) >= self.relocate_after
        ]
        for v in stale:
            dq = self.pending.pop(v)
            tgt = int(live_ids[int(self._relocate_rng.integers(live_ids.size))])
            for req in dq:
                req.node = tgt
            self.relocated += len(dq)
            self.pending.setdefault(tgt, deque()).extend(dq)

    # -- the tick loop ------------------------------------------------------
    def tick(self) -> None:
        t = self.ticks
        self._arrivals(t)
        key = jax.random.fold_in(self._base_key, t)
        if self.fault_model is None:
            self.fleet, _hops = self._advance(self.fleet, key, self._p_j)
        else:
            self._advance_faults(t, key)
        where = np.asarray(self.fleet.nodes)
        self.visits.append(where.copy())
        self.walk_steps += self.num_walkers
        for v in where.tolist():
            if v in self._down_now:
                continue  # a walker parked on a dead node serves nothing
            dq = self.pending.get(v)
            if not dq:
                continue
            for _ in range(self.pickup):
                if not dq:
                    break
                req = dq.popleft()
                self.pending_count -= 1
                if req.deadline is not None and t > req.deadline:
                    self.engine.shed(req, "deadline")
                    continue
                if self.engine.submit(req, tick=t):
                    self.picked_up += 1
            if not dq:
                self.pending.pop(v, None)
        self.engine.step(tick=t)
        self.ticks += 1

    def _expire_pending(self) -> None:
        """Shed deadline-expired requests still waiting at their node;
        expiry observed at a currently-down node sheds as ``node_down``."""
        t = self.ticks
        for v in list(self.pending):
            keep: deque = deque()
            dq = self.pending.pop(v)
            while dq:
                req = dq.popleft()
                if req.deadline is not None and t > req.deadline:
                    self.engine.shed(
                        req,
                        "node_down" if req.node in self._down_now
                        else "deadline",
                    )
                    self.pending_count -= 1
                else:
                    keep.append(req)
            if keep:
                self.pending[v] = keep

    def run(self, num_ticks: int, drain_ticks: int = 0) -> dict:
        """``num_ticks`` with arrivals, then ``drain_ticks`` at rate 0."""
        t0 = time.time()
        for _ in range(num_ticks):
            self.tick()
        rate, self.rate = self.rate, 0.0
        self._draining = True
        try:
            for _ in range(drain_ticks):
                self.tick()
        finally:
            self.rate = rate
            self._draining = False
        self._expire_pending()
        self._wall += time.time() - t0
        return self.metrics()

    # -- telemetry ----------------------------------------------------------
    def metrics(self) -> dict:
        eng = self.engine.stats()
        if self.visits:
            traj = np.concatenate(self.visits)
            conc = occupancy_concentration(traj, self.n, topk=min(8, self.n))
        else:
            conc = {"herfindahl": 0.0, "topk_share": 0.0}
        wall = max(self._wall, 1e-9)
        return {
            "ticks": self.ticks,
            "offered": self.offered,
            "picked_up": self.picked_up,
            "pending_left": self.pending_count,
            "completed": eng["completed"],
            "generated_tokens": eng["generated_tokens"],
            "queued_left": eng["queued"],
            "shed_queue_full": eng["shed_queue_full"],
            "shed_deadline": eng["shed_deadline"],
            "shed_node_down": eng["shed_node_down"],
            "cache_recycles": eng["cache_recycles"],
            "slot_occupancy": eng["slot_utilization"],
            "mean_queue_depth": eng["mean_queue_depth"],
            "max_queue_depth": eng["max_queue_depth"],
            "requests_per_sec": eng["completed"] / wall,
            "tokens_per_sec": eng["generated_tokens"] / wall,
            "walk_steps_per_sec": self.walk_steps / wall,
            "p50_ticks": eng["p50_ticks"],
            "p95_ticks": eng["p95_ticks"],
            "p99_ticks": eng["p99_ticks"],
            "herfindahl": conc["herfindahl"],
            "topk_share": conc["topk_share"],
            # degradation telemetry — all zeros when fault_model is None,
            # so the metrics schema is stable across sweep legs
            "walker_rescues": self.rescues,
            "walker_blocked_steps": self.blocked_steps,
            "relocated_requests": self.relocated,
            "node_downtime_frac": (
                self.down_node_ticks / max(1, self.ticks * self.n)
            ),
        }


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's arguments (``main`` and ``chip_smoke.py`` share it)."""
    from repro.walk_sgd.trainer import METHODS

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mamba2-370m", choices=sorted(ARCHITECTURES))
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--nodes", type=int, default=2000,
                    help="graph size (ragged-layout Barabasi-Albert)")
    ap.add_argument("--ba-m", type=int, default=3,
                    help="Barabasi-Albert attachment parameter")
    ap.add_argument("--walkers", type=int, default=32,
                    help="routing fleet size W")
    ap.add_argument("--method", default="mhlj", choices=list(METHODS),
                    help="routing law (the trainer METHODS seam)")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="mean Poisson arrivals per tick")
    ap.add_argument("--ticks", type=int, default=200)
    ap.add_argument("--drain", type=int, default=100,
                    help="extra arrival-free ticks to drain the system")
    ap.add_argument("--batch", type=int, default=4, help="decode slots")
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pickup", type=int, default=4,
                    help="max requests a walker picks up per visit")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission-queue bound (backpressure)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request admission deadline in ticks")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--crash-rate", type=float, default=0.0,
                    help="per-tick node crash probability (0 = no faults)")
    ap.add_argument("--recovery-rate", type=float, default=0.0,
                    help="per-tick dead-node recovery probability")
    ap.add_argument("--patience", type=int, default=3,
                    help="consecutive blocked steps before a Lévy rescue")
    ap.add_argument("--no-rescue", action="store_true",
                    help="disable the Lévy-jump rescue (blocked walkers "
                    "just wait)")
    ap.add_argument("--relocate-after", type=int, default=3,
                    help="ticks a node stays down before its pending "
                    "requests are re-queued at a live node")
    ap.add_argument("--trace", default=None,
                    help="replay arrivals from a recorded trace file "
                    "instead of the Poisson generator")
    ap.add_argument("--record-trace", default=None,
                    help="write this run's arrival trace to a file "
                    "(replayable via --trace)")
    ap.add_argument("--standalone", action="store_true",
                    help="skip graph routing: direct-submit --requests "
                    "requests to the slot engine (the original demo)")
    ap.add_argument("--requests", type=int, default=8,
                    help="standalone mode: number of direct-submitted requests")
    return ap


def build_engine(args) -> ServeEngine:
    """The slot engine for parsed CLI ``args`` (``--scale full`` = the
    architecture at its published widths, ``smoke`` = ``reduced()``)."""
    cfg = reduced(get_arch(args.arch)) if args.scale == "smoke" else get_arch(args.arch)
    return ServeEngine(
        cfg, args.batch, args.cache_len, seed=args.seed, max_queue=args.max_queue
    )


def build_simulator(args, engine: ServeEngine, graph=None) -> ServeSimulator:
    """The walk-routed simulator for parsed CLI ``args``; ``graph``
    defaults to the ragged Barabasi-Albert graph the arguments describe."""
    if graph is None:
        graph = barabasi_albert(
            args.nodes, args.ba_m, seed=args.seed, layout="ragged"
        )
    fault_model = None
    if args.crash_rate > 0.0:
        fault_model = FaultModel(
            crash_rate=args.crash_rate,
            recovery_rate=args.recovery_rate,
            patience=args.patience,
            rescue=not args.no_rescue,
        )
    return ServeSimulator(
        graph,
        engine,
        method=args.method,
        num_walkers=args.walkers,
        rate=args.rate,
        pickup=args.pickup,
        deadline_ticks=args.deadline,
        max_new_tokens=args.max_new,
        seed=args.seed,
        fault_model=fault_model,
        relocate_after=args.relocate_after,
        arrival_trace=(
            load_arrival_trace(args.trace) if args.trace else None
        ),
    )


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)
    engine = build_engine(args)
    cfg = engine.cfg

    if args.standalone:
        rng = np.random.default_rng(args.seed)
        for rid in range(args.requests):
            plen = int(rng.integers(4, 24))
            engine.submit(
                Request(
                    rid=rid,
                    prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=args.max_new,
                )
            )
        stats = engine.run()
        for k, v in stats.items():
            print(f"{k}: {v:.4g}" if isinstance(v, float) else f"{k}: {v}")
        return 0 if stats["completed"] == args.requests else 1

    sim = build_simulator(args, engine)
    metrics = sim.run(args.ticks, drain_ticks=args.drain)
    if args.record_trace:
        save_arrival_trace(args.record_trace, sim.arrival_log)
    for k, v in metrics.items():
        print(f"{k}: {v:.4g}" if isinstance(v, float) else f"{k}: {v}")
    return 0 if metrics["completed"] > 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
