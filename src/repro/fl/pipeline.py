"""Staged, compile-once, cohort-batched round pipeline (the Auxo hot path).

The seed engine executed cohorts one at a time — per leaf cohort one
`vmap(local_train)` dispatch, a host-side numpy aggregation, an eager
server-opt application, and a separate clustering round-trip — so round
wall-clock grew linearly with the cohort count, and every partition mutated
the padded batch shape (`quota`) and recompiled everything. This module
rearchitects that path into three explicit stages:

  ① MatchPlan        — vectorized matching: ε-greedy + sticky-reward +
                       negative-streak logic as numpy masks over dense
                       per-(client, cohort-slot) affinity tables, and ONE
                       `kops.cosine_similarity` call of the (N, d)
                       fingerprint matrix against the (C, d) leaf-identity
                       matrix (replacing N per-client tree descents).
  ② BatchedExecution — all leaf cohorts train in ONE jitted fused step of
                       fixed shape: participants of every cohort are packed
                       along a flat row axis of width B (the full round
                       budget), each row gathers its cohort's params from
                       the stacked CohortBank, local training runs as one
                       `vmap` over rows, aggregation is a masked
                       segment-sum over cohort slots, and the server
                       optimizer applies to all slots via `vmap`
                       (`algorithms.apply_stacked`). Shapes depend only on
                       the round budget and bank capacity — partitions
                       never recompile.
  ③ FeedbackBatch    — client fingerprint EMAs update vectorized, then
                       `CohortCoordinator.feedback_all` runs clustering +
                       instant rewards for ALL cohorts as one vmapped
                       dispatch over a stacked ClusterState; affinity
                       rewards, ExploreReward propagation, and partition
                       events apply as dense table updates.

The sequential per-cohort path survives as a REFERENCE ORACLE
(`mode="sequential"`): it consumes the same MatchPlan and applies the same
feedback, but executes one device dispatch per cohort exactly like the
seed engine — equivalence tests check both modes produce the same models,
and benchmarks/round_latency.py measures the speedup.

ROUND PIPELINING (ARCHITECTURE.md §⑤): with ``FLConfig.round_overlap = 1``
the three stages form a depth-2 software pipeline. The fused stage-② step
is dispatched NON-blocking (``ExecResult`` holds device arrays; stage ③
fetches lazily, donation on accelerators) and every round executes against
a plan computed BEFORE the previous round's feedback landed — one-round
staleness, paper-compatible: matching is ε-greedy over slowly-moving
affinity/EMA state. While the device executes round r, the host applies
round r-1's FeedbackBatch and plans + packs (and device-stages) round r+1;
stage-①/③ control math runs as numpy twins (``host_control``) because a
device dispatch there would queue behind the in-flight step and serialize
the pipeline. Partition events are the one place a stale plan is invalid;
they FLUSH the pipeline (drain the in-flight round synchronously, discard
the staged plan, refill against the reseeded tables). ``round_overlap = 0``
keeps the strict synchronous plan → execute → feedback order, bit-equal to
the pre-overlap engine.

PLACEMENT (ARCHITECTURE.md §④): with ``FLConfig.cohort_shards = S > 1`` the
CohortBank's slot axis shards over a ``cohort`` device mesh
(launch/mesh.make_cohort_mesh + launch/sharding.bank_shardings) and the
flat row axis becomes S blocks of ``shard_width`` rows, block j packed with
participants of the cohorts whose slots live on device j. The fused step
runs under ``shard_map`` with NO collectives: each device gathers, trains,
segment-sums, and server-opts only its own slots; only per-row sketches and
losses (d_sketch + 1 floats per participant) return to the host. Partitions
stay a device-side scatter (slot placement preserved), shapes stay fixed —
the compile-once and one-dispatch-per-round invariants survive sharding.
benchmarks/cohort_scaling.py sweeps C = 8..64 single-device vs sharded.

Semantic deltas vs the seed engine (documented, benign):
- client affinity lives in dense tables over *leaf slots*; stale non-leaf
  cohort ids no longer accumulate reward crumbs (the coordinator previously
  resolved such stale requests by tree descent — with synchronous table
  reseeding at partition time, stale requests cannot arise);
- host RNG draws are batched per round instead of per client/cohort, so
  trajectories differ from the seed engine draw-for-draw while remaining
  statistically identical.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.clustering import _cosine_np
from repro.core.cohort import distance_matrix
from repro.fl.algorithms import apply_stacked
from repro.fl.client import local_train
from repro.kernels import ops as kops
from repro.launch.mesh import cohort_size, make_cohort_mesh
from repro.launch.sharding import bank_shardings, row_sharding
from repro.scale.store import ChunkedAffinityTable


# positional arguments of the fused round step that are donated on
# accelerators: the bank's optimizer state only (see _make_exec_step)
EXEC_DONATE = (1,)


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1). Used to bucket data-dependent
    batch widths so jit caches stay small instead of recompiling per round."""
    return 1 << max(0, int(n) - 1).bit_length()


def bank_capacity(auxo) -> Tuple[int, int]:
    """(bank slot capacity, max leaf count) implied by the partition policy.

    Partitions stop once leaves >= max_cohorts, but the LAST partition can
    overshoot: leaves after p splits = 1 + (k-1)p, so the true ceiling is
    1 + (k-1)·ceil((max_cohorts-1)/(k-1)).
    """
    k = max(2, auxo.cluster_k)
    if not auxo.enabled:
        return 1, 1
    n_partitions = -(-(auxo.max_cohorts - 1) // (k - 1))  # ceil
    return 1 + k * n_partitions, 1 + (k - 1) * n_partitions


def table_capacity(fl, auxo) -> int:
    """Affinity-table column count: bank capacity AFTER shard padding
    (CohortBank pads so every mesh device owns an equal slot block)."""
    cap, _ = bank_capacity(auxo)
    s = max(1, int(getattr(fl, "cohort_shards", 0) or 1))
    return -(-cap // s) * s


# ---------------------------------------------------------------------------
# CohortBank: every cohort's params/opt-state stacked on a leading slot axis
# ---------------------------------------------------------------------------
class CohortBank:
    """Stacked pytree storage for all cohort models, fixed capacity.

    Leaf arrays have shape (capacity, ...); slot 0 is the root cohort "0".
    Partitions copy the parent slot into freshly allocated child slots
    (device-side scatter) — array shapes never change, so the fused round
    step compiles exactly once.

    PLACEMENT: with a ``cohort`` mesh the slot axis shards across devices
    (``launch/sharding.bank_shardings``): capacity is padded to a multiple
    of the shard count, device j owns the contiguous slot block
    [j*slots_per_shard, (j+1)*slots_per_shard), and each model leaf is
    replicated (``dp``) or tp-sharded within its slot. Slot ALLOCATION is
    round-robin across shards (allocation n -> slot
    (n % S)*slots_per_shard + n//S) so live leaf cohorts spread evenly over
    devices as the tree partitions. ``spawn_children`` stays a device-side
    scatter (jitted, donated, sharding-preserving): the parent slot crosses
    the mesh once per partition — the only time model bytes move between
    devices.
    """

    def __init__(self, params, opt_state, capacity: int, mesh=None, policy: str = "dp"):
        self.mesh = mesh
        self.n_shards = cohort_size(mesh) if mesh is not None else 1
        # pad capacity so every device owns an equal slot block
        self.capacity = -(-capacity // self.n_shards) * self.n_shards
        self.slots_per_shard = self.capacity // self.n_shards
        cap = self.capacity

        def stack(tree):
            shapes = jax.eval_shape(
                lambda t: jax.tree.map(
                    lambda a: jnp.zeros((cap,) + a.shape, a.dtype), t
                ),
                tree,
            )
            shardings = (
                bank_shardings(shapes, mesh, policy) if mesh is not None else None
            )

            def one(a, sh):
                f = jax.jit(
                    lambda x: jnp.zeros((cap,) + x.shape, x.dtype).at[0].set(x),
                    out_shardings=sh,
                )
                return f(a)

            if shardings is None:
                return jax.tree.map(lambda a: one(a, None), tree), None
            return jax.tree.map(one, tree, shardings), shardings

        self.params, self._params_sh = stack(params)
        self.opt_state, self._opt_sh = stack(opt_state)
        self.slot_of: Dict[str, int] = {"0": 0}
        self.id_of: Dict[int, str] = {0: "0"}
        self.clock = np.zeros(self.capacity, np.float64)
        self.rounds = np.zeros(self.capacity, np.int64)
        self._next = 1  # number of allocated slots (allocation counter)
        # device-side warm-start scatter. out_shardings PINS the bank's
        # placement: without it the scatter's output layout can drift from
        # the construction-time sharding, which would silently retrace the
        # fused round step after the first partition (breaking the
        # compile-once invariant). Donation would make it single-copy on
        # TPU, but CPU — the test substrate — warns on every donated call.
        def scatter_fn(t, ii, ps):
            return jax.tree.map(lambda a: a.at[ii].set(a[ps]), t)

        self._scatter_params = jax.jit(scatter_fn, out_shardings=self._params_sh)
        self._scatter_opt = jax.jit(scatter_fn, out_shardings=self._opt_sh)

    def shard_of(self, slot: int) -> int:
        """Mesh position (cohort-axis index) of the device owning `slot`."""
        return slot // self.slots_per_shard

    def _alloc_slot(self, n: int) -> int:
        """Slot id of the n-th allocation: round-robin across shard blocks
        so concurrently-live cohorts land on different devices."""
        if self.n_shards == 1:
            return n
        return (n % self.n_shards) * self.slots_per_shard + n // self.n_shards

    def params_of(self, cohort_id: str):
        i = self.slot_of[cohort_id]
        return jax.tree.map(lambda a: a[i], self.params)

    def opt_state_of(self, cohort_id: str):
        i = self.slot_of[cohort_id]
        return jax.tree.map(lambda a: a[i], self.opt_state)

    def spawn_children(self, parent: str, children: List[str]) -> List[int]:
        """Warm-start child slots from the parent slot (§4.2)."""
        ps = self.slot_of[parent]
        idx = []
        for ch in children:
            if self._next >= self.capacity:
                raise RuntimeError(
                    f"CohortBank capacity {self.capacity} exhausted at {ch}"
                )
            slot = self._alloc_slot(self._next)
            self.slot_of[ch] = slot
            self.id_of[slot] = ch
            idx.append(slot)
            self._next += 1
        ii = jnp.asarray(idx)
        psa = jnp.asarray(ps)
        self.params = self._scatter_params(self.params, ii, psa)
        self.opt_state = self._scatter_opt(self.opt_state, ii, psa)
        self.clock[idx] = self.clock[ps]
        self.rounds[idx] = self.rounds[ps]
        return idx


# ---------------------------------------------------------------------------
# Dense client-affinity tables (soft state, vectorized)
# ---------------------------------------------------------------------------
class AffinityTable:
    """Per-(client, cohort-slot) reward records as dense arrays.

    The seed engine held one python dict per client; matching then looped
    over N clients per round. Dense tables make the whole ①-matching stage
    a handful of numpy array ops.
    """

    def __init__(self, n_clients: int, capacity: int):
        self.reward = np.zeros((n_clients, capacity), np.float32)
        self.known = np.zeros((n_clients, capacity), bool)
        self.cluster_idx = np.full((n_clients, capacity), -1, np.int32)

    def wipe(self, cids: np.ndarray):
        """§5.2 unstable clients: lost soft state restarts exploration."""
        self.reward[cids] = 0.0
        self.known[cids] = False
        self.cluster_idx[cids] = -1

    def feedback(self, cids: np.ndarray, slot: int, delta: np.ndarray, gamma: float):
        """EMA reward-record update: R <- γ·ΔR + (1−γ)·R."""
        self.reward[cids, slot] = (
            gamma * delta + (1.0 - gamma) * self.reward[cids, slot]
        )
        self.known[cids, slot] = True

    def set_cluster(self, cids: np.ndarray, slot: int, assign: np.ndarray):
        has = assign >= 0  # -1 = clustering not yet started
        self.cluster_idx[cids[has], slot] = assign[has]

    def propagate(self, cids: np.ndarray, delta: np.ndarray, slot_dist: Dict[int, int]):
        """ExploreReward (§4.3): push ΔR/(d+1) to the other leaves.

        One fancy-indexed block update over (clients x other-leaves) — the
        per-slot loop this replaces made stage ③ O(L²) per round.
        """
        if not slot_dist or cids.size == 0:
            return
        slots = np.fromiter(slot_dist.keys(), np.int64, len(slot_dist))
        dists = np.fromiter(slot_dist.values(), np.float64, len(slot_dist))
        self.reward[np.ix_(cids, slots)] += delta[:, None] / (dists[None, :] + 1)
        self.known[np.ix_(cids, slots)] = True

    def seed_children(self, parent_slot: int, child_slots: List[int]):
        """Algorithm 1 line 22: child rewards R + 0.1·1(L == k)."""
        has = self.known[:, parent_slot]
        base = self.reward[has, parent_slot]
        L = self.cluster_idx[has, parent_slot]
        for k, cs in enumerate(child_slots):
            self.reward[has, cs] = base + np.where(L == k, 0.1, 0.0)
            self.known[has, cs] = True
            self.cluster_idx[has, cs] = 0

    def preferred_slot(self, c: int, slots: np.ndarray) -> Optional[int]:
        known = self.known[c, slots]
        if not known.any():
            return None
        masked = np.where(known, self.reward[c, slots], -np.inf)
        return int(slots[int(np.argmax(masked))])

    # store-compatible access API (ARCHITECTURE.md §⑥): the pipeline talks
    # to the table ONLY through these + the ops above, so the chunked
    # PopulationStore view (repro.scale.ChunkedAffinityTable) is a drop-in
    def gather_rows(self, cids) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Full-width (len(cids), capacity) row copies of the three tables."""
        return self.reward[cids], self.known[cids], self.cluster_idx[cids]

    def scatter_rows(self, cids, reward, known, cluster_idx):
        self.reward[cids] = reward
        self.known[cids] = known
        self.cluster_idx[cids] = cluster_idx

    def match_view(self, cids, slots) -> Tuple[np.ndarray, np.ndarray]:
        """(reward, known) blocks over (cids × slots) — read-only copies."""
        return self.reward[cids][:, slots], self.known[cids][:, slots]

    def known_at(self, cids, slot) -> np.ndarray:
        return self.known[cids, slot]

    def cluster_at(self, c, slot) -> int:
        return int(self.cluster_idx[c, slot])


def check_cross_cohort_unique(client_rows: np.ndarray, kept: np.ndarray):
    """Assert no client id occupies two kept rows in one round.

    The vectorized matcher assigns every client exactly one leaf, so this
    cannot fire today — it guards future matching policies (e.g. multi-
    cohort membership experiments) against silently double-counting a
    client's update. Opt out explicitly with
    ``FLConfig.allow_cross_cohort_duplicates = True``.
    """
    ids = client_rows[kept]
    uniq, counts = np.unique(ids, return_counts=True)
    dup = uniq[counts > 1]
    if dup.size:
        raise ValueError(
            f"client id(s) {dup[:8].tolist()} hold kept rows in more than one "
            "cohort this round; set FLConfig.allow_cross_cohort_duplicates=True "
            "to permit multi-cohort membership explicitly"
        )


# ---------------------------------------------------------------------------
# Stage outputs
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MatchPlan:
    """Stage-① output: the round's flat, fixed-width execution layout.

    B = n_shards * shard_width; under sharding, rows [j*W, (j+1)*W) are
    block j and hold only participants of cohorts placed on device j (plus
    padding), so the execution stage needs no cross-device gathers. `order`
    records the layout-independent canonical fill order (leaf by leaf, in
    tree order): host-side data sampling and per-row PRNG keys follow it,
    which keeps sharded and single-device runs drawing identical streams.
    """

    round_idx: int
    leaves: List[str]  # all leaf cohorts, tree order
    active: List[str]  # leaves that train this round (≥ 2 candidates)
    slot_rows: np.ndarray  # (B,) int32 bank slot per flat row
    client_rows: np.ndarray  # (B,) int32 client id per row
    real: np.ndarray  # (B,) bool — row is a real participant (not padding)
    kept: np.ndarray  # (B,) bool — survived the over-commitment straggler drop
    claimed: np.ndarray  # (B,) bool — client requested this cohort as best-fit
    sizes: np.ndarray  # (B,) float32 client dataset sizes
    update_slots: np.ndarray  # (capacity,) bool — slots that train this round
    durations: Dict[str, float]
    key_seed: int
    order: np.ndarray  # (B,) int32 — canonical row order; first n_real real
    n_real: int  # real participant rows this round
    dropped: int  # participants dropped to a full shard row block (§④)


class ExecResult:
    """Stage-② output: per-row training artifacts, fetched lazily.

    The batched path stores DEVICE arrays: converting them to numpy blocks
    until the fused step finishes, so the conversion happens on first
    attribute access (stage ③) rather than at dispatch time — the dispatch
    itself returns immediately and the host can retire the previous round
    and plan/pack the next one while the device trains this one (§⑤).
    """

    def __init__(self, sketches, losses):
        self._sketches = sketches  # (B, d_sketch) device or host
        self._losses = losses  # (B,)

    @property
    def sketches(self) -> np.ndarray:
        if not isinstance(self._sketches, np.ndarray):
            self._sketches = np.asarray(self._sketches)
        return self._sketches

    @property
    def losses(self) -> np.ndarray:
        if not isinstance(self._losses, np.ndarray):
            self._losses = np.asarray(self._losses)
        return self._losses


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------
class RoundPipeline:
    """Drives one global round as MatchPlan → BatchedExecution → FeedbackBatch.

    mode="batched"   — one fused jitted dispatch for the execution stage and
                       one vmapped dispatch for the feedback clustering,
                       independent of the leaf-cohort count.
    mode="sequential" — reference oracle: same plan, same feedback
                       application, but per-cohort device dispatches like
                       the seed engine (used by equivalence tests and the
                       round-latency benchmark baseline).

    With ``FLConfig.cohort_shards = S > 1`` (batched mode only) the bank and
    the flat row axis shard over an S-device ``cohort`` mesh and the fused
    step runs under shard_map with no collectives — see the module
    docstring and ARCHITECTURE.md §④.
    """

    def __init__(self, engine, mode: str = "batched"):
        assert mode in ("batched", "sequential"), mode
        self.eng = engine
        self.mode = mode
        fl, auxo = engine.fl, engine.auxo
        capacity, self.max_leaves = bank_capacity(auxo)
        self.n_shards = max(1, int(fl.cohort_shards or 1))
        if self.n_shards > 1:
            assert mode == "batched", "cohort sharding requires the batched pipeline"
            self.mesh = make_cohort_mesh(self.n_shards)
        else:
            self.mesh = None
        self.bank = CohortBank(
            engine._init_params,
            engine.server_opt.init(engine._init_params),
            capacity,
            mesh=self.mesh,
        )
        # §⑥ population plane: with FLConfig.population_store the table is
        # a view over the engine's chunked PopulationStore — same method
        # API, same bit-level math, O(touched clients) memory
        store = getattr(engine, "store", None)
        if store is not None:
            self.table = ChunkedAffinityTable(store)
            assert self.table.capacity == self.bank.capacity, (
                self.table.capacity, self.bank.capacity
            )
        else:
            self.table = AffinityTable(engine.data.n_clients, self.bank.capacity)
        # full-population id vector for use_availability=False rounds,
        # computed ONCE (was a per-round O(N) allocation) and LAZILY — an
        # availability-sampled million-client run never materializes it
        self._all_ids_cache: Optional[np.ndarray] = None
        # flat execution width: the full round budget, fixed for the run.
        # L·quota(L) ≤ max(int(P·oc), 2·L) for every leaf count L, so this
        # width fits every partition state without a reshape.
        self.width = max(
            2, int(fl.participants_per_round * fl.overcommit), 2 * self.max_leaves
        )
        # per-device row block (§④): each shard owns `shard_width` rows for
        # the cohorts placed on it. The default (2·width/S, i.e. 2x the
        # balanced share) absorbs leaf-placement skew; a cohort whose block
        # fills trains with fewer participants that round (counted in
        # MatchPlan.dropped) — the per-device participant *capacity*
        # semantic. rows_per_shard=width restores strict single-device
        # semantics at the cost of S·width padded rows.
        if self.n_shards == 1:
            self.shard_width = self.width
        else:
            auto = min(self.width, max(2, -(-2 * self.width // self.n_shards)))
            self.shard_width = int(fl.rows_per_shard or auto)
        self.exec_width = self.shard_width * self.n_shards
        self.exec_dispatches = 0  # device dispatches issued by stage ② so far
        self.dropped_rows = 0  # participants dropped to full shard blocks
        # §⑤ round pipelining: 0 = synchronous, 1 = depth-2 overlap
        self.overlap = int(getattr(fl, "round_overlap", 0) or 0)
        if self.overlap:
            assert self.overlap == 1, "only depth-2 overlap (round_overlap=1)"
            assert mode == "batched", "round overlap requires the batched pipeline"
        # host control plane (§⑤): with the overlap on, stage-①/③ control
        # math (matching cosine, clustering feedback, rewards) runs as
        # numpy twins — any device dispatch there queues behind the
        # in-flight fused step and its fetch serializes the pipeline.
        # Overridable for the staleness-oracle tests.
        self.host_control = bool(self.overlap)
        self._inflight = None  # (plan, res) dispatched but not yet retired
        self._staged: Optional[Tuple[int, Any, Any]] = None  # (round, plan, packed)
        # §⑨ elasticity: host copies (xs, ys, inv) of the most recent staged
        # round's pack buffers. The device-staged tuple in _staged is
        # layout-bound (shard-local slot ids, device placement) and cannot
        # be serialized portably; checkpoint.run_state saves these host
        # buffers instead and re-stages them through _stage_buffers on load.
        self._staged_host: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        self.flushes = 0  # partition-triggered pipeline flushes
        # §⑧ serving snapshot: the newest bank state CONSISTENT with the
        # host tables (round boundary). With the overlap on, the live
        # bank.params are round-r futures while the tables still hold
        # round r-1 — the serving plane must never pair them. run_round
        # republishes this after every feedback application; a partition
        # flush refreshes it from the drained bank (a pre-partition
        # snapshot would expose child slots that were not spawned yet).
        self.serve_params = self.bank.params
        # cumulative host wall-time per stage (benchmarks/round_overlap.py)
        self.stage_seconds = {
            "plan": 0.0, "pack": 0.0, "dispatch": 0.0, "feedback": 0.0
        }
        self._exec_step = self._make_exec_step()

    @property
    def _all_ids(self) -> np.ndarray:
        if self._all_ids_cache is None:
            self._all_ids_cache = np.arange(
                self.eng.data.n_clients, dtype=np.int64
            )
        return self._all_ids_cache

    def _timed(self, key: str, fn, *args):
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            self.stage_seconds[key] += time.perf_counter() - t0

    # ------------------------------------------------------------ stage ①
    def plan_round(self, r: int) -> Optional[MatchPlan]:
        eng, fl, auxo = self.eng, self.eng.fl, self.eng.auxo
        if fl.use_availability:
            if getattr(eng.trace, "mode", "compat") == "chunked":
                # §⑥ streaming availability: per-chunk Poisson counts +
                # in-chunk id sampling, capped at a candidate pool around
                # the round budget — O(budget + N/chunk), the full active
                # set is never materialized
                pool = max(4 * self.exec_width, 2 * int(fl.participants_per_round))
                avail, _n_avail = eng.trace.sample(r, pool, eng.rng)
            else:
                avail = np.asarray(eng.trace.available(r, eng.rng))
        else:
            avail = self._all_ids  # materialized lazily, once
        store = getattr(eng, "store", None)
        if store is not None and store.n_departed:
            avail = avail[store.alive(avail)]  # churned-out clients skip rounds
        bl = eng.coordinator.blacklist
        if bl:
            avail = avail[~np.isin(avail, np.fromiter(bl, int, len(bl)))]
        if avail.size == 0:
            return None

        leaves = eng.coordinator.tree.leaves()
        slots = np.array([self.bank.slot_of[l] for l in leaves])
        nA = avail.size

        if auxo.enabled and len(leaves) > 1:
            want, claimed = self._match_vectorized(r, avail, leaves, slots)
        else:
            want = np.zeros(nA, np.int64)
            # single-leaf rounds: a client "claims" the (only) cohort iff it
            # is its preferred one, i.e. it holds any reward record there —
            # keeps the §5.2 fake-affinity detection live pre-partition
            claimed = self.table.known_at(avail, int(slots[0]))

        # per-cohort resource budget: equal split of the round budget (§4.4)
        quota = max(
            2, int(fl.participants_per_round * fl.overcommit / len(leaves))
        )
        B = self.exec_width
        W = self.shard_width
        slot_rows = np.zeros(B, np.int32)
        client_rows = np.zeros(B, np.int32)
        real = np.zeros(B, bool)
        kept = np.zeros(B, bool)
        claim_rows = np.zeros(B, bool)
        update_slots = np.zeros(self.bank.capacity, bool)
        durations: Dict[str, float] = {}
        active: List[str] = []
        cursors = np.zeros(self.n_shards, np.int64)  # fill level per block
        order_list: List[int] = []  # canonical (layout-independent) order
        dropped = 0
        for li, leaf in enumerate(leaves):
            cand = avail[want == li]
            if cand.size < 2:
                continue
            ccl = claimed[want == li]
            take = min(quota, cand.size)
            # §④ per-device participant capacity: a cohort trains with at
            # most the free rows of its slot's shard block
            shard = self.bank.shard_of(int(slots[li]))
            space = int(W - cursors[shard])
            if take > space:
                dropped += take - space
                take = space
            if take < 2:
                dropped += take
                continue
            sel = eng.rng.choice(cand.size, size=take, replace=False)
            part = cand[sel]
            # over-commitment straggler drop: latency is a pure function of
            # device speeds, so the kept set is known before execution
            kept_ids, duration = eng.speeds.round_duration(
                part,
                fl.local_steps * fl.batch_size,
                overcommit=fl.overcommit,
            )
            base = shard * W + int(cursors[shard])
            rows = slice(base, base + take)
            slot_rows[rows] = slots[li]
            client_rows[rows] = part
            real[rows] = True
            kept[rows] = np.isin(part, kept_ids)
            claim_rows[rows] = ccl[sel]
            update_slots[slots[li]] = True
            durations[leaf] = duration
            active.append(leaf)
            cursors[shard] += take
            order_list.extend(range(rows.start, rows.stop))
        n_real = len(order_list)
        if n_real == 0:
            return None
        # padding rows replicate their block's first row (weight 0, never
        # kept); an EMPTY block pads with its shard's first local slot so
        # the per-row param gather still never crosses the mesh
        first_real = order_list[0]
        for j in range(self.n_shards):
            lo, hi = j * W + int(cursors[j]), (j + 1) * W
            if lo == hi:
                continue
            src = j * W if cursors[j] > 0 else first_real
            slot_rows[lo:hi] = (
                slot_rows[src] if cursors[j] > 0 else j * self.bank.slots_per_shard
            )
            client_rows[lo:hi] = client_rows[src]
        order = np.concatenate(
            [np.asarray(order_list, np.int64), np.flatnonzero(~real)]
        ).astype(np.int32)
        if not fl.allow_cross_cohort_duplicates:
            check_cross_cohort_unique(client_rows, kept)
        self.dropped_rows += dropped
        # §⑦: sizes come through the plane's paged cache (the overlap path
        # hits this every round, one round ahead; churn invalidates)
        sizes = eng.data.client_sizes(client_rows).astype(np.float32)
        return MatchPlan(
            round_idx=r,
            leaves=leaves,
            active=active,
            slot_rows=slot_rows,
            client_rows=client_rows,
            real=real,
            kept=kept,
            claimed=claim_rows,
            sizes=sizes,
            update_slots=update_slots,
            durations=durations,
            key_seed=int(eng.rng.integers(2**31)),
            order=order,
            n_real=n_real,
            dropped=dropped,
        )

    def _match_vectorized(self, r, avail, leaves, slots):
        """①-matching without a per-client loop.

        Returns (want — index into `leaves` per available client, claimed —
        whether the choice equals the client's preferred cohort).
        """
        eng, auxo = self.eng, self.eng.auxo
        nA = avail.size
        eps = eng.selector.epsilon(r)
        u = eng.rng.random(nA)
        rand_pick = eng.rng.integers(len(leaves), size=nA)

        rew_blk, known = self.table.match_view(avail, slots)  # (nA, L) each
        rew = np.where(known, rew_blk, -np.inf)
        known_any = known.any(1)
        rand_draw = (~known_any) | (u < eps)

        # persistently-negative clients: forced exploration + optional
        # fingerprint decay (fresh rounds re-dominate the EMA)
        forced = eng.neg_streak[avail] >= auxo.neg_streak_explore
        if forced.any():
            if auxo.fp_decay_on_streak < 1.0:
                eng.fingerprint[avail[forced]] *= auxo.fp_decay_on_streak
            eng.neg_streak[avail[forced]] = 0

        exploit = np.argmax(rew, axis=1)
        want = np.where(rand_draw | forced, rand_pick, exploit)
        idx = np.arange(nA)
        # a client is EXPLORING only if it holds no reward record for the
        # cohort it picked — an ε-draw that lands on a known cohort (common
        # once ExploreReward propagation has spread crumbs) still resolves
        # by assisted matching below, exactly like the per-client engine
        exploring = ~known[idx, want]
        exploring |= forced
        best_r = np.where(known[idx, want], rew[idx, want], 0.0)

        # sticky-reward check (assisted matching): fingerprinted clients
        # whose best reward is below the stick threshold request the ROOT
        # and are placed by flat nearest-identity matching — ONE
        # cosine-similarity call for the whole population
        thresh = auxo.reward_stick if auxo.assisted_matching else 0.0
        to_root = eng.fp_seen[avail] & (~exploring) & (best_r <= thresh)
        if to_root.any():
            ident_leaves = [l for l in leaves if l in eng.coordinator.identity]
            if len(ident_leaves) >= 2:
                idents = np.stack(
                    [eng.coordinator.identity[l] for l in ident_leaves]
                ).astype(np.float32)
                fps = eng.fingerprint[avail[to_root]]
                if self.host_control:
                    # §⑤: numpy twin — a kernel dispatch here would queue
                    # behind the in-flight fused step and its fetch would
                    # stall the overlapped schedule
                    sims = _cosine_np(fps, idents)
                else:
                    # pad the fingerprint batch to a power-of-two bucket
                    # (floor 512): the raw to_root count varies every round
                    # and would recompile the cosine kernel each time
                    # (measured: the dominant stage-① cost at C = 32); the
                    # floor keeps steady state at ONE compiled size — the
                    # padded rows are zeros and the extra compute is trivial
                    n = fps.shape[0]
                    fpad = np.zeros(
                        (max(512, _next_pow2(n)), fps.shape[1]), np.float32
                    )
                    fpad[:n] = fps
                    sims = np.asarray(
                        kops.cosine_similarity(jnp.asarray(fpad), jnp.asarray(idents))
                    )[:n]
                li = np.array([leaves.index(l) for l in ident_leaves])
                want[to_root] = li[np.argmax(sims, axis=1)]
            else:
                # identities not established yet: per-client prototype
                # descent through the tree (rare — first rounds only)
                for j in np.nonzero(to_root)[0]:
                    c = int(avail[j])
                    leaf = eng.coordinator.match_request(
                        c,
                        "0",
                        self.table.cluster_at(c, 0),
                        fingerprint=eng.fingerprint[c],
                    )
                    if leaf in leaves:
                        want[j] = leaves.index(leaf)
        # §⑥/⑦ churn-aware matching (FLConfig.warm_rearrivals): a
        # re-arrival's check-ins probe the root model and seed its
        # affinity from the probe fingerprint's nearest-identity leaf,
        # instead of re-exploring cold (A/B in tests/test_population_scale).
        # The marker is consumed on actual PARTICIPATION (stage-③ kept
        # rows, see _consume_rearrivals), not here — an available client
        # the quota never selects stays warm for its next check-in. Note
        # the probe is a device dispatch: under round_overlap=1 it rides
        # the plan path and can stall the §⑤ schedule on churn-heavy
        # rounds — the policy is opt-in and aimed at sync/ablation runs.
        store = getattr(eng, "store", None)
        if (
            eng.fl.warm_rearrivals
            and store is not None
            and "rearrived" in store.field_names  # pre-§⑦ checkpoints lack it
            and eng.global_mu_seen
            and len(eng.coordinator.identity) >= 2
        ):
            warm = store.gather("rearrived", avail)
            if warm.any():
                pf = eng._probe_fingerprints(avail[warm])
                best, _m, il = eng.coordinator.match_many(pf)
                # the one-line policy: check in at the nearest identity
                want[warm] = np.array([leaves.index(l) for l in il])[best]
        claimed = known_any & (want == exploit)
        return want, claimed

    def _consume_rearrivals(self, plan: MatchPlan):
        """One-shot warm-rearrival markers clear when a re-arrival actually
        LANDS a kept row (it now holds a real reward record): clearing at
        match time would waste the seed on clients the quota skipped, or on
        plans a partition flush later discards."""
        eng = self.eng
        store = getattr(eng, "store", None)
        if (
            not eng.fl.warm_rearrivals
            or store is None
            or "rearrived" not in store.field_names
        ):
            return
        kept_ids = plan.client_rows[plan.kept]
        if kept_ids.size:
            warm = store.gather("rearrived", kept_ids)
            if warm.any():
                store.scatter("rearrived", kept_ids[warm], False)

    # ------------------------------------------------------------ stage ②
    def _make_exec_step(self):
        """Build the fused fixed-shape round step (compiled once).

        (bank_params, bank_opt, slot_rows, xs, ys, seed, inv, sizes, kept,
        upd) -> (new_params, new_opt, sketches, losses); every leaf
        cohort's local training, masked aggregation, and server-opt
        application in one program. ``slot_rows`` are bank slot ids —
        global on one device, shard-local under the cohort mesh.

        Sharded (n_shards > 1): the same body runs under ``shard_map`` —
        each device sees its (slots_per_shard, ...) bank block and its
        shard_width row block, whose slot ids were made block-local by the
        MatchPlan packing. The program contains NO collectives: gather,
        training, the masked segment-sum aggregation, and the server
        optimizer all stay on the slot's device; only sketches and losses
        (returned row-sharded, fetched by stage ③) leave it.
        """
        eng, fl = self.eng, self.eng.fl
        loss_fn = eng.task.loss
        opt = eng.server_opt
        sketcher = eng.sketcher
        qfed_q = fl.qfed_q
        exec_width = self.exec_width

        def step(bparams, bopt, slot_rows, xs, ys, seed, inv, sizes, kept, upd,
                 *, nseg):
            # per-row PRNG keys derived IN-GRAPH (§⑤): the former host-side
            # jax.random.split + key_data fetch was a device round-trip on
            # the overlapped hot path whose fetch stalled behind the
            # in-flight step. Bit-identical threefry stream: row i uses
            # split(key(seed), B)[inv[i]], exactly what the host computed.
            # Under shard_map the split is replicated (seed is replicated,
            # `inv` carries global canonical indices per local row).
            base = jax.random.split(jax.random.key(seed), exec_width)
            keys = base[inv]
            # each flat row trains against ITS cohort's model (gather)
            prow = jax.tree.map(lambda a: a[slot_rows], bparams)
            deltas, losses = jax.vmap(
                lambda p, x, y, k: local_train(
                    loss_fn,
                    p,
                    x,
                    y,
                    k,
                    lr=fl.lr,
                    prox_mu=fl.prox_mu,
                    dp_clip=fl.dp_clip,
                    dp_sigma=fl.dp_sigma,
                )
            )(prow, xs, ys, keys)

            # ③ masked per-cohort aggregation (q-FedAvg or size weighting)
            if qfed_q > 0:
                wr = jnp.power(jnp.maximum(losses, 1e-6), qfed_q)
            else:
                wr = sizes
            wr = wr * kept
            denom = jax.ops.segment_sum(wr, slot_rows, num_segments=nseg)
            w = wr / jnp.maximum(denom[slot_rows], 1e-9)
            agg = jax.tree.map(
                lambda d: jax.ops.segment_sum(
                    d * w.reshape((-1,) + (1,) * (d.ndim - 1)),
                    slot_rows,
                    num_segments=nseg,
                ),
                deltas,
            )
            new_p, new_o = apply_stacked(opt, bparams, bopt, agg, upd)
            sketches = jax.vmap(sketcher)(deltas)
            return new_p, new_o, sketches, losses

        # Only bopt (EXEC_DONATE) is donated, on accelerators: the step's
        # output optimizer state reuses the input buffers. bparams is never
        # donated — the bank it replaces is what `serve_params` publishes
        # (boundary r-1 while round r is in flight, or the boundary a
        # caller's snapshot holds across a synchronous step), and a donated
        # buffer would be deleted under the serving plane. Serving a
        # snapshot while a round computes the next bank needs both copies
        # anyway; between rounds the old params are freed as soon as the
        # snapshot moves on. On CPU donation is gated OFF: XLA CPU cannot
        # donate, and requesting it forces the dispatch to synchronize on
        # input readiness, serializing the pipeline this module overlaps.
        donate = (
            {} if jax.default_backend() == "cpu"
            else {"donate_argnums": EXEC_DONATE}
        )
        nseg = self.bank.capacity if self.n_shards == 1 else self.bank.slots_per_shard

        def fused_round_step(*args):  # names the program jit_fused_round_step
            return step(*args, nseg=nseg)

        if self.n_shards == 1:
            return jax.jit(fused_round_step, **donate)
        spec = P("cohort")
        local = jax.shard_map(
            fused_round_step,
            mesh=self.mesh,
            # all row/slot inputs shard over the cohort axis; the PRNG seed
            # is replicated (every device re-derives the global key table)
            in_specs=(spec,) * 5 + (P(),) + (spec,) * 4,
            out_specs=(spec,) * 4,
            check_vma=False,
        )
        return jax.jit(local, **donate)

    def _pack_rows(self, plan: MatchPlan):
        """Host-side data plane: local batches + PRNG keys for every row.

        Rows are sampled in the plan's canonical order (leaf by leaf) as
        ONE batched population draw (`pop.sample_batches`) — the seed
        per-client `sample_batch` loop was the dominant host cost of stage
        ② and serialized against the device; padding rows replicate the
        first real row's batch (they carry weight 0). The canonical order
        keeps the draw identical for every shard layout. Returns buffers
        ready for `execute` — already staged on device in batched mode
        (`_stage_buffers`), host arrays for the sequential oracle; in the
        §⑤ overlapped schedule this runs one round ahead, while the device
        executes the previous round.
        """
        eng, fl = self.eng, self.eng.fl
        B = plan.slot_rows.shape[0]
        order_real = plan.order[: plan.n_real]
        cids = plan.client_rows[order_real]
        xs_r, ys_r = eng.data.sample_batches(
            cids, fl.batch_size, fl.local_steps, eng.rng
        )
        if eng.corrupted:
            bad = np.isin(
                cids, np.fromiter(eng.corrupted, np.int64, len(eng.corrupted))
            )
            if bad.any():
                ys_r[bad] = eng.rng.integers(
                    0, eng.data.n_classes, size=ys_r[bad].shape
                ).astype(ys_r.dtype)
        xs = np.zeros((B,) + xs_r.shape[1:], xs_r.dtype)
        ys = np.zeros((B,) + ys_r.shape[1:], ys_r.dtype)
        xs[order_real] = xs_r
        ys[order_real] = ys_r
        pad = plan.order[plan.n_real :]
        src = int(plan.order[0])
        xs[pad] = xs[src]
        ys[pad] = ys[src]
        # per-row PRNG keys follow the canonical order too: the key of a
        # participant depends on its (leaf, position) — not on which shard
        # block the layout put its row in. The batched step derives the
        # keys in-graph from (seed, inv); the sequential oracle keeps the
        # host-side derivation (bit-identical threefry either way).
        inv = np.empty(B, np.int64)
        inv[plan.order] = np.arange(B)
        if self.mode != "batched":
            base = jax.random.split(jax.random.key(plan.key_seed), B)
            kd = np.asarray(jax.random.key_data(base))[inv]
            return xs, ys, kd
        inv32 = inv.astype(np.int32)
        if self.overlap:
            # keep the host copies for checkpointing (§⑨): under the
            # overlap the LAST _pack_rows call of a run_round is always the
            # staged next round, so these buffers pair with _staged
            self._staged_host = (xs, ys, inv32)
        return self._stage_buffers(plan, xs, ys, inv32)

    def _stage_buffers(self, plan: MatchPlan, xs, ys, inv) -> tuple:
        """Place one round's row buffers on the device(s), execution-ready.

        The transfers (and the shard-local slot-id rewrite) live in the
        PACK stage, not at dispatch time: under the §⑤ overlap they happen
        one round ahead, while the previous fused step is still executing —
        at C = 32 the row-sharded device_put of the (B, steps, batch, d)
        batches was most of the dispatch-time host cost.
        """
        slot_rows = plan.slot_rows
        if self.n_shards > 1:
            # shard-local slot ids: row block j only references slots owned
            # by device j, so the in-step gather never crosses the mesh
            B = slot_rows.shape[0]
            shard_of_row = np.arange(B) // self.shard_width
            slot_rows = slot_rows - (
                shard_of_row * self.bank.slots_per_shard
            ).astype(slot_rows.dtype)
            rsh = row_sharding(self.mesh)
            ush = NamedSharding(self.mesh, P("cohort"))
            put = lambda a: jax.device_put(np.asarray(a), rsh)  # noqa: E731
            upd = jax.device_put(plan.update_slots, ush)
            seed = jax.device_put(
                np.int32(plan.key_seed), NamedSharding(self.mesh, P())
            )
        else:
            put = jnp.asarray
            upd = jnp.asarray(plan.update_slots)
            seed = jnp.asarray(np.int32(plan.key_seed))
        return (
            put(slot_rows),
            put(xs),
            put(ys),
            seed,
            put(inv),
            put(plan.sizes),
            put(plan.kept.astype(np.float32)),
            upd,
        )

    def execute(self, plan: MatchPlan, packed=None) -> ExecResult:
        """Stage ②: dispatch the round. Non-blocking in batched mode — the
        returned ExecResult holds device arrays until stage ③ reads them.
        `packed` lets the §⑤ scheduler pass buffers packed (and staged on
        device) a round ahead.
        """
        eng, fl = self.eng, self.eng.fl
        if packed is None:
            packed = self._timed("pack", self._pack_rows, plan)
        t0 = time.perf_counter()
        if self.mode == "batched":
            res = self._execute_batched(plan, packed)
        else:
            xs, ys, kd = packed
            keys = jax.random.wrap_key_data(jnp.asarray(kd))
            res = self._execute_sequential(plan, xs, ys, keys)
        self.stage_seconds["dispatch"] += time.perf_counter() - t0
        # simulated wall-clock + resource accounting
        for leaf in plan.active:
            slot = self.bank.slot_of[leaf]
            self.bank.clock[slot] += plan.durations[leaf]
            self.bank.rounds[slot] += 1
        eng.resource_used += (
            int(plan.real.sum()) * fl.local_steps * fl.batch_size
        )
        return res

    def _execute_batched(self, plan, staged) -> ExecResult:
        new_p, new_o, sketches, losses = self._exec_step(
            self.bank.params, self.bank.opt_state, *staged
        )
        self.exec_dispatches += 1
        self.bank.params = new_p
        self.bank.opt_state = new_o
        # NO host copy here: fetching would block until the step finishes.
        # ExecResult converts lazily when stage ③ reads the arrays.
        return ExecResult(sketches, losses)

    def _execute_sequential(self, plan, xs, ys, keys) -> ExecResult:
        """Reference oracle: one padded device dispatch PER cohort, host
        aggregation and eager server-opt application, like the seed engine."""
        eng, fl = self.eng, self.eng.fl
        B = plan.slot_rows.shape[0]
        d_sketch = eng.auxo.d_sketch
        sketches = np.zeros((B, d_sketch), np.float32)
        losses = np.zeros((B,), np.float32)
        quota = max(2, int(fl.participants_per_round * fl.overcommit / len(plan.leaves)))
        for leaf in plan.active:
            slot = self.bank.slot_of[leaf]
            rows = np.nonzero(plan.real & (plan.slot_rows == slot))[0]
            pad = np.concatenate([rows, np.repeat(rows[0], quota - rows.size)])
            params = self.bank.params_of(leaf)
            deltas, loss_c = eng._vmapped_train(
                params, jnp.asarray(xs[pad]), jnp.asarray(ys[pad]), keys[pad]
            )
            self.exec_dispatches += 1
            loss_np = np.asarray(loss_c)
            if fl.qfed_q > 0:
                w = np.power(np.maximum(loss_np, 1e-6), fl.qfed_q)
            else:
                w = plan.sizes[pad].astype(np.float32)
            w = w * np.concatenate(
                [plan.kept[rows], np.zeros(quota - rows.size)]
            ).astype(np.float32)
            w = jnp.asarray(w / max(w.sum(), 1e-9), jnp.float32)
            agg = jax.tree.map(lambda d: jnp.tensordot(w, d, axes=1), deltas)
            new_p, new_o = eng.server_opt.apply(
                params, self.bank.opt_state_of(leaf), agg
            )
            si = jnp.asarray(slot)
            self.bank.params = jax.tree.map(
                lambda a, v: a.at[si].set(v), self.bank.params, new_p
            )
            self.bank.opt_state = jax.tree.map(
                lambda a, v: a.at[si].set(v), self.bank.opt_state, new_o
            )
            if eng.auxo.enabled:
                sk = np.asarray(eng._vmapped_sketch(deltas))
                sketches[rows] = sk[: rows.size]
            losses[rows] = loss_np[: rows.size]
        return ExecResult(sketches, losses)

    # ------------------------------------------------------------ stage ③
    def apply_feedback(self, plan: MatchPlan, res: ExecResult) -> bool:
        """Retire a round: clustering feedback + dense-table updates.

        Returns True iff a partition event was applied — the §⑤ scheduler
        flushes the pipeline then (a stale plan is invalid across a
        partition). Reading `res.sketches` here is the first (lazy) device
        fetch of the round's artifacts.
        """
        return self._timed("feedback", self._apply_feedback, plan, res)

    def _apply_feedback(self, plan: MatchPlan, res: ExecResult) -> bool:
        eng, fl, auxo = self.eng, self.eng.fl, self.eng.auxo
        if not auxo.enabled:
            return False
        nact = len(plan.active)
        if nact == 0:
            return False
        self._consume_rearrivals(plan)
        rows_by = [
            np.nonzero(plan.kept & (plan.slot_rows == self.bank.slot_of[leaf]))[0]
            for leaf in plan.active
        ]
        # tight per-cohort batch width: pad to the power-of-two bucket of
        # the round's largest kept set, NOT the full flat row width B — at
        # C = 32 the old (nact, B, d) layout made stage ③'s clustering
        # dispatch 30x larger than the data it carried (the dominant round
        # cost); bucketing keeps the jit cache small
        p_fb = max(8, _next_pow2(max(r.size for r in rows_by)))
        fp_batch = np.zeros((nact, p_fb, auxo.d_sketch), np.float32)
        masks = np.zeros((nact, p_fb), np.float32)
        kept_ids_list: List[np.ndarray] = []
        claimed_list: List[np.ndarray] = []
        for ci, leaf in enumerate(plan.active):
            rows = rows_by[ci]
            kept_ids = plan.client_rows[rows]
            sk_kept = res.sketches[rows]
            # center against the cross-cohort GLOBAL mean (EMA'd in leaf
            # order, like the per-cohort sequential updates), normalize, EMA
            round_mu = sk_kept.mean(0)
            if eng.global_mu_seen:
                eng.global_mu = 0.8 * eng.global_mu + 0.2 * round_mu
            else:
                eng.global_mu, eng.global_mu_seen = round_mu.copy(), True
            ctr = sk_kept - eng.global_mu[None, :]
            ctr /= np.linalg.norm(ctr, axis=1, keepdims=True) + 1e-9
            if fl.affinity_loss_rate > 0:
                lose = eng.rng.random(kept_ids.size) < fl.affinity_loss_rate
                eng.fingerprint[kept_ids[lose]] = 0.0
                eng.fp_seen[kept_ids[lose]] = False
            seen = eng.fp_seen[kept_ids]
            eng.fingerprint[kept_ids] = np.where(
                seen[:, None],
                (1 - eng.fp_beta) * eng.fingerprint[kept_ids] + eng.fp_beta * ctr,
                ctr,
            )
            eng.fp_seen[kept_ids] = True
            fp_batch[ci, : kept_ids.size] = eng.fingerprint[kept_ids]
            masks[ci, : kept_ids.size] = 1.0
            kept_ids_list.append(kept_ids)
            claimed_list.append(plan.claimed[rows])

        results = eng.coordinator.feedback_all(
            plan.active,
            [k.tolist() for k in kept_ids_list],
            # host control plane keeps the batches in numpy — no transfer
            fp_batch if self.host_control else jnp.asarray(fp_batch),
            masks if self.host_control else jnp.asarray(masks),
            plan.round_idx,
            fl.rounds,
            claimed_list,
            batched=(self.mode == "batched"),
            backend="host" if self.host_control else "device",
        )

        # dense-table reward application + ExploreReward propagation;
        # `cur` tracks the live leaf set so propagation targets match the
        # cohort-by-cohort semantics of the sequential engine
        cur = list(plan.leaves)
        dists = distance_matrix(cur)
        gamma = auxo.gamma
        if (
            fl.affinity_loss_rate == 0
            and not fl.allow_cross_cohort_duplicates
            and not any(fb.event is not None for fb in results)
        ):
            # fast path (steady-state rounds): client sets are disjoint
            # across cohorts (the dedup assert guarantees it — a policy
            # that opts into duplicates must take the loop below, whose
            # sequential EMA handles repeated ids) and no event mutates the
            # leaf set mid-loop, so every per-cohort table update collapses
            # into one fancy-indexed block over (kept clients x leaf slots)
            self._apply_rewards_vectorized(results, cur, dists, gamma)
            return False
        any_event = False
        for fb in results:
            ids = np.asarray(fb.client_ids, np.int64)
            if ids.size == 0:
                if fb.event is not None:
                    any_event = True
                    self._apply_partition(fb.event, cur)
                continue
            neg = fb.delta < 0
            eng.neg_streak[ids[neg]] += 1
            eng.neg_streak[ids[~neg]] = 0
            if fl.affinity_loss_rate > 0:
                lose = eng.rng.random(ids.size) < fl.affinity_loss_rate
            else:
                lose = np.zeros(ids.size, bool)
            if lose.any():
                self.table.wipe(ids[lose])  # unstable client restarts exploring
            ok = ~lose
            slot = self.bank.slot_of[fb.cohort_id]
            self.table.feedback(ids[ok], slot, fb.delta[ok], gamma)
            self.table.set_cluster(ids[ok], slot, fb.assign[ok])
            src = cur.index(fb.cohort_id)
            slot_dist = {
                self.bank.slot_of[o]: int(dists[src, j])
                for j, o in enumerate(cur)
                if o != fb.cohort_id
            }
            self.table.propagate(ids[ok], fb.delta[ok], slot_dist)
            if fb.event is not None:
                any_event = True
                self._apply_partition(fb.event, cur)
                dists = distance_matrix(cur)
        return any_event

    def _apply_rewards_vectorized(self, results, cur: List[str], dists, gamma):
        """Event-free stage-③ table application as a handful of numpy ops.

        Equivalent to the per-cohort loop below (client ids are unique
        across cohorts within a round — see check_cross_cohort_unique — so
        the fancy-indexed writes never collide); split out because the
        cohort loop was a visible slice of round latency at C >= 32.
        """
        eng = self.eng
        live = [fb for fb in results if len(fb.client_ids) > 0]
        if not live:
            return
        ids = np.concatenate([np.asarray(fb.client_ids, np.int64) for fb in live])
        delta = np.concatenate([fb.delta for fb in live]).astype(np.float32)
        assign = np.concatenate([fb.assign for fb in live])
        src = np.concatenate(
            [
                np.full(len(fb.client_ids), cur.index(fb.cohort_id), np.int64)
                for fb in live
            ]
        )
        neg = delta < 0
        eng.neg_streak[ids[neg]] += 1
        eng.neg_streak[ids[~neg]] = 0
        leaf_slots = np.array([self.bank.slot_of[l] for l in cur], np.int64)
        own = leaf_slots[src]
        # one gather → block update → one scatter: the same cells and dtype
        # math as direct dense writes (ids are unique — see the dedup
        # assert — so the gathered copies cannot alias), and the only form
        # the chunked store view can serve without a dense (N, capacity)
        # table behind it
        row = np.arange(ids.size)
        rw, kn, cl = self.table.gather_rows(ids)
        # EMA reward-record update on the trained cohort's slot
        rw[row, own] = gamma * delta + (1.0 - gamma) * rw[row, own]
        has = assign >= 0
        cl[row[has], own[has]] = assign[has]
        # ExploreReward propagation: ΔR/(d+1) to every OTHER leaf
        w = delta[:, None] / (dists[src] + 1.0)
        w[row, src] = 0.0
        rw[:, leaf_slots] += w.astype(np.float32)
        kn[:, leaf_slots] = True
        self.table.scatter_rows(ids, rw, kn, cl)

    def _apply_partition(self, event, cur: List[str]):
        child_slots = self.bank.spawn_children(event.parent, event.children)
        self.table.seed_children(self.bank.slot_of[event.parent], child_slots)
        i = cur.index(event.parent)
        cur[i : i + 1] = list(event.children)

    # ------------------------------------------------------------ driver
    def _plan_and_pack(self, r: int) -> Tuple[int, Any, Any]:
        plan = self._timed("plan", self.plan_round, r)
        if plan is None:
            if self.overlap:
                self._staged_host = None  # no buffers ride with an empty round
            return (r, None, None)
        packed = self._timed("pack", self._pack_rows, plan)
        return (r, plan, packed)

    def _retire(self) -> bool:
        """Apply the in-flight round's feedback (True iff it partitioned)."""
        if self._inflight is None:
            return False
        plan, res = self._inflight
        self._inflight = None
        return self.apply_feedback(plan, res)

    def flush(self):
        """Drain the pipeline: retire the in-flight round's feedback.

        Called before evaluation and at end of run so host tables and
        fingerprints are consistent with the bank models. A partition
        during the drain discards the staged next-round plan (it was
        computed against pre-partition tables); otherwise the staged plan
        survives — its one-round staleness is exactly the steady-state
        semantics, so an eval-time flush does not perturb the schedule.
        No-op in synchronous mode and on an empty pipeline.
        """
        if self._retire():
            self._staged = None
            self._staged_host = None
        self.serve_params = self.bank.params

    def run_round(self, r: int):
        if not self.overlap:
            plan = self._timed("plan", self.plan_round, r)
            if plan is None:
                return
            res = self.execute(plan)
            self.apply_feedback(plan, res)
            self.serve_params = self.bank.params
            return
        # §⑤ depth-2 overlapped schedule. Host-visible order per call:
        #   fetch round r-1's sketches/losses (the ONLY device dependency
        #     of stage ③; this drains the device queue)
        #   → dispatch round r (plan/buffers staged by the previous call;
        #     the queue is empty, so the enqueue never blocks — XLA CPU
        #     caps the multi-device in-flight depth at 1, measured)
        #   → apply round r-1's feedback        ┐ host-control numpy,
        #   → plan round r+1 (one-round-stale)  │ all overlapped with the
        #   → pack + device-stage its buffers   ┘ device executing round r
        staged, self._staged = self._staged, None
        prev, self._inflight = self._inflight, None
        if prev is not None:
            prev[1].sketches, prev[1].losses  # lazy fetch, before dispatch
        if staged is not None and staged[0] == r:
            _, plan, packed = staged
        else:
            _, plan, packed = self._plan_and_pack(r)
        # serving snapshot candidate: the bank BEFORE round r's dispatch
        # replaces it with futures. prev's fetch above already drained the
        # queue, so these leaves are concrete round r-1 values.
        pre = self.bank.params
        res = self.execute(plan, packed) if plan is not None else None
        events = prev is not None and self.apply_feedback(*prev)
        if plan is not None:
            if events:
                # pipeline FLUSH: the partition invalidated round r's stale
                # plan (it trained the pre-partition leaf set one extra
                # round) — drain it synchronously instead of keeping it in
                # flight, so the next plan sees fully reseeded tables
                self.flushes += 1
                self.apply_feedback(plan, res)
            else:
                self._inflight = (plan, res)
        # publish the serving snapshot for the gap ahead: boundary r-1
        # while round r stays in flight, boundary r if it was drained (a
        # flush also reseeded tables, so only the post-partition bank
        # matches them)
        self.serve_params = self.bank.params if self._inflight is None else pre
        # stage round r+1 against the current tables: they are missing only
        # round r's feedback (in flight) — stale by exactly one round
        self._staged = self._plan_and_pack(r + 1)
