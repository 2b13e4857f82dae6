"""Episodic serving engine: production-shaped adapt-many-tasks serving.

The LM engine (repro.serve.engine) serves token decode; this engine serves
the paper's test-time workload — ORBIT-style per-user personalization at
traffic scale.  A request is one episode: a support set to adapt on and a
query stream to answer.  The paper's headline tradeoff is that
meta-learners are cheap at test time ("just a few optimization steps or a
single forward pass" per new task); at "millions of users" the scarce
resource is therefore millions of *adapted task states*, not params, and
re-adaptation is the expensive tail (fomaml re-adapt is ~66x a query
chunk per table1_adaptation_cost.csv).  The engine is built around that:

* **Continuous batching with admission control** — ``submit`` enqueues;
  each ``step`` admits FIFO from the queue into up to ``n_slots`` live
  task lanes (head-of-line: a request whose uid is already live defers so
  one uid is never adapted twice concurrently), batch-adapts newly
  admitted tasks, and micro-batches the next query chunk of every live
  task, all through a per-shape AOT compile cache
  (:class:`repro.train.pipeline.BucketedStepCache`) padded to fixed
  shapes so compile counters stay flat and co-scheduling is bit-exact.
* **Per-request latency accounting from an injectable clock** — requests
  carry enqueue/admit/adapt/first-logit/done timestamps stamped from the
  engine ``clock`` (default ``time.monotonic``; tests inject a manually
  advanced ``FakeClock``), and ``stats()`` reports exact nearest-rank
  p50/p99 adapt latency (enqueue → state ready) and query latency
  (enqueue → first logit) plus the current queue depth.
* **SLO-aware dispatch scheduling** — adaptation is the expensive tail,
  query chunks are cheap.  With ``query_slo_us`` set, a step whose
  pending adapt wave would push a live lane's first-unserved-query past
  its deadline (estimated from an EWMA of measured adapt-dispatch cost,
  seedable via ``adapt_cost_hint_us``) *defers the adapt wave* and spends
  the dispatch on queries instead; a deadline that is already missed no
  longer preempts (the SLO is blown either way), so adapt waves cannot
  starve.  ``stats()['slo_preemptions']`` counts the decisions.
* **Two-tier task-state store** — adapted states live in an L1 LRU
  (:class:`TaskStateCache`) keyed by task uid; with ``warm_dir`` set, L1
  eviction *spills* the state to a disk warm tier
  (:class:`WarmTaskStore`) through the checkpoint serialization
  (``repro.train.checkpoint.save_array_tree``), and a repeat uid that
  misses L1 *rehydrates* from the warm tier instead of re-adapting —
  bit-exact to the originally adapted state, with unchanged avals so the
  compiled predict dispatch is reused (counters flat).  Without
  ``warm_dir`` eviction discards, as before.
* **LITE-chunked forward-only adaptation** — the aggregating learners run
  the serve estimators (repro.core.lite.serve_sum / serve_segment_sum):
  exact values, no-grad chunks, so a 1000-image support set adapts under
  an O(chunk_size) activation bound.

    engine = EpisodicServeEngine(learner, params, n_slots=4,
                                 support_buckets=(64,), query_chunk=8,
                                 warm_dir="/tmp/warm", query_slo_us=5e4)
    engine.run_to_completion([EpisodicRequest(uid=0, support_x=sx,
                                              support_y=sy, query_x=qx)])
"""
from __future__ import annotations

import collections
import dataclasses
import math
import os
import pathlib
import pickle
import shutil
import time
import zlib
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.episodic import (Task, index_task_state, stack_task_states)
from repro.core.episodic_train import task_key
from repro.kernels import dispatch
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearner
from repro.data.episodic import (bucket_for, collate_task_batch,
                                 iter_query_chunks)
from repro.faults.plan import WARM_CORRUPT, WARM_VANISH
from repro.serve.quant_params import (dequantize_params, param_bytes,
                                      place_serving_weights, quantize_frozen)
from repro.train.checkpoint import load_array_tree, save_array_tree
from repro.train.pipeline import BucketedStepCache

PyTree = Any


def stable_uid_hash(uid: int) -> int:
    """Process-stable hash of a task uid (crc32 of its 8-byte encoding).

    Python's builtin ``hash`` is salted per process; routing and warm-dir
    sharding both need a uid -> integer map that agrees across engine
    restarts and across replica processes, so repeat visitors always land
    on the replica (and warm subdir) holding their state."""
    return zlib.crc32(int(uid).to_bytes(8, "little", signed=True))


def _pctl(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (classic definition): exact and assertable
    against a scripted arrival stream — no interpolation."""
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


@dataclasses.dataclass
class EpisodicRequest:
    """One personalization episode.

    ``uid`` is the task identity (the state-store key): two requests with
    the same uid are the same task, and the second may omit its support
    set entirely if the first's state is still in either store tier.
    ``query_x`` is the query stream — served in engine-sized chunks,
    logits accumulated in arrival order.

    Degradation outcomes (each also a ``stats()`` counter): ``rejected``
    — bounded-queue backpressure refused the submit (``retry_after_us``
    estimates when to re-offer; the request was never admitted and no
    admitted request is ever dropped for it); ``abandoned`` — the
    per-request deadline passed before the first logit (queued or still
    awaiting adaptation) and the engine freed the lane; ``failed`` — a
    support-less request whose only stored state turned out corrupt
    (quarantined): nothing can ever produce its logits.

    The ``t_*`` timestamps are stamped by the engine from its injectable
    clock (seconds, monotonic): ``t_enqueue`` at submit, ``t_admit`` when
    a slot is taken, ``t_adapt`` when the adapted state lands (absent on
    a state-store hit), ``t_first_logit`` when the first query chunk
    returns, ``t_done`` at retirement."""

    uid: int
    query_x: np.ndarray                          # (M, ...) query stream
    support_x: Optional[np.ndarray] = None       # (N, ...); None ok on a
    support_y: Optional[np.ndarray] = None       # (N,)     expected cache hit
    way: int = 5
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    served: int = 0
    cache_hit: Optional[bool] = None             # set at admission
    done: bool = False
    rejected: bool = False                       # backpressure refusal
    retry_after_us: Optional[float] = None       # stamped on rejection
    abandoned: bool = False                      # deadline passed pre-logit
    failed: bool = False                         # unrecoverable (see above)
    t_enqueue: Optional[float] = None
    t_admit: Optional[float] = None
    t_adapt: Optional[float] = None
    t_first_logit: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def n_queries(self) -> int:
        return int(np.asarray(self.query_x).shape[0])

    def all_logits(self) -> np.ndarray:
        """(M, way) logits in query order (complete once ``done``)."""
        if not self.logits:
            return np.zeros((0, self.way), np.float32)
        return np.concatenate(self.logits, axis=0)

    def predictions(self) -> np.ndarray:
        return np.argmax(self.all_logits(), axis=-1)


class TaskStateCache:
    """LRU cache of adapted task states keyed by task uid — the L1 of the
    two-tier store.

    Stats contract (well-defined, tested): ``hits``/``misses`` count
    ``get`` lookups ONLY.  ``put`` on an existing uid is an *overwrite* —
    it refreshes recency and bumps ``overwrites``, never hits/misses.
    ``evictions`` counts capacity evictions (never overwrites); each
    evicted ``(uid, state)`` is handed to ``on_evict`` — the two-tier
    store's spill path — before being dropped from L1."""

    def __init__(self, capacity: int = 64,
                 on_evict: Optional[Callable[[int, PyTree], None]] = None):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self.overwrites = 0
        self.evictions = 0
        self._on_evict = on_evict
        self._d: "collections.OrderedDict[int, PyTree]" = \
            collections.OrderedDict()

    def get(self, uid: int) -> Optional[PyTree]:
        if uid in self._d:
            self._d.move_to_end(uid)
            self.hits += 1
            return self._d[uid]
        self.misses += 1
        return None

    def put(self, uid: int, state: PyTree) -> None:
        if uid in self._d:
            self.overwrites += 1
        self._d[uid] = state
        self._d.move_to_end(uid)
        while len(self._d) > self.capacity:
            old_uid, old_state = self._d.popitem(last=False)
            self.evictions += 1
            if self._on_evict is not None:
                self._on_evict(old_uid, old_state)

    def __contains__(self, uid: int) -> bool:
        return uid in self._d

    def __len__(self) -> int:
        return len(self._d)


class WarmTaskStore:
    """Disk warm tier for spilled task states: one self-describing npz
    per uid (atomic tmp + ``os.replace``), written/read through the
    checkpoint serialization (``save_array_tree``/``load_array_tree``) so
    a rehydrated state is bit-exact to the spilled one.  The abstract
    template per uid (shapes/dtypes/treedef — tiny) is held host-side AND
    persisted beside the npz as a pickle sidecar (``uid_N.tmpl.pkl``,
    atomic tmp + replace), so spilled states survive an engine restart: a
    fresh store over the same directory rescans the sidecars and serves
    every surviving uid bit-exactly (``template_restores`` counts them).
    A sidecar that fails to load is dropped (its uid just re-adapts); a
    quarantined npz drops its sidecar too, so restart can never resurrect
    an entry that was ruled corrupt.

    **Sharded layout + cross-process safety** (the multi-replica serving
    contract): with ``shards > 1`` each uid's files live in the uid-hash
    subdir ``shard_{stable_uid_hash(uid) % shards}`` — a pure function of
    the uid, so every store over the same directory (one per serving
    replica) agrees on where a uid lives without coordination, and
    replicas whose routed uid sets map to disjoint shards never contend
    on a subdir.  The template index is no longer frozen at construction:
    a ``get``/``in`` miss *rescans* the uid's canonical sidecar path (and,
    defensively, every shard subdir) before giving up, so a uid spilled by
    replica A AFTER replica B's startup scan is still found by B — the
    post-failover rehydration path (``rescan_hits`` counts these late
    finds).  Entries written under a different shard count remain
    loadable: the rescan walks all subdirs, and a later ``put`` migrates
    the files to the canonical shard.

    Every read verifies the whole-content CRC32 the writer embedded
    (``load_array_tree(verify=True)``); a zero-byte/truncated file fails
    earlier inside ``np.load``.  ANY read failure — bad zip, checksum
    mismatch, missing leaf — *quarantines* the entry: the file is renamed
    aside (``quarantine_uid_*.npz``, kept for forensics), the template is
    dropped, ``quarantined`` is bumped, and ``get`` returns None so the
    caller falls back to re-adaptation.  A file that vanished outright
    (template present, path gone) counts as quarantined too.  ``fault_plan``
    (:class:`repro.faults.FaultPlan`) drives site ``warm.corrupt``:
    fired at a uid's ``put``, the just-published npz is truncated to
    ``payload`` bytes — crash-mid-write residue, deterministically."""

    def __init__(self, directory: str | pathlib.Path, fault_plan=None,
                 shards: int = 1):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.shards = int(shards)
        self._templates: Dict[int, PyTree] = {}
        # where each known uid's files actually live (its subdir) — equals
        # the canonical shard dir except for entries written under a
        # different shard count and not yet migrated by a fresh put
        self._homes: Dict[int, pathlib.Path] = {}
        self._fault_plan = fault_plan
        self.quarantined = 0
        self.template_restores = 0
        self.rescan_hits = 0
        # durable warm tier: rescan template sidecars left by a previous
        # store over this directory (engine restart) — an unreadable
        # sidecar is dropped, its uid simply re-adapts
        for side in sorted(self.dir.glob("uid_*.tmpl.pkl")) + \
                sorted(self.dir.glob("shard_*/uid_*.tmpl.pkl")):
            if self._load_sidecar(side):
                self.template_restores += 1

    def _load_sidecar(self, side: pathlib.Path) -> bool:
        try:
            uid = int(side.name.split(".")[0].split("_", 1)[1])
            with open(side, "rb") as f:
                self._templates[uid] = pickle.load(f)
            self._homes[uid] = side.parent
            return True
        except Exception as e:  # noqa: BLE001 — any unreadable sidecar
            print(f"warm tier: dropping unreadable template sidecar "
                  f"{side.name} ({type(e).__name__}: {e})", flush=True)
            side.unlink(missing_ok=True)
            return False

    def _shard_dir(self, uid: int) -> pathlib.Path:
        """Canonical subdir for ``uid`` — a pure function of (uid, shards),
        so independent stores over the same directory agree on it."""
        if self.shards == 1:
            return self.dir
        return self.dir / f"shard_{stable_uid_hash(uid) % self.shards}"

    def _home(self, uid: int) -> pathlib.Path:
        return self._homes.get(uid, self._shard_dir(uid))

    def _path(self, uid: int) -> pathlib.Path:
        return self._home(uid) / f"uid_{uid}.npz"

    def _tmpl_path(self, uid: int) -> pathlib.Path:
        return self._home(uid) / f"uid_{uid}.tmpl.pkl"

    def _rescan(self, uid: int) -> bool:
        """Rescan-on-miss: look for ``uid``'s sidecar written AFTER this
        store's startup scan (another replica's spill — the post-failover
        rehydration path).  Canonical shard path first, then every shard
        subdir and the root (entries from a different shard count)."""
        candidates = [self._shard_dir(uid) / f"uid_{uid}.tmpl.pkl",
                      self.dir / f"uid_{uid}.tmpl.pkl"]
        candidates += sorted(self.dir.glob(f"shard_*/uid_{uid}.tmpl.pkl"))
        for side in candidates:
            if side.exists() and self._load_sidecar(side):
                self.rescan_hits += 1
                return True
        return False

    def put(self, uid: int, state: PyTree) -> None:
        home = self._shard_dir(uid)
        if home != self.dir:
            # parents=False: a vanished warm ROOT must stay an OSError for
            # the caller (warm.vanish degrades to L1-only), never be
            # silently recreated here
            home.mkdir(exist_ok=True)
        old_home = self._homes.get(uid)
        tmp = home / f".tmp_uid_{uid}.npz"
        save_array_tree(tmp, state)
        os.replace(tmp, home / f"uid_{uid}.npz")
        tmpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
            state)
        self._templates[uid] = tmpl
        self._homes[uid] = home
        # template sidecar AFTER the npz: a crash between the two leaves
        # an orphan npz that a restarted store simply never lists (safe),
        # never a template pointing at a half-written payload
        side_tmp = home / f".tmp_uid_{uid}.tmpl.pkl"
        with open(side_tmp, "wb") as f:
            pickle.dump(tmpl, f)
        os.replace(side_tmp, home / f"uid_{uid}.tmpl.pkl")
        if old_home is not None and old_home != home:
            # migrated from a stale shard layout: drop the old files so a
            # directory-walking rescan can never resurrect the stale copy
            (old_home / f"uid_{uid}.npz").unlink(missing_ok=True)
            (old_home / f"uid_{uid}.tmpl.pkl").unlink(missing_ok=True)
        if self._fault_plan is not None:
            spec = self._fault_plan.fire(WARM_CORRUPT, uid)
            if spec is not None:
                keep = int(spec.payload) if spec.payload is not None else 16
                with open(self._path(uid), "r+b") as f:
                    f.truncate(keep)

    def _quarantine(self, uid: int, err: Exception) -> None:
        path = self._path(uid)
        self.quarantined += 1
        self._tmpl_path(uid).unlink(missing_ok=True)
        self._templates.pop(uid, None)
        if path.exists():
            aside = path.parent / \
                f"quarantine_uid_{uid}_{self.quarantined}.npz"
            os.replace(path, aside)
            where = f"moved aside to {aside.name}"
        else:
            where = "file already gone"
        self._homes.pop(uid, None)
        print(f"warm tier: quarantined uid={uid} ({type(err).__name__}: "
              f"{err}; {where})", flush=True)

    def get(self, uid: int) -> Optional[PyTree]:
        if uid not in self._templates and not self._rescan(uid):
            return None
        if not self._path(uid).exists():
            self._quarantine(uid, FileNotFoundError(str(self._path(uid))))
            return None
        try:
            return load_array_tree(self._path(uid), self._templates[uid],
                                   verify=True)
        except Exception as e:  # noqa: BLE001 — any unreadable entry
            self._quarantine(uid, e)
            return None

    def __contains__(self, uid: int) -> bool:
        if uid not in self._templates and not self._rescan(uid):
            return False
        return self._path(uid).exists()

    def __len__(self) -> int:
        return sum(1 for uid in self._templates if self._path(uid).exists())


class TwoTierTaskStore:
    """L1 LRU of resident task states over an optional disk warm tier.

    ``get`` promotes a warm-tier hit back into L1 (which may spill
    another state — states cascade, none is silently lost while the warm
    tier holds it).  ``hits``/``misses`` are the L1's; ``spills`` counts
    evictions that landed in the warm tier, ``rehydrates`` counts
    warm-tier loads.  With ``warm_dir=None`` eviction discards (the PR3
    behavior) and ``rehydrates`` stays 0.

    A spill whose write fails (warm directory removed out from under the
    engine — tmpfs cleanup, the ``warm.vanish`` fault site) does NOT take
    the engine down: the error is logged once, ``spill_errors`` is
    bumped, and the store degrades to L1-only for the rest of its life
    (evictions discard, warm lookups stop) — correctness is untouched
    because a discarded state just re-adapts on the next request."""

    def __init__(self, capacity: int = 64,
                 warm_dir: Optional[str | pathlib.Path] = None,
                 fault_plan=None, warm_shards: int = 1):
        self.warm = (WarmTaskStore(warm_dir, fault_plan=fault_plan,
                                   shards=warm_shards)
                     if warm_dir is not None else None)
        self.l1 = TaskStateCache(capacity, on_evict=self._spill)
        self._fault_plan = fault_plan
        self.spills = 0
        self.rehydrates = 0
        self.spill_errors = 0
        self.warm_disabled = False

    @property
    def quarantined(self) -> int:
        return self.warm.quarantined if self.warm is not None else 0

    @property
    def rescan_hits(self) -> int:
        return self.warm.rescan_hits if self.warm is not None else 0

    def _warm_live(self) -> bool:
        return self.warm is not None and not self.warm_disabled

    def _spill(self, uid: int, state: PyTree) -> None:
        if not self._warm_live():
            return
        if self._fault_plan is not None and \
                self._fault_plan.fire(WARM_VANISH, uid) is not None:
            shutil.rmtree(self.warm.dir, ignore_errors=True)
        try:
            self.warm.put(uid, state)
        except OSError as e:
            self.spill_errors += 1
            self.warm_disabled = True
            print(f"warm tier: spill of uid={uid} failed "
                  f"({type(e).__name__}: {e}) — degrading to L1-only, "
                  f"evicted states will re-adapt", flush=True)
            return
        self.spills += 1

    def get(self, uid: int) -> Optional[PyTree]:
        state = self.l1.get(uid)
        if state is not None:
            return state
        if self._warm_live():
            state = self.warm.get(uid)
            if state is not None:
                self.rehydrates += 1
                self.l1.put(uid, state)      # promote (may spill another)
                return state
        return None

    def put(self, uid: int, state: PyTree) -> None:
        self.l1.put(uid, state)

    def __contains__(self, uid: int) -> bool:
        return uid in self.l1 or (self._warm_live() and uid in self.warm)

    def __len__(self) -> int:
        return len(self.l1)


@dataclasses.dataclass
class _Slot:
    req: EpisodicRequest
    state: Optional[PyTree]                      # None => awaiting adaptation
    stream: Iterator


class EpisodicServeEngine:
    """Single-host adapt-many-tasks engine over the batched TaskState
    contract (``learner.adapt_batch`` / ``learner.predict_batch``) with
    continuous batching, SLO accounting, and a two-tier state store (see
    module docstring for the full contract).

    ``support_buckets`` are the planned support pad caps
    (:func:`repro.data.episodic.plan_buckets` builds them from a stream
    histogram); a support set larger than every cap is rejected at
    admission with an actionable error, same stale-histogram contract as
    training-side collation.  All requests must share the learner's
    ``way`` and one query trailing shape — one engine per model input
    spec, as with the LM engine.
    """

    def __init__(self, learner: MetaLearner, params: PyTree, *,
                 lite: Optional[LiteSpec] = None, n_slots: int = 4,
                 query_chunk: int = 8,
                 support_buckets: Sequence[int] = (64,),
                 cache_capacity: int = 64, seed: int = 0,
                 kernel_backend: Optional[str] = None,
                 clock: Optional[Callable[[], float]] = None,
                 warm_dir: Optional[str | pathlib.Path] = None,
                 query_slo_us: Optional[float] = None,
                 adapt_cost_hint_us: Optional[float] = None,
                 fault_plan=None,
                 max_queue: Optional[int] = None,
                 deadline_us: Optional[float] = None,
                 serve_quant: str = "none",
                 serve_layout: Optional[str] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 warm_shards: int = 1):
        """Fault-tolerance knobs: ``fault_plan`` threads to the store tiers
        (sites ``warm.corrupt`` / ``warm.vanish``); ``max_queue`` bounds
        the admission queue — a submit over the bound is REJECTED with a
        ``retry_after_us`` estimate from the adapt-cost EWMA instead of
        growing the queue without bound (admitted requests are never
        dropped); ``deadline_us`` abandons a request whose deadline
        (from ``t_enqueue``) passes before its first logit, freeing the
        lane/queue slot.  All three default off — behavior unchanged.

        Weight-stationary quantized serving: ``serve_quant='int8'``
        quantizes the learner kind's FROZEN param slice into the blockwise
        int8 form (``repro.serve.quant_params.quantize_frozen``) —
        dequantized lazily inside both jitted dispatches, never resident
        in f32 — and ``stats()`` reports the measured resident parameter
        bytes.  ``serve_layout`` + ``mesh`` place the serving weights in a
        named layout from ``repro.roofline.analysis.SERVING_LAYOUTS``
        (e.g. ``weight_stationary``: contracting dims sharded so the
        per-step wire carries small activations instead of gathered
        weights); resolve ``'auto'`` to a concrete name with
        ``choose_serving_layout`` BEFORE construction (the serving
        launcher does) — the engine applies a layout, it does not score
        one.  In a multi-replica deployment ``mesh`` is the replica's OWN
        disjoint device group (``make_replica_mesh``): weights are
        stationary within the group and no predict-step collective ever
        crosses it.

        ``warm_shards`` partitions the warm directory into uid-hash
        subdirs (see :class:`WarmTaskStore`) — replicas sharing one warm
        root spill/rehydrate without contending on a subdir."""
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.learner = learner
        self.params = params
        # serving weights: the frozen slice quantized (or wrapped as-is
        # for mode 'none' — the dispatch path is identical either way, so
        # flipping --serve-quant can never change compile counters)
        self.serve_quant = serve_quant
        self._weights = quantize_frozen(learner, params, serve_quant)
        self._param_bytes = param_bytes(self._weights)
        self.serve_layout = serve_layout
        self.mesh = mesh
        self._weights = place_serving_weights(self._weights, mesh,
                                              serve_layout)
        # serve-time default: exact forward values, chunk-bounded memory
        self.lite = lite if lite is not None else LiteSpec(exact=True,
                                                           chunk_size=32)
        self.n_slots = n_slots
        self.query_chunk = query_chunk
        self.support_buckets = tuple(sorted(support_buckets))
        self.store = TwoTierTaskStore(cache_capacity, warm_dir,
                                      fault_plan=fault_plan,
                                      warm_shards=warm_shards)
        self.clock = clock if clock is not None else time.monotonic
        self.query_slo_us = query_slo_us
        self.max_queue = max_queue
        self.deadline_us = deadline_us
        # EWMA of measured adapt-dispatch wall time; zero-duration
        # observations (a FakeClock that wasn't advanced) are ignored so
        # scripted tests keep a stable, assertable estimate
        self._adapt_cost_est_us: Optional[float] = adapt_cost_hint_us
        self._queue: "collections.deque[EpisodicRequest]" = \
            collections.deque()
        self._slots: List[Optional[_Slot]] = [None] * n_slots
        self._base_key = jax.random.key(seed)
        # The aggregation-kernel backend (repro.kernels.dispatch) is an
        # ENGINE property, resolved once at construction (None = the
        # ambient dispatch default) and bound at trace time inside both
        # dispatches.  The per-shape compile cache keys on shapes alone,
        # so flipping the ambient default on a warm engine never
        # recompiles or changes results — a different backend is a
        # different engine.
        self.kernel_backend = dispatch.resolve_backend(kernel_backend)

        def _adapt_fn(sw, batch, keys):
            with dispatch.use_backend(self.kernel_backend):
                # lazy in-jit dequantize: XLA fuses the int8->f32 expansion
                # into the step; the f32 weights never persist between steps
                return learner.adapt_batch(dequantize_params(sw), batch,
                                           keys, self.lite)

        def _predict_fn(sw, states, qx):
            with dispatch.use_backend(self.kernel_backend):
                return learner.predict_batch(dequantize_params(sw), states,
                                             qx)

        self._adapt = BucketedStepCache(_adapt_fn)
        self._predict = BucketedStepCache(_predict_fn)
        # resident stacked states for an unchanged live cohort — slot
        # states are immutable after adaptation, so the (n_slots, ...)
        # predict-side stack is rebuilt only when a slot joins or retires
        self._stacked_states: Optional[tuple] = None
        self._adapt_lat_us: List[float] = []
        self._query_lat_us: List[float] = []
        self.tasks_adapted = 0
        self.queries_served = 0
        self.slo_preemptions = 0
        self.steps = 0
        self.rejections = 0
        self.deadline_abandoned = 0
        self.failed_requests = 0

    # -- scheduling ----------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def submit(self, req: EpisodicRequest) -> bool:
        """Enqueue ``req`` (stamps ``t_enqueue``); admission happens FIFO
        inside ``step`` as slots free up — the continuous-batching entry
        point.  With ``max_queue`` set, a submit that would overflow the
        bound is REJECTED (returns False; ``req.rejected`` set): the
        request is not enqueued, nothing already admitted/queued is
        displaced, and ``req.retry_after_us`` carries a re-offer estimate
        — queue-depth-ahead / n_slots adapt waves at the EWMA-estimated
        adapt cost (0 when no estimate exists yet)."""
        if req.t_enqueue is None:
            req.t_enqueue = self.clock()
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            req.rejected = True
            est = self._adapt_cost_est_us or 0.0
            req.retry_after_us = math.ceil(
                (len(self._queue) + 1) / self.n_slots) * est
            self.rejections += 1
            return False
        self._queue.append(req)
        return True

    def add_request(self, req: EpisodicRequest) -> bool:
        """Immediate-admission compatibility path: try to place ``req`` in
        a free slot right now; False when all slots are live or the uid is
        already live (re-offer after a step).  ``submit`` + ``step`` is
        the production path."""
        if req.t_enqueue is None:
            req.t_enqueue = self.clock()
        return self._try_admit(req)

    def _try_admit(self, req: EpisodicRequest) -> bool:
        """Admit ``req`` into a free slot.  False defers (no free slot, or
        its uid is already live — the state in flight will be shared, one
        uid is never adapted twice concurrently).  A support-less request
        whose uid is in neither store tier is an error — nothing will
        ever produce its state; a support set exceeding every planned
        bucket is an error at admission, not at dispatch."""
        if self._free_slot() is None:
            return False
        if req.way != self.learner.cfg.way:
            raise ValueError(f"request way={req.way} != learner way="
                             f"{self.learner.cfg.way}")
        if any(s is not None and s.req.uid == req.uid for s in self._slots):
            return False
        if req.support_x is not None:
            n = int(np.asarray(req.support_x).shape[0])
            if n > self.support_buckets[-1]:
                raise ValueError(
                    f"request uid={req.uid}: support size {n} exceeds every "
                    f"planned bucket {self.support_buckets}; re-plan buckets "
                    f"from a fresh stream histogram")
        elif req.uid not in self.store:
            raise ValueError(f"request uid={req.uid}: no cached task state "
                             f"and no support set to adapt on")
        state = self.store.get(req.uid)
        if state is None and req.support_x is None:
            # membership said the state existed, but the read quarantined
            # it (corrupt warm entry discovered at load).  With no support
            # set nothing can ever produce this task's state — terminal
            # failure, not a crash, and the slot stays free.  A request
            # WITH support just falls through to re-adaptation.
            req.failed = True
            req.done = True
            req.t_done = self.clock()
            self.failed_requests += 1
            return True                          # consumed; no slot taken
        req.cache_hit = state is not None
        req.t_admit = self.clock()
        self._slots[self._free_slot()] = _Slot(
            req=req, state=state,
            stream=iter_query_chunks(req.query_x, self.query_chunk))
        return True

    def _admit_from_queue(self) -> None:
        """FIFO admission with head-of-line order (matching the PR3
        run_to_completion loop): the queue head is admitted or everyone
        waits — deterministic, no reordering."""
        while self._queue and self._try_admit(self._queue[0]):
            self._queue.popleft()

    def _earliest_query_deadline_us(self) -> Optional[float]:
        """Earliest SLO deadline over live ADAPTED lanes with queries
        still to serve — the lanes a deferred adapt wave would actually
        help.  Lanes awaiting adaptation are excluded: adaptation is
        their prerequisite, deferring it only hurts them."""
        if self.query_slo_us is None:
            return None
        deadlines = [s.req.t_enqueue * 1e6 + self.query_slo_us
                     for s in self._slots
                     if s is not None and s.state is not None]
        return min(deadlines) if deadlines else None

    def _adapt_wave_preempted(self, now: float) -> bool:
        """The SLO decision: defer the pending adapt wave iff some live
        query lane's deadline is still AHEAD but would be missed by
        waiting out the (estimated) adapt dispatch.  An already-missed
        deadline never preempts — the SLO is blown either way — so adapt
        waves cannot be starved by a permanently-late stream."""
        if self._adapt_cost_est_us is None:
            return False
        dmin = self._earliest_query_deadline_us()
        if dmin is None:
            return False
        now_us = now * 1e6
        return now_us < dmin <= now_us + self._adapt_cost_est_us

    # -- the two batched dispatches ------------------------------------------

    def _adapt_pending(self) -> None:
        """One adapt_batch dispatch per support-bucket group of slots
        awaiting adaptation, each padded to n_slots task lanes.  A task's
        pad cap is chosen by its OWN support size — never by its
        co-tenants' — so the adapted (and stored) state is a pure function
        of (params, support, uid) and co-scheduling stays bit-exact even
        with several planned buckets."""
        need = [i for i, s in enumerate(self._slots)
                if s is not None and s.state is None]
        if not need:
            return
        groups: Dict[int, List[int]] = {}
        for i in need:
            n = int(np.asarray(self._slots[i].req.support_x).shape[0])
            groups.setdefault(bucket_for(n, self.support_buckets),
                              []).append(i)
        for cap, idxs in sorted(groups.items()):
            tasks, uids = [], []
            for i in idxs:
                r = self._slots[i].req
                sx = np.asarray(r.support_x, np.float32)
                # queries ride their own micro-batched dispatch; the
                # collated task carries a 1-row dummy so the adapt shape
                # key is fixed
                tasks.append(Task(
                    support_x=sx,
                    support_y=np.asarray(r.support_y, np.int32),
                    query_x=np.zeros((1,) + sx.shape[1:], np.float32),
                    query_y=np.zeros((1,), np.int32), way=r.way))
                uids.append(r.uid)
            while len(tasks) < self.n_slots:   # fixed task-lane count
                tasks.append(tasks[0])
                uids.append(uids[0])
            batch = collate_task_batch(tasks, support_size=cap, query_size=1)
            keys = jax.vmap(lambda u: task_key(self._base_key, u))(
                jnp.asarray(uids))
            t0 = self.clock()
            states = jax.block_until_ready(
                self._adapt(self._weights, batch, keys))
            t1 = self.clock()
            dt_us = (t1 - t0) * 1e6
            if dt_us > 0:                      # fake clocks may not advance
                self._adapt_cost_est_us = (
                    dt_us if self._adapt_cost_est_us is None
                    else 0.7 * self._adapt_cost_est_us + 0.3 * dt_us)
            for lane, i in enumerate(idxs):
                st = index_task_state(states, lane)
                slot = self._slots[i]
                slot.state = st
                slot.req.t_adapt = t1
                self._adapt_lat_us.append((t1 - slot.req.t_enqueue) * 1e6)
                self.store.put(slot.req.uid, st)
            self.tasks_adapted += len(idxs)

    def _retire(self, i: int) -> None:
        r = self._slots[i].req
        r.done = True
        r.t_done = self.clock()
        self._slots[i] = None

    def _serve_queries(self) -> int:
        """ONE predict_batch dispatch serving the next query chunk of every
        live task; empty lanes carry a filler state and zero queries."""
        lanes = []                               # (slot_idx, chunk, n_real)
        for i, s in enumerate(self._slots):
            if s is None or s.state is None:     # awaiting (deferred) adapt
                continue
            item = next(s.stream, None)
            if item is None:                     # stream exhausted (M == 0)
                self._retire(i)
                continue
            chunk, _, n_real = item
            lanes.append((i, chunk, n_real))
        if not lanes:
            return 0
        chunk_shape = lanes[0][1].shape
        if any(l[1].shape != chunk_shape for l in lanes):
            raise ValueError("live tasks disagree on query trailing shape; "
                             "one engine serves one model input spec")
        qx = np.zeros((self.n_slots,) + chunk_shape, np.float32)
        for lane, (i, chunk, _) in enumerate(lanes):
            qx[lane] = chunk
        cohort = tuple((i, self._slots[i].req.uid) for i, _, _ in lanes)
        if (self._stacked_states is not None
                and self._stacked_states[0] == cohort):
            stacked = self._stacked_states[1]
        else:
            states = [self._slots[i].state for i, _, _ in lanes]
            filler = states[0]                   # well-conditioned pad state
            states.extend([filler] * (self.n_slots - len(lanes)))
            stacked = stack_task_states(states)
            self._stacked_states = (cohort, stacked)
        logits = np.asarray(
            self._predict(self._weights, stacked, jnp.asarray(qx)))
        t_out = self.clock()
        served = 0
        for lane, (i, _, n_real) in enumerate(lanes):
            r = self._slots[i].req
            r.logits.append(logits[lane, :n_real])
            r.served += n_real
            served += n_real
            if r.t_first_logit is None:
                r.t_first_logit = t_out
                self._query_lat_us.append((t_out - r.t_enqueue) * 1e6)
            if r.served >= r.n_queries:
                self._retire(i)
        return served

    def _abandon_hopeless(self) -> None:
        """Deadline abandonment (``deadline_us``): a request whose
        deadline (``t_enqueue + deadline_us``) has passed before its FIRST
        logit is never going to meet it — drop it from the queue, or
        retire its lane if it was admitted but still awaiting (possibly
        SLO-deferred) adaptation, so the capacity goes to requests that
        can still be served in time.  A request already streaming logits
        is past the latency-critical point and runs to completion —
        abandonment never discards produced output."""
        if self.deadline_us is None:
            return
        now_us = self.clock() * 1e6

        def hopeless(r: EpisodicRequest) -> bool:
            return (r.t_first_logit is None
                    and now_us > r.t_enqueue * 1e6 + self.deadline_us)

        kept = collections.deque()
        for r in self._queue:
            if hopeless(r):
                r.abandoned = True
                r.done = True
                r.t_done = now_us / 1e6
                self.deadline_abandoned += 1
            else:
                kept.append(r)
        self._queue = kept
        for i, s in enumerate(self._slots):
            if s is not None and hopeless(s.req):
                s.req.abandoned = True
                self.deadline_abandoned += 1
                self._retire(i)

    def step(self) -> int:
        """One engine step: deadline abandonment first (frees lanes/queue
        slots), then FIFO admission from the queue, then spend the
        step's dispatches — the pending adapt wave first UNLESS the SLO
        scheduler preempts it (a live lane's query deadline would be
        missed waiting out the adapt dispatch), then one micro-batched
        query dispatch.  Returns #queries served."""
        self._abandon_hopeless()
        self._admit_from_queue()
        pending_adapt = any(s is not None and s.state is None
                            for s in self._slots)
        if pending_adapt:
            if self._adapt_wave_preempted(self.clock()):
                self.slo_preemptions += 1
            else:
                self._adapt_pending()
        served = self._serve_queries()
        self.queries_served += served
        self.steps += 1
        return served

    def run_to_completion(self, requests: List[EpisodicRequest],
                          max_steps: int = 100000) -> List[EpisodicRequest]:
        for r in requests:
            self.submit(r)
        steps = 0
        while (self._queue or any(s is not None for s in self._slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return requests

    def drain_unfinished(self) -> List[EpisodicRequest]:
        """Remove and return every request this engine still owes logits —
        live lanes first (slot order == admission order), then the queue
        FIFO — leaving the engine empty.  The replica-failover hook: when
        a replica group dies, the router drains the dead engine and
        re-routes its unfinished requests to the survivors (the warm tier
        makes spilled state rehydratable there; the rest re-adapts)."""
        out: List[EpisodicRequest] = []
        for i, s in enumerate(self._slots):
            if s is not None:
                out.append(s.req)
                self._slots[i] = None
        out.extend(self._queue)
        self._queue.clear()
        self._stacked_states = None
        return out

    @property
    def busy(self) -> bool:
        """True while any request is queued or live in a slot."""
        return bool(self._queue) or any(s is not None for s in self._slots)

    # -- observability -------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        """Counters plus exact nearest-rank latency percentiles (µs).
        ``adapt_p*_us`` is enqueue → adapted state ready (cold requests
        only); ``query_p*_us`` is enqueue → first logit; both computed
        from the injected clock.  ``cache_*``/``hit_rate`` are the L1's;
        ``spills``/``rehydrates`` count warm-tier traffic.

        Degradation counters: ``quarantined`` (corrupt/vanished warm
        entries moved aside), ``spill_errors`` (warm writes that failed —
        >0 means the store degraded to L1-only), ``rejections``
        (bounded-queue backpressure refusals), ``deadline_abandoned``
        (requests dropped past their deadline pre-first-logit),
        ``failed_requests`` (support-less requests whose only stored
        state was quarantined)."""
        l1 = self.store.l1
        lookups = l1.hits + l1.misses
        return dict(
            tasks_adapted=self.tasks_adapted,
            queries_served=self.queries_served,
            steps=self.steps,
            queue_depth=len(self._queue),
            cache_hits=l1.hits,
            cache_misses=l1.misses,
            hit_rate=l1.hits / lookups if lookups else 0.0,
            evictions=l1.evictions,
            overwrites=l1.overwrites,
            spills=self.store.spills,
            rehydrates=self.store.rehydrates,
            rescan_hits=self.store.rescan_hits,
            quarantined=self.store.quarantined,
            spill_errors=self.store.spill_errors,
            rejections=self.rejections,
            deadline_abandoned=self.deadline_abandoned,
            failed_requests=self.failed_requests,
            slo_preemptions=self.slo_preemptions,
            adapt_cost_est_us=(self._adapt_cost_est_us
                               if self._adapt_cost_est_us is not None
                               else 0.0),
            adapt_p50_us=_pctl(self._adapt_lat_us, 50),
            adapt_p99_us=_pctl(self._adapt_lat_us, 99),
            query_p50_us=_pctl(self._query_lat_us, 50),
            query_p99_us=_pctl(self._query_lat_us, 99),
            adapt_compiles=self._adapt.compile_count,
            predict_compiles=self._predict.compile_count,
            # measured resident parameter bytes (host accounting over the
            # stored arrays; int8 engines count q+scale, not f32)
            param_bytes_resident=self._param_bytes["resident_bytes"],
            param_bytes_fp32=self._param_bytes["fp32_bytes"],
            frozen_param_bytes_resident=(
                self._param_bytes["frozen_resident_bytes"]),
            frozen_param_bytes_fp32=self._param_bytes["frozen_fp32_bytes"],
        )
