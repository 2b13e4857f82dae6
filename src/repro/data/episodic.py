"""Synthetic episodic task generators (ORBIT / VTAB+MD stand-ins — the
real datasets are unavailable offline).

Image tasks: each class is a Gaussian blob in pixel space with a class-
specific low-frequency pattern — linearly separable enough that accuracy
trends (flat-in-|H|, LITE > subsampled-task) are measurable in minutes on
CPU, yet non-trivial for a conv net from scratch.  Two sources: the jitted
on-device sampler (``task_batch_at``) and a host-side numpy twin
(``host_task_batch_at``) whose collation/augmentation a
``repro.train.pipeline.Prefetcher`` can overlap with device compute.

Shape bucketing: ``plan_buckets`` turns a stream histogram of task sizes
into <= ``max_buckets`` pad targets and ``collate_with_buckets`` collates
against them, so ragged streams hit a small closed set of compiled shapes
(paired with ``repro.train.pipeline.BucketedStepCache``).

Token tasks: each class is a distinct unigram distribution over the vocab;
sequences sample iid from it.  Used by the episodic-LM integration.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.episodic import Task, TaskBatch


@dataclasses.dataclass(frozen=True)
class EpisodicImageConfig:
    way: int = 5
    shot: int = 10                   # support examples per class
    query_per_class: int = 10
    image_size: int = 32
    channels: int = 3
    class_sep: float = 0.5           # distance between class means
    noise: float = 1.5


def sample_image_task(key: jax.Array, cfg: EpisodicImageConfig) -> Task:
    km, ks, kq, kp = jax.random.split(key, 4)
    h = w = cfg.image_size
    # class prototype pattern: low-freq random image per class
    base = jax.random.normal(kp, (cfg.way, h // 4, w // 4, cfg.channels))
    base = jax.image.resize(base, (cfg.way, h, w, cfg.channels), "linear")
    base = cfg.class_sep * base / jnp.sqrt(jnp.mean(base ** 2) + 1e-8)

    def draw(k, per_class):
        noise = cfg.noise * jax.random.normal(
            k, (cfg.way, per_class, h, w, cfg.channels))
        x = base[:, None] + noise
        y = jnp.repeat(jnp.arange(cfg.way), per_class)
        return x.reshape(-1, h, w, cfg.channels), y

    sx, sy = draw(ks, cfg.shot)
    qx, qy = draw(kq, cfg.query_per_class)
    perm = jax.random.permutation(km, sx.shape[0])
    return Task(support_x=sx[perm], support_y=sy[perm],
                query_x=qx, query_y=qy, way=cfg.way)


def image_task_stream(key: jax.Array, cfg: EpisodicImageConfig) -> Iterator[Task]:
    while True:
        key, sub = jax.random.split(key)
        yield sample_image_task(sub, cfg)


# ---------------------------------------------------------------------------
# Task-batch collation (the task-batched engine's input side)
# ---------------------------------------------------------------------------


def bucket_size(n: int, multiple: int = 8) -> int:
    """Round n up to the next bucket boundary.  Bucketing the pad targets
    keeps the number of distinct compiled shapes small when task sizes vary
    stream-to-stream (each (support, query) bucket pair is one XLA program)."""
    return max(((n + multiple - 1) // multiple) * multiple, multiple)


def plan_buckets(sizes: Sequence[int], max_buckets: int = 4,
                 multiple: int = 8) -> Tuple[int, ...]:
    """Choose at most ``max_buckets`` pad targets from a stream histogram.

    Every observed size rounds up (``bucket_size``) into a candidate cap;
    candidates are then greedily merged — always absorbing the cap whose
    removal adds the least total padding, weighted by how many stream
    elements land in it — until at most ``max_buckets`` remain.  The
    returned caps are ascending, cover ``max(sizes)``, and each is a
    multiple of ``multiple``, so a ragged stream collated against them
    produces a bounded set of compiled shapes with near-minimal padding
    waste for the observed distribution.
    """
    if not sizes:
        raise ValueError("plan_buckets needs a non-empty size histogram")
    if max_buckets < 1:
        raise ValueError(f"max_buckets={max_buckets} must be >= 1")
    hist: dict = {}
    for s in sizes:
        cap = bucket_size(s, multiple)
        hist[cap] = hist.get(cap, 0) + 1
    caps = sorted(hist)
    counts = [hist[c] for c in caps]
    while len(caps) > max_buckets:
        # merging cap i into cap i+1 pads each of its count_i elements by
        # at most (caps[i+1] - caps[i]) extra rows
        costs = [(caps[i + 1] - caps[i]) * counts[i]
                 for i in range(len(caps) - 1)]
        i = costs.index(min(costs))
        counts[i + 1] += counts[i]
        del caps[i], counts[i]
    return tuple(caps)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest planned bucket that fits ``n``.  Overflow raises (same
    explicit-contract behavior as ``collate_task_batch`` with a fixed
    size): a stream element larger than every planned cap means the
    histogram the plan was built from is stale — recompute the plan rather
    than silently minting a new compiled shape."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"size {n} exceeds every planned bucket {tuple(buckets)}; "
                     f"re-plan buckets from a fresh stream histogram")


def collate_with_buckets(tasks: Sequence[Task],
                         support_buckets: Sequence[int],
                         query_buckets: Sequence[int]) -> TaskBatch:
    """Collate against planned buckets: pad targets are the smallest
    support/query caps covering the batch maxima, so every batch from the
    stream lands on one of ``len(support_buckets) * len(query_buckets)``
    compiled shapes."""
    return collate_task_batch(
        tasks,
        support_size=bucket_for(max(t.n_support for t in tasks),
                                support_buckets),
        query_size=bucket_for(max(t.n_query for t in tasks), query_buckets))


def collate_task_batch(tasks: Sequence[Task],
                       support_size: Optional[int] = None,
                       query_size: Optional[int] = None,
                       bucket_multiple: int = 0) -> TaskBatch:
    """Stack ragged tasks into one static-shape :class:`TaskBatch`.

    Support/query sets are right-padded to a common length (the batch max,
    an explicit ``support_size``/``query_size``, or the batch max rounded to
    ``bucket_multiple``) and validity masks record which rows are real.
    Padded support labels are -1 — the zero row of ``one_hot`` — so class
    sums/counts never see them; padded query labels are 0 and only the mask
    keeps them out of the loss.  All tasks must share ``way``.
    """
    if not tasks:
        raise ValueError("collate_task_batch needs at least one task")
    way = tasks[0].way
    if any(t.way != way for t in tasks):
        raise ValueError("all tasks in a batch must share `way`")

    # An explicit support_size/query_size is a fixed-compiled-shape
    # contract: it is used EXACTLY, and tasks that overflow it raise rather
    # than silently emitting a new shape.  Without one, the pad target is
    # the batch max, optionally rounded up to bucket_multiple.
    def target(actual: int, explicit: Optional[int], kind: str) -> int:
        if explicit is not None:
            if actual > explicit:
                raise ValueError(f"task {kind} size {actual} exceeds bucket "
                                 f"{kind}_size={explicit}")
            return explicit
        return bucket_size(actual, bucket_multiple) if bucket_multiple else actual

    n = target(max(t.n_support for t in tasks), support_size, "support")
    m = target(max(t.n_query for t in tasks), query_size, "query")

    def pad_rows(a: np.ndarray, rows: int, fill) -> np.ndarray:
        a = np.asarray(a)
        cfg = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, cfg, constant_values=fill)

    def mask_rows(real: int, rows: int) -> np.ndarray:
        return (np.arange(rows) < real).astype(np.float32)

    return TaskBatch(
        support_x=jnp.asarray(np.stack(
            [pad_rows(t.support_x, n, 0) for t in tasks])),
        support_y=jnp.asarray(np.stack(
            [pad_rows(t.support_y, n, -1) for t in tasks])),
        support_mask=jnp.asarray(np.stack(
            [mask_rows(t.n_support, n) for t in tasks])),
        query_x=jnp.asarray(np.stack(
            [pad_rows(t.query_x, m, 0) for t in tasks])),
        query_y=jnp.asarray(np.stack(
            [pad_rows(t.query_y, m, 0) for t in tasks])),
        query_mask=jnp.asarray(np.stack(
            [mask_rows(t.n_query, m) for t in tasks])),
        way=way,
    )


def iter_query_chunks(query_x: np.ndarray, chunk: int
                      ) -> Iterator[Tuple[np.ndarray, np.ndarray, int]]:
    """Split a request's query stream into fixed-shape ``(chunk, ...)``
    pieces: yields ``(padded_chunk, mask, n_real)`` per piece, tail
    zero-padded with a float32 validity mask.  The serve-side twin of
    :func:`repro.core.episodic.query_batches` (which is device-side for
    Algorithm 1's training loop): host numpy, streamed lazily, so the
    episodic serving engine's micro-batcher pulls one fixed-shape piece per
    live task per step and every ``predict_batch`` dispatch lands on one
    compiled shape."""
    if chunk < 1:
        raise ValueError(f"query chunk must be >= 1, got {chunk}")
    q = np.asarray(query_x)
    for s in range(0, q.shape[0], chunk):
        piece = q[s:s + chunk]
        n = piece.shape[0]
        if n < chunk:
            piece = np.pad(piece,
                           [(0, chunk - n)] + [(0, 0)] * (piece.ndim - 1))
        yield piece, (np.arange(chunk) < n).astype(np.float32), n


@functools.partial(jax.jit, static_argnums=(1, 2))
def sample_image_task_batch(key: jax.Array, cfg: EpisodicImageConfig,
                            num_tasks: int) -> TaskBatch:
    """vmapped synthetic sampler: num_tasks equally-shaped tasks as one
    TaskBatch (all-ones masks — no padding needed on the synthetic stream).
    Jitted (cfg/num_tasks static), so per-step data generation compiles
    once instead of re-tracing op-by-op in the training loop."""
    tasks = jax.vmap(lambda k: sample_image_task(k, cfg))(
        jax.random.split(key, num_tasks))
    ones = lambda a: jnp.ones(a.shape[:2], jnp.float32)
    return TaskBatch(support_x=tasks.support_x, support_y=tasks.support_y,
                     query_x=tasks.query_x, query_y=tasks.query_y,
                     support_mask=ones(tasks.support_y),
                     query_mask=ones(tasks.query_y), way=cfg.way)


def task_batch_at(key: jax.Array, cfg: EpisodicImageConfig,
                  tasks_per_step: int, step: int) -> TaskBatch:
    """Deterministic batch-for-step: a pure function of (key, cfg, step) —
    the contract repro.train.loop relies on for checkpoint-exact restarts."""
    return sample_image_task_batch(jax.random.fold_in(key, step), cfg,
                                   tasks_per_step)


# ---------------------------------------------------------------------------
# Host-side task source (the production-loader stand-in)
# ---------------------------------------------------------------------------
#
# Real episodic datasets (ORBIT video frames, VTAB images) are decoded,
# augmented, and collated on the HOST.  ``host_task_batch_at`` is the numpy
# twin of the device-side synthetic sampler: same class-blob task family,
# but all work runs in plain numpy (large GIL-releasing ops) so a
# :class:`repro.train.pipeline.Prefetcher` can overlap it with device
# compute — the device-side ``task_batch_at`` serializes with the train
# step on the accelerator queue and has nothing to overlap.


@dataclasses.dataclass(frozen=True)
class HostEpisodicConfig:
    """Host (numpy) episodic image stream.  ``augment`` adds the standard
    loader work — random crop (from ``image_size + crop_pad``), horizontal
    flip, per-image standardization — all vectorized over the batch."""

    way: int = 5
    shot: int = 10
    query_per_class: int = 10
    image_size: int = 32
    channels: int = 3
    class_sep: float = 0.5
    noise: float = 1.5
    augment: bool = True
    crop_pad: int = 4


def host_task_batch_at(seed: int, cfg: HostEpisodicConfig,
                       tasks_per_step: int, step: int) -> TaskBatch:
    """Deterministic host-side batch-for-step: a pure function of
    (seed, cfg, step) — the same restart-exactness contract as
    ``task_batch_at``, built on a counter-based PRNG
    (``np.random.SeedSequence([seed, step])``) so any step is
    reconstructible in isolation."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, step])))
    t, way, c = tasks_per_step, cfg.way, cfg.channels
    per = cfg.shot + cfg.query_per_class
    big = cfg.image_size + (cfg.crop_pad if cfg.augment else 0)
    # class prototype: low-freq pattern upsampled 2x (numpy nearest);
    # built at ceil(big/2) and cropped so odd sizes work too
    base = rng.standard_normal(
        (t, way, (big + 1) // 2, (big + 1) // 2, c)).astype(np.float32)
    base = base.repeat(2, axis=2).repeat(2, axis=3)[:, :, :big, :big]
    base *= cfg.class_sep / np.sqrt((base ** 2).mean() + 1e-8)
    noise = cfg.noise * rng.standard_normal(
        (t, way, per, big, big, c)).astype(np.float32)
    x = (base[:, :, None] + noise).reshape(t * way * per, big, big, c)
    if cfg.augment:
        m, img = x.shape[0], cfg.image_size
        oy = rng.integers(0, cfg.crop_pad + 1, m)
        ox = rng.integers(0, cfg.crop_pad + 1, m)
        iy = oy[:, None] + np.arange(img)
        ix = ox[:, None] + np.arange(img)
        x = x[np.arange(m)[:, None, None], iy[:, :, None], ix[:, None, :]]
        flip = rng.integers(0, 2, m).astype(bool)
        x[flip] = x[flip, :, ::-1]
        mu = x.mean(axis=(1, 2), keepdims=True)
        sd = x.std(axis=(1, 2), keepdims=True) + 1e-6
        x = (x - mu) / sd
    img = cfg.image_size
    x = x.reshape(t, way, per, img, img, c)
    sx = np.ascontiguousarray(
        x[:, :, :cfg.shot].reshape(t, way * cfg.shot, img, img, c))
    qx = np.ascontiguousarray(
        x[:, :, cfg.shot:].reshape(t, way * cfg.query_per_class, img, img, c))
    sy = np.tile(np.repeat(np.arange(way), cfg.shot), (t, 1)).astype(np.int32)
    qy = np.tile(np.repeat(np.arange(way), cfg.query_per_class),
                 (t, 1)).astype(np.int32)
    ones = lambda y: np.ones(y.shape, np.float32)
    return TaskBatch(support_x=sx, support_y=sy, query_x=qx, query_y=qy,
                     support_mask=ones(sy), query_mask=ones(qy), way=way)


@dataclasses.dataclass(frozen=True)
class EpisodicTokenConfig:
    way: int = 5
    shot: int = 8
    query_per_class: int = 8
    seq_len: int = 64
    vocab: int = 256
    concentration: float = 0.3       # lower = more distinct class unigrams


def sample_token_task(key: jax.Array, cfg: EpisodicTokenConfig) -> Task:
    kd, ks, kq, km = jax.random.split(key, 4)
    logits = jax.random.normal(kd, (cfg.way, cfg.vocab)) / cfg.concentration

    def draw(k, per_class):
        keys = jax.random.split(k, cfg.way)
        xs = jnp.stack([
            jax.random.categorical(kk, logits[c], shape=(per_class, cfg.seq_len))
            for c, kk in enumerate(keys)])
        y = jnp.repeat(jnp.arange(cfg.way), per_class)
        return xs.reshape(-1, cfg.seq_len).astype(jnp.int32), y

    sx, sy = draw(ks, cfg.shot)
    qx, qy = draw(kq, cfg.query_per_class)
    perm = jax.random.permutation(km, sx.shape[0])
    return Task(support_x=sx[perm], support_y=sy[perm],
                query_x=qx, query_y=qy, way=cfg.way)
