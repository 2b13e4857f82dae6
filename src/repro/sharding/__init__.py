"""SPMD distribution layer: mesh builders, partition rules, constraints.

Also re-exports ``shard_map``: all repo code and test snippets import it
from here, the one import site ``repro.lint``'s ``jax-api-drift`` rule
enforces, so the next upstream move or keyword rename (``check_rep``
became ``check_vma``) is absorbed in one place.

Two-level DP mesh contract (the task-batched meta-training engine,
``repro.core.episodic_train.make_batched_meta_train_step``):

* ``repro.launch.mesh.make_two_level_dp_mesh(dcn, dp)`` builds a
  ``(dcn_axis='dcn', dp_axis='data')`` mesh — the outer ``dcn`` axis is
  the slow cross-host DCN domain (rows align with hosts because
  ``jax.devices()`` orders devices process-major), the inner ``data``
  axis is the fast per-host ICI domain.
* The task axis of a ``TaskBatch`` shards over BOTH axes,
  ``P(('dcn', 'data'))``; params and optimizer state are replicated
  (``P()``), except the compressed-reduction error-feedback residual
  ``opt_state['ef']`` whose leading axis shards ``P('dcn')`` (one
  residual per host; checkpointed like any other opt-state leaf).
* Gradients ``pmean`` first over ``data`` (cheap, per host), then reduce
  once over ``dcn`` — exact ``pmean`` or error-feedback
  ``compressed_psum`` (``repro.optim.compress``).  With ``accum_steps``
  the per-shard tasks are scanned in chunks BEFORE the reduction, so the
  collective count per optimizer step never grows.
* At ``dcn`` size 1 the pmean path skips the dcn reduction, so the
  engine is bit-identical to the 1-D ``make_dp_mesh`` path (tested in
  tests/test_multihost.py).  Per-step collective wire bytes are
  accounted by ``repro.roofline.hlo.collectives_report`` (its wire test there).

Replica-serving mesh contract (the multi-replica episodic engine,
``repro.serve.replica.ReplicatedServeEngine``):

* ``repro.launch.mesh.make_replica_mesh(replicas, devices_per_replica)``
  builds ``replicas`` DISJOINT 1-D ``('serve',)`` meshes over contiguous
  device groups (process-major, so groups align with hosts).  Each
  replica engine compiles and places its serving weights on its OWN group
  mesh — the compiled program cannot name a device outside the group, so
  every predict-step collective is intra-group by construction and
  per-step wire bytes scale with ``devices_per_replica``, never with the
  deployment size (asserted via ``collectives_report`` in
  tests/test_replica.py).
* Work is partitioned ACROSS groups by data, not by tensor: requests
  route by stable uid hash (``repro.serve.episodic.stable_uid_hash``), so
  the task population — the paper's scaling axis at serving time — splits
  across replicas while weights are simply replicated per group (the
  serving-group discipline of scaling_transformer_inference_efficiency).
* The shared warm tier partitions by the SAME hash into a fixed number of
  shard subdirs independent of the replica count: any replica can locate
  any uid's spilled state (failover rehydration), and resizing the
  deployment re-routes uids without moving their files.
"""

from jax import shard_map

__all__ = ["shard_map"]
