"""Weight-stationary int8 serving path (quant marker, tier 1).

Covers the three tentpole pieces end to end:

  * ``repro.serve.quant_params``: the frozen slice of each learner kind
    quantizes into the blockwise int8 ``{q, scale, n}`` form, dequantizes
    lazily in-jit, and the measured resident frozen-slice bytes shrink
    >=3x at the launcher's backbone widths;
  * the ``int8_matmul`` kernel dispatch site: Pallas (interpret mode on
    CPU) vs the dequantize-then-dot oracle, all backends, under vmap/jit;
  * fp32-vs-int8 SERVING equivalence per kind through the real engine:
    logit tolerance, >=99% argmax agreement (fomaml bit-identical — its
    frozen slice is empty), and compile-counter flatness across the
    quant flag;
  * the durable warm tier: spilled task states survive an engine restart
    (fresh ``WarmTaskStore`` over the same directory) bit-exactly, and
    quarantine drops the sidecar so restart cannot resurrect a corrupt
    entry;
  * the serving layout chooser on 4 emulated devices: the chosen
    weight-stationary placement moves strictly fewer wire bytes per
    compiled predict step than the training placement.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.episodic_train import task_key
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearnerConfig, make_learner
from repro.core.set_encoder import SetEncoderConfig
from repro.data.episodic import EpisodicImageConfig, sample_image_task
from repro.kernels import dispatch
from repro.kernels.int8_matmul import int8_matmul as pallas_int8_matmul
from repro.models.conv_backbone import ConvBackboneConfig, make_conv_backbone
from repro.optim.quant import dequantize, quantize
from repro.serve.episodic import (EpisodicRequest, EpisodicServeEngine,
                                  WarmTaskStore)
from repro.serve.quant_params import (FROZEN_SLICES, ServingWeights,
                                      dequantize_params, is_quantized_leaf,
                                      param_bytes, quantize_frozen)

pytestmark = pytest.mark.quant

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

# the launcher's episodic backbone: realistic widths so the per-block
# scale overhead (4 bytes per 128-block) does not mask the int8 win
BB = make_conv_backbone(ConvBackboneConfig(widths=(16, 32), feature_dim=64))
SET_CFG = SetEncoderConfig(kind="conv", conv_blocks=2, conv_width=16,
                           task_dim=32)
WAY = 3
KINDS = ["protonets", "cnaps", "simple_cnaps", "fomaml", "finetuner"]
LITE = LiteSpec(exact=True, chunk_size=8)


def _learner(kind):
    # film_init_std=0.02: near-identity FiLM modulation at init (the
    # CNAPs-paper initialization).  A LARGE random FiLM generator is an
    # amplifier with no trained structure — int8 backbone noise perturbs
    # the task embedding, which perturbs every query feature through a
    # random map — and that worst case is not what serving quantizes.
    return make_learner(MetaLearnerConfig(kind=kind, way=WAY, inner_steps=2,
                                          film_init_std=0.02), BB, SET_CFG)


def _tasks(n, shot=10, q=8, seed=100):
    # class-separable tasks (class_sep/noise flipped from the training
    # defaults): argmax agreement is measured on decisions the fp32 model
    # actually makes, not on coin-flip queries of an unseparable task
    return [sample_image_task(
        jax.random.key(seed + i),
        EpisodicImageConfig(way=WAY, shot=shot, query_per_class=q,
                            image_size=8, class_sep=2.0, noise=0.5))
            for i in range(n)]


def _serve(lr, params, tasks, **engine_kw):
    eng = EpisodicServeEngine(lr, params, lite=LITE, n_slots=2,
                              query_chunk=8, support_buckets=(32,),
                              **engine_kw)
    reqs = [EpisodicRequest(uid=i, support_x=np.asarray(t.support_x),
                            support_y=np.asarray(t.support_y),
                            query_x=np.asarray(t.query_x), way=WAY)
            for i, t in enumerate(tasks)]
    eng.run_to_completion(reqs)
    return np.concatenate([r.all_logits() for r in reqs]), eng


# ---------------------------------------------------------------------------
# quantize_frozen / dequantize_params / param_bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_quantize_frozen_slices_per_kind(kind, key):
    """Only the kind's frozen roots quantize; live tensors stay fp32;
    fomaml (empty frozen slice) degrades to mode='none'."""
    lr = _learner(kind)
    params = lr.init(key)
    sw = quantize_frozen(lr, params, "int8")
    roots = FROZEN_SLICES[kind]
    if not roots:                            # fomaml: principled no-op
        assert sw.mode == "none" and sw.tree is params
        assert sw.quant_paths == ()
        return
    assert sw.mode == "int8" and len(sw.quant_paths) > 0
    for p in sw.quant_paths:
        assert p.split("/", 1)[0] in roots
    # the conv backbone's head matmul is a native int8 site
    assert any(p.endswith("head/w") for p in sw.native_paths)
    # every live (non-frozen) float leaf is untouched fp32
    flat, _ = jax.tree_util.tree_flatten_with_path(
        sw.tree, is_leaf=is_quantized_leaf)
    for path, leaf in flat:
        root = str(getattr(path[0], "key", path[0]))
        if root not in roots:
            assert not is_quantized_leaf(leaf)


def test_dequantize_params_error_bounded_and_native_leaves_stay_int8(key):
    lr = _learner("protonets")
    params = lr.init(key)
    sw = quantize_frozen(lr, params, "int8")
    deq = dequantize_params(sw)
    # native-path leaves remain quantized dicts for the kernel site
    for p in sw.native_paths:
        node = deq
        for k in p.split("/"):
            node = node[k]
        assert is_quantized_leaf(node)
    # every dequantized frozen leaf is within its own block scale
    flat_o = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            deq, is_leaf=is_quantized_leaf)[0]:
        if is_quantized_leaf(leaf) or path not in flat_o:
            continue
        orig = flat_o[path]
        if orig.shape == leaf.shape and np.any(
                np.asarray(orig) != np.asarray(leaf)):
            err = float(jnp.max(jnp.abs(orig - leaf)))
            assert err <= float(jnp.max(jnp.abs(orig))) / 127.0 + 1e-7


def test_mode_none_is_passthrough_and_bad_mode_raises(key):
    lr = _learner("cnaps")
    params = lr.init(key)
    sw = quantize_frozen(lr, params, "none")
    assert sw.tree is params and sw.mode == "none"
    assert dequantize_params(sw) is params
    with pytest.raises(ValueError, match="serve_quant"):
        quantize_frozen(lr, params, "int4")


def test_serving_weights_is_a_pytree_with_static_aux(key):
    """ServingWeights flows through jit; int8-vs-none trees can never
    collide on a compile-cache entry (aux differs)."""
    lr = _learner("protonets")
    params = lr.init(key)
    a = quantize_frozen(lr, params, "int8")
    b = quantize_frozen(lr, params, "none")
    assert (jax.tree_util.tree_structure(a) !=
            jax.tree_util.tree_structure(b))
    out = jax.jit(lambda sw: jax.tree.reduce(
        lambda x, y: x + jnp.sum(jnp.abs(y).astype(jnp.float32)),
        sw, 0.0))(a)
    assert np.isfinite(float(out))


def test_frozen_resident_bytes_shrink_3x(key):
    """Acceptance: >=3x measured reduction of the resident frozen slice
    at the launcher's widths (the per-block scale overhead is real and
    included — this is accounting over the stored arrays)."""
    lr = _learner("protonets")
    params = lr.init(key)
    b_none = param_bytes(quantize_frozen(lr, params, "none"))
    b_int8 = param_bytes(quantize_frozen(lr, params, "int8"))
    assert b_none["frozen_resident_bytes"] == b_none["frozen_fp32_bytes"]
    ratio = (b_none["frozen_resident_bytes"] /
             b_int8["frozen_resident_bytes"])
    assert ratio >= 3.0, ratio
    # live tensors are identical either way
    live_none = b_none["resident_bytes"] - b_none["frozen_resident_bytes"]
    live_int8 = b_int8["resident_bytes"] - b_int8["frozen_resident_bytes"]
    assert live_none == live_int8


# ---------------------------------------------------------------------------
# int8 matmul kernel: pallas (interpret) vs oracle, all backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(1, 32, 64), (8, 64, 64), (5, 130, 257),
                                   (128, 256, 128)])
def test_int8_matmul_pallas_matches_oracle(m, k, n, key):
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (k, n), jnp.float32)
    qs = quantize(w)
    want = x @ dequantize(qs)
    got = pallas_int8_matmul(x, qs["q"], qs["scale"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("backend", ["naive", "ref", "pallas"])
def test_int8_matmul_dispatch_backends_agree(backend, key):
    x = jax.random.normal(key, (6, 96), jnp.float32)
    qs = quantize(jax.random.normal(jax.random.key(2), (96, 40), jnp.float32))
    with dispatch.use_backend("ref"):
        want = dispatch.int8_matmul(x, qs)
    with dispatch.use_backend(backend):
        got = dispatch.int8_matmul(x, qs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert got.shape == (6, 40)


def test_int8_matmul_handles_leading_dims_and_jit(key):
    """The dispatch wrapper flattens (T, B, k) activations — the shape the
    batched predict path feeds — identically under jit and vmap."""
    x = jax.random.normal(key, (3, 4, 64), jnp.float32)
    qs = quantize(jax.random.normal(jax.random.key(3), (64, 16), jnp.float32))
    with dispatch.use_backend("pallas"):
        got = jax.jit(lambda a, b: dispatch.int8_matmul(a, b))(x, qs)
        vm = jax.vmap(lambda a: dispatch.int8_matmul(a, qs))(x)
    with dispatch.use_backend("ref"):
        want = dispatch.int8_matmul(x, qs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(vm), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# fp32-vs-int8 serving equivalence through the engine, per kind
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_engine_int8_matches_fp32_serving(kind, key):
    """Acceptance: per-kind logit closeness and >=99% argmax agreement
    between a fp32 engine and an int8 engine over the same traffic;
    fomaml is bit-identical (empty frozen slice); compile counters are
    IDENTICAL across the quant flag (same dispatch paths, same buckets).
    """
    lr = _learner(kind)
    params = lr.init(key)
    tasks = _tasks(8)
    lf, ef = _serve(lr, params, tasks, serve_quant="none")
    lq, eq = _serve(lr, params, tasks, serve_quant="int8")
    sf, sq = ef.stats(), eq.stats()
    assert (sf["adapt_compiles"], sf["predict_compiles"]) == \
           (sq["adapt_compiles"], sq["predict_compiles"])
    if kind == "fomaml":
        np.testing.assert_array_equal(lf, lq)
        assert sq["param_bytes_resident"] == sf["param_bytes_resident"]
        return
    agree = float((lf.argmax(-1) == lq.argmax(-1)).mean())
    assert agree >= 0.99, (kind, agree)
    # logits move only by the feature perturbation scale, not wildly:
    # normalize per-row (cnaps-family scores are unnormalized distances)
    denom = np.maximum(np.abs(lf).max(-1, keepdims=True), 1.0)
    rel = np.abs(lf - lq) / denom
    assert float(np.median(rel)) < 0.1, (kind, float(np.median(rel)))
    # and the int8 engine actually holds fewer resident weight bytes
    assert (sq["frozen_param_bytes_resident"] * 3 <=
            sf["frozen_param_bytes_resident"])


def test_engine_stats_report_resident_bytes(key):
    lr = _learner("protonets")
    params = lr.init(key)
    _, eng = _serve(lr, params, _tasks(1), serve_quant="int8")
    s = eng.stats()
    assert s["param_bytes_fp32"] > s["param_bytes_resident"]
    assert s["frozen_param_bytes_fp32"] == 28800      # widths (16,32), f64
    assert s["frozen_param_bytes_resident"] * 3 <= s["frozen_param_bytes_fp32"]


# ---------------------------------------------------------------------------
# durable warm tier: restart rehydration
# ---------------------------------------------------------------------------


@pytest.mark.serve
@pytest.mark.parametrize("kind", KINDS)
def test_warm_tier_survives_restart_bitexact(kind, key, tmp_path):
    """A fresh WarmTaskStore over the same directory (engine restart)
    rescans the template sidecars and serves every spilled uid bit-exactly
    — for every learner kind's state pytree."""
    lr = _learner(kind)
    params = lr.init(key)
    t = _tasks(1)[0]
    st = lr.adapt(params, t.support_x, t.support_y, key=task_key(key, 0),
                  lite=LITE)
    store = WarmTaskStore(tmp_path)
    store.put(0, st)
    del store

    fresh = WarmTaskStore(tmp_path)          # the restart
    assert fresh.template_restores == 1
    back = fresh.get(0)
    assert back is not None
    assert jax.tree.structure(back) == jax.tree.structure(st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.serve
def test_warm_tier_restart_skips_quarantined_and_orphan_entries(key, tmp_path):
    """Quarantine drops the sidecar (restart cannot resurrect a corrupt
    uid); an orphan npz without a sidecar (crash between the two writes)
    is simply not listed; an unreadable sidecar is dropped, not fatal."""
    lr = _learner("protonets")
    params = lr.init(key)
    t = _tasks(1)[0]
    st = lr.adapt(params, t.support_x, t.support_y, key=task_key(key, 0),
                  lite=LITE)
    store = WarmTaskStore(tmp_path)
    for uid in (0, 1, 2):
        store.put(uid, st)
    # corrupt uid 0 and trigger quarantine in the FIRST store
    with open(tmp_path / "uid_0.npz", "r+b") as f:
        f.truncate(10)
    assert store.get(0) is None and store.quarantined == 1
    assert not (tmp_path / "uid_0.tmpl.pkl").exists()
    # orphan: uid 1's sidecar lost (simulates crash between npz + sidecar)
    (tmp_path / "uid_1.tmpl.pkl").unlink()
    # unreadable sidecar for a uid with no payload at all
    (tmp_path / "uid_9.tmpl.pkl").write_bytes(b"not a pickle")

    fresh = WarmTaskStore(tmp_path)
    assert fresh.template_restores == 1      # only uid 2 survives
    assert fresh.get(2) is not None
    assert fresh.get(0) is None and fresh.get(1) is None
    assert not (tmp_path / "uid_9.tmpl.pkl").exists()


# ---------------------------------------------------------------------------
# layout chooser: wire guard on 4 emulated devices
# ---------------------------------------------------------------------------


_LAYOUT_CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.episodic_train import task_key
    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,
                                     sample_image_task)
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.roofline.analysis import choose_serving_layout
    from repro.serve.quant_params import dequantize_params, quantize_frozen

    BB = make_conv_backbone(ConvBackboneConfig(widths=(16, 32),
                                               feature_dim=64))
    SET_CFG = SetEncoderConfig(kind="conv", conv_blocks=2, conv_width=16,
                               task_dim=32)
    lr = make_learner(MetaLearnerConfig(kind="protonets", way=3), BB, SET_CFG)
    params = lr.init(jax.random.key(0))
    sw = quantize_frozen(lr, params, "int8")
    mesh = jax.make_mesh((4,), ("serve",))
    ts = [sample_image_task(jax.random.key(100 + i),
          EpisodicImageConfig(way=3, shot=5, query_per_class=4, image_size=8))
          for i in range(2)]
    batch = collate_task_batch(ts, support_size=16, query_size=12)
    keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(jnp.arange(2))
    lite = LiteSpec(exact=True, chunk_size=8)
    states = lr.adapt_batch(dequantize_params(sw), batch, keys, lite)

    pick = choose_serving_layout(
        lambda w, st, qx: lr.predict_batch(dequantize_params(w), st, qx),
        sw, (states, batch.query_x), mesh)
    rows = pick["rows"]
    ws, tr = rows["weight_stationary"], rows["training"]
    # acceptance guard: weight-stationary moves STRICTLY less wire than
    # the training placement at serving batch sizes
    assert ws["wire_bytes"] < tr["wire_bytes"], (ws, tr)
    assert ws["wire_bytes"] > 0                 # it is not the replicated row
    assert rows["replicated"]["wire_bytes"] == 0
    # for this weights-dominated predict step the chooser picks it too
    assert pick["choice"] == "weight_stationary", pick["choice"]
    print("WIRE", int(tr["wire_bytes"]), int(ws["wire_bytes"]))
""")


@pytest.mark.serve
def test_weight_stationary_moves_less_wire_than_training():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", _LAYOUT_CODE],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    tr, ws = [int(v) for v in out.stdout.split("WIRE", 1)[1].split()[:2]]
    assert ws < tr


def test_layout_scorer_imports_no_model_stack():
    code = ("import sys, repro.roofline.analysis; print(sorted(m for m in "
            "sys.modules if m.startswith(('repro.configs', 'repro.launch', "
            "'repro.models'))))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=dict(
                             os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == "[]", out.stdout
