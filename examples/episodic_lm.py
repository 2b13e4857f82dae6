"""The paper's technique as a first-class LM-framework feature: episodic
meta-training (ProtoNets + LITE) wrapped around an assigned LM
architecture — support/query examples are token sequences; FiLM modulates
the residual stream per layer.

    PYTHONPATH=src python examples/episodic_lm.py --arch minitron-4b
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearnerConfig, make_learner
from repro.core.set_encoder import SetEncoderConfig
from repro.data.episodic import EpisodicTokenConfig, sample_token_task
from repro.models.lm_backbone import make_lm_backbone
from repro.optim import clip_by_global_norm


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=["minitron-4b", "qwen2-72b",
                                       "gemma2-2b", "mamba2-780m"],
                    default="minitron-4b")
    ap.add_argument("--kind", choices=["protonets", "simple_cnaps"],
                    default="simple_cnaps")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--h", type=int, default=8, help="|H| back-propagated")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    backbone = make_lm_backbone(cfg)
    task_cfg = EpisodicTokenConfig(way=4, shot=8, query_per_class=6,
                                   seq_len=48, vocab=cfg.vocab)
    learner = make_learner(
        MetaLearnerConfig(kind=args.kind, way=4),
        backbone,
        SetEncoderConfig(kind="tokens", in_channels=cfg.vocab, task_dim=32),
    )
    params = learner.init(jax.random.key(0))
    lite = LiteSpec(h=args.h, chunk_size=8)
    n_support = task_cfg.way * task_cfg.shot
    print(f"episodic {args.kind}+LITE over {cfg.name}: "
          f"N={n_support} support sequences, |H|={args.h} back-propagated")

    @jax.jit
    def meta_step(p, task, key):
        (loss, aux), g = jax.value_and_grad(
            lambda pp: learner.meta_loss(pp, task, key, lite), has_aux=True)(p)
        g, _ = clip_by_global_norm(g, 10.0)
        return jax.tree.map(lambda a, b: a - 1e-3 * b, p, g), loss, aux

    key = jax.random.key(1)
    for step in range(args.steps):
        key, kt, kh = jax.random.split(key, 3)
        task = sample_token_task(kt, task_cfg)
        params, loss, aux = meta_step(params, task, kh)
        if step % 10 == 0:
            print(f"step {step:3d}  loss {float(loss):8.4f}  "
                  f"acc {float(aux['accuracy']):.2f}")

    accs = []
    for i in range(10):
        t = sample_token_task(jax.random.fold_in(jax.random.key(5), i), task_cfg)
        st = learner.adapt(params, t.support_x, t.support_y)
        pred = jnp.argmax(learner.predict(params, st, t.query_x), -1)
        accs.append(float(jnp.mean((pred == t.query_y).astype(jnp.float32))))
    print(f"held-out episodic accuracy over {cfg.name}: {np.mean(accs):.3f}")


if __name__ == "__main__":
    main()
