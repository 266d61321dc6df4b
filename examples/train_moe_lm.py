"""End-to-end driver: train a ~100M-parameter MoE language model for a few
hundred steps on the synthetic corpus, with checkpointing and balance
metrics — the paper's §5.1 setup at laptop scale.

Run: PYTHONPATH=src python examples/train_moe_lm.py [--steps 300]
"""
import argparse
import os
import tempfile

import jax

from repro.common import param as pm
from repro.data.pipeline import DataConfig, DataIterator, optimal_xent
from repro.models.paper_lm import (PaperLMConfig, paper_lm_defs,
                                   paper_lm_loss)
from repro.optim.optimizers import OptConfig
from repro.train.trainer import Trainer, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--workdir", default=os.path.join(
        tempfile.gettempdir(), "repro_moe_lm"))
    args = ap.parse_args()

    # MoE-64 with ~1M-param experts (the paper's expert size), d_model 256.
    cfg = PaperLMConfig(vocab_size=8192, variant="moe",
                        n_experts=args.experts, k=4, d_model=256,
                        expert_hidden=1024, dropout=0.0,
                        w_importance=0.1, w_load=0.1)
    params = pm.materialize(paper_lm_defs(cfg), jax.random.PRNGKey(0))
    print(f"model: MoE-{args.experts}, {pm.param_count(params)/1e6:.0f}M "
          f"params total")

    dc = DataConfig(vocab_size=8192, seq_len=64, batch_size=32,
                    n_clusters=512, noise_prob=0.02)
    trainer = Trainer(
        loss_fn=lambda p, b, r: paper_lm_loss(p, b, cfg, rng=r),
        params=params,
        oc=OptConfig(kind="factored",          # the paper's App-D optimizer
                     learning_rate=1e-2, warmup_steps=100),
        loop=TrainLoopConfig(total_steps=args.steps, microbatches=2,
                             checkpoint_every=100, log_every=25),
        data_iter=DataIterator(dc), workdir=args.workdir)
    final = trainer.run()
    print(f"final: xent={final['xent']:.3f} "
          f"(entropy floor {optimal_xent(dc):.3f}) "
          f"ppl={final['perplexity']:.1f} "
          f"max/mean load={final['max_over_mean_load']:.2f}")
    print(f"checkpoints in {args.workdir}/ckpt — rerun to resume")


if __name__ == "__main__":
    main()
