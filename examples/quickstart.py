"""Quickstart: train a tiny decoder LM with the repro stack in ~30 seconds.

  PYTHONPATH=src python examples/quickstart.py
"""

import tempfile

import jax

from repro.data import SyntheticLMDataset, make_train_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LMConfig
from repro.optim import cosine_schedule, make_optimizer
from repro.train import make_train_state, make_train_step
from repro.train.trainer import Trainer


def main():
    cfg = LMConfig(name="quickstart", n_layers=4, d_model=128, n_heads=4,
                   n_kv_heads=2, d_head=32, d_ff=256, vocab_size=256,
                   tie_embeddings=True)
    print(f"model: {cfg.param_count()/1e6:.2f}M params")

    opt = make_optimizer(lr=cosine_schedule(8e-3, warmup=8, total=80),
                         weight_decay=0.01)
    step, _ = make_train_step(cfg, opt, n_loss_chunks=2)
    state = make_train_state(cfg, jax.random.PRNGKey(0), opt)
    ds = SyntheticLMDataset(vocab_size=256, seq_len=64, global_batch=16,
                            seed=0, n_clusters=8)

    with tempfile.TemporaryDirectory() as ckdir:
        trainer = Trainer(cfg, step, ckdir, checkpoint_every=20)
        state, rep = trainer.run(state, make_train_iterator(ds), n_steps=80)
    print(f"step  1 loss: {rep.losses[0]:.3f}")
    print(f"step {rep.steps_done} loss: {rep.final_loss:.3f}")
    assert rep.final_loss < rep.losses[0] - 0.25, "model must learn"
    print("OK — loss decreased; checkpointing + straggler watchdog exercised")


if __name__ == "__main__":
    enable_compile_cache()
    main()
