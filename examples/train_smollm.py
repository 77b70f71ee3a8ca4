"""End-to-end training driver: train a ~smollm-family model for a few
hundred steps on the synthetic pipeline, with async checkpointing and the
straggler watchdog.  On CPU this uses a reduced config by default; pass
--full to build the real 360M config (slow on CPU).

    PYTHONPATH=src python examples/train_smollm.py --steps 200
"""
import argparse

import jax

from repro.configs import base as C
from repro.data import DataConfig, SyntheticLM
from repro.models import build
from repro.optim import OptConfig
from repro.train import TrainConfig, Trainer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get(args.arch)
    if not args.full:
        cfg = C.reduced(cfg, n_layers=4, d_model=128, vocab=512,
                        d_ff_scale=64)
    mesh = make_host_mesh((1, 1), ("data", "model"))
    model = build(cfg, mesh)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=0))
    tr = Trainer(model,
                 OptConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps),
                 TrainConfig(ckpt_every=50, ckpt_dir=args.ckpt), data)
    if not tr.restore():
        tr.init_state(jax.random.PRNGKey(0))
        print("fresh start")
    else:
        print(f"restored from step {tr.step}")
    losses = tr.run(args.steps)
    print(f"step {tr.step}: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    print("watchdog:", tr.watchdog.summary())


if __name__ == "__main__":
    main()
