"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
the full production stack — supervisor (checkpoint/restart), resumable
data pipeline, straggler monitor, WSD schedule.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --arch minicpm-2b
(the arch config is scaled to ~100M params for CPU)
"""

import argparse
import time

import jax

from repro.configs import get_config
from repro.data.pipeline import SyntheticTokens, make_batch_fn
from repro.jit_cache import enable_compile_cache
from repro.models.registry import build_model, param_count
from repro.runtime import TrainSupervisor
from repro.train import init_train_state, make_optimizer, make_train_step
from repro.train.optimizer import wsd_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    # ~100M-param reduction of the chosen family
    cfg = get_config(args.arch).scaled(
        n_layers=6, d_model=512, n_heads=8, n_kv_heads=8, d_ff=2048,
        head_dim=64, vocab_size=32000, dtype="float32",
        sliding_window=0, global_layers=(),
    )
    model = build_model(cfg)
    opt = make_optimizer("adamw", wsd_schedule(3e-4, 20, args.steps - 60, 40))
    state = init_train_state(model, opt, jax.random.key(0))
    print(f"{cfg.name}: {param_count(state['params']) / 1e6:.1f}M params")

    src = SyntheticTokens(cfg.vocab_size, args.seq, args.batch, seed=0)
    batch_fn = make_batch_fn(src)
    step_fn = jax.jit(make_train_step(model, opt))

    sup = TrainSupervisor(args.ckpt_dir, ckpt_every=50)
    t0 = time.time()

    def log(step, metrics, dt, slow):
        if step % 20 == 0:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics.get('lr', 0)):.2e} {dt * 1e3:.0f} ms"
                  + (" [STRAGGLER]" if slow else ""))

    state = sup.run(state, step_fn, batch_fn, args.steps, log=log)
    print(f"done in {time.time() - t0:.1f}s; restarts={sup.restarts}; "
          f"stragglers flagged={len(sup.monitor.flagged)}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
