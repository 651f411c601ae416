"""Production training launcher: run the (sharded) train step for any
assigned arch on whatever devices exist. On the real TPU cluster this runs
under `python -m repro.launch.train --arch <id>` per host; in the container
it runs the reduced config on CPU (--reduced, default when 1 device).

The dry-run (launch/dryrun.py) is the no-hardware path that validates the
production mesh; this launcher shares its step functions (launch/specs.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.synthetic import MarkovSpec, sample_corpus
from repro.launch.specs import make_train_step
from repro.models.model import init_params
from repro.runtime_env import use_compilation_cache
from repro.training.optim import init_adamw


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--full-config", action="store_true",
                    help="use the production config (needs a real cluster)")
    args = ap.parse_args()
    use_compilation_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        import dataclasses
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    print(f"[train] arch={cfg.name} devices={len(jax.devices())}")

    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    opt = init_adamw(params)
    step = jax.jit(make_train_step(cfg))

    if cfg.modality == "audio":
        feats = np.random.RandomState(0).randn(
            args.batch, args.seq_len, cfg.d_model).astype(np.float32)
        batch = {
            "features": jnp.asarray(feats),
            "targets": jnp.asarray(np.random.RandomState(1).randint(
                0, cfg.vocab_size, (args.batch, args.seq_len))),
            "mask": jnp.asarray(np.random.RandomState(2).rand(
                args.batch, args.seq_len) < 0.3),
        }
        batches = [batch] * args.steps
    else:
        spec = MarkovSpec(vocab_size=cfg.vocab_size, seed=0)
        data = sample_corpus(spec, args.batch * args.steps, args.seq_len)
        batches = [{"tokens": jnp.asarray(
            data[i * args.batch:(i + 1) * args.batch])}
            for i in range(args.steps)]

    t0 = time.time()
    for i, batch in enumerate(batches):
        params, opt, metrics = step(params, opt, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"[train {i:4d}] loss={float(metrics['loss']):.4f} "
                  f"({time.time()-t0:.1f}s)")
    print("[train] done")


if __name__ == "__main__":
    main()
