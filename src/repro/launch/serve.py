"""Serving CLI: ``PYTHONPATH=src python -m repro.launch.serve --arch <id>``."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.serve.engine import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    # jitted: the float32 draws fuse into the bf16 weights instead of
    # materializing at full width next to them
    params = jax.jit(init_params, static_argnums=0)(cfg,
                                                   jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    out = greedy_generate(cfg, params, prompts, max_new_tokens=args.max_new,
                          temperature=args.temperature)
    for i in range(args.batch):
        print(f"req{i}: {np.asarray(out[i])}")


if __name__ == "__main__":
    main()
