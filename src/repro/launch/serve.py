"""Serving launcher: --arch <id>, synthetic batched requests.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Request, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled(param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        max_seq=args.prompt_len + args.max_new + 4)
    reqs = [Request(i, np.random.default_rng(i).integers(
                1, cfg.vocab_size - 1, size=(args.prompt_len,)
            ).astype(np.int32), args.max_new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    resp = eng.serve(reqs)
    dt = time.perf_counter() - t0
    tok = sum(len(r.tokens) for r in resp)
    print(f"{len(resp)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
