"""Serving launcher: batched prefill+decode over any assigned architecture.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --requests 4 --new-tokens 16
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args()

    import numpy as np

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_zoo as zoo
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.sampling import SamplingParams

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    print(f"arch={cfg.name} params={zoo.param_count(cfg)/1e6:.1f}M")
    params = zoo.init_params(cfg, 0)
    eng = ServeEngine(cfg, params, batch_size=args.batch_size,
                      max_len=args.max_len,
                      sampling=SamplingParams(greedy=args.greedy))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, args.prompt_len)
                    .astype(np.int32), max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    for r in done[:3]:
        print(f"req {r.rid}: out={r.out_tokens[:8]}...")
    print(f"throughput: {eng.throughput()} wall={dt:.1f}s")


if __name__ == "__main__":
    main()
