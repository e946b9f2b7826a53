"""Serving launcher: SmartPQ continuous batching over a synthetic workload.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
      --requests 24 --slots 4
"""

import argparse


def main():
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--burst", type=int, default=6)
    args = ap.parse_args()

    import numpy as np
    import jax

    from repro.configs.registry import get_config, reduced_config
    from repro.models.registry import build_model
    from repro.serve.engine import EngineConfig, ServeEngine
    from repro.serve.scheduler import Request

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg, remat=False)
    params, _ = model.init(jax.random.key(0))
    engine = ServeEngine(
        cfg, params, EngineConfig(batch_size=args.slots, max_seq=args.max_seq)
    )

    rng = np.random.default_rng(0)
    workload, uid = [], 0
    while uid < args.requests:
        arrivals = []
        for _ in range(min(args.burst, args.requests - uid)):
            arrivals.append(
                Request(
                    uid=uid,
                    prompt_len=int(rng.integers(4, 16)),
                    max_new_tokens=int(rng.integers(2, 6)),
                    slo_class=int(rng.integers(0, 3)),
                )
            )
            uid += 1
        workload.append(arrivals)
        workload.extend([[]] * 4)

    summary = engine.run(workload, max_steps=10_000)
    print(
        f"[serve] {cfg.name}: {summary['completed']}/{args.requests} requests "
        f"in {summary['steps']} steps ({summary['wall_s']:.1f}s), "
        f"pq transitions={summary['pq_transitions']}"
    )


if __name__ == "__main__":
    main()
