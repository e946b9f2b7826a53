"""Record `tpu_scoped.xplane.pb`, the scoped trace that
`bench/tests/test_bench_scopes.py` reads. Needs a TPU:

    python3 bench/tests/fixtures/record_tpu_scoped.py <output dir>

Three jitted programs, each dispatched three times under the harness's host
spans (`bench.window`, `generate`, `dispatch`, `readback`):

* `scan_insert`: a `lax.scan` whose body gathers under
  `pq.insert/kernel.windowed_merge.rank`, sorts under `pq.insert/pq.compact`
  (nested: the innermost scope names the layer) and runs a `lax.switch`
  under `pq.schedule` whose two branches open `pq.schedule.spray_herlihy`
  and `pq.schedule.hier`; a sort under `pq.presort` before the scan;
* `scan_refill`: the same program with `pq.refill` in place of
  `pq.insert` and another constant in a branch, so that its instructions
  carry the same names as the first program's under other scopes (the TPU
  runtime serves two programs that differ in nothing but metadata with
  one executable);
* `decide`: elementwise work and reductions under `pq.decide`.

The persistent compilation cache is off: it keys a program without its
debug info, so the second scan would load the first one's executable and
embed the first one's scopes. Copy the `.xplane.pb` written under the
output directory to `bench/tests/fixtures/tpu_scoped.xplane.pb`.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np


def make_scan(layer: str, scale: int):
    def scan(x, idx):
        def body(c, t):
            with jax.named_scope(layer):
                with jax.named_scope("kernel.windowed_merge.rank"):
                    g = jnp.take_along_axis(c, idx, axis=1)
                with jax.named_scope("pq.compact"):
                    g = jnp.sort(g, axis=1)

            def spray(v):
                with jax.named_scope("pq.schedule.spray_herlihy"):
                    return jnp.sort(v, axis=0) - t

            def hier(v):
                with jax.named_scope("pq.schedule.hier"):
                    return jnp.flip(v, axis=1) * scale + t

            with jax.named_scope("pq.schedule"):
                c = jax.lax.switch(t % 2, [spray, hier], g)
            return c, jnp.sum(c)

        with jax.named_scope("pq.presort"):
            x = jnp.sort(x, axis=0)
        return jax.lax.scan(body, x, jnp.arange(6, dtype=jnp.int32))

    scan.__name__ = "scan_" + layer.split(".")[1]
    return scan


def decide(c):
    with jax.named_scope("pq.decide"):
        return jnp.max((c * 7 + 3) % 11, axis=1) + jnp.argmin(c, axis=1)


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU found: the fixture is a TPU trace")
    jax.config.update("jax_enable_compilation_cache", False)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 256, (64, 256)), jnp.int32)
    scan_insert = jax.jit(make_scan("pq.insert", 3))
    scan_refill = jax.jit(make_scan("pq.refill", 5))
    decide_jit = jax.jit(decide)

    def dispatch(x):
        c, _ = scan_insert(x, idx)
        c, _ = scan_refill(c, idx)
        return decide_jit(c)

    np.asarray(dispatch(jnp.zeros((64, 256), jnp.int32)))  # compile first
    span = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with span("bench.window"):
        for _ in range(3):
            with span("generate"):
                x = jnp.asarray(rng.integers(0, 1 << 20, (64, 256)),
                                jnp.int32)
            with span("dispatch"):
                y = dispatch(x)
            with span("readback"):
                np.asarray(y)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
