"""kernels_autotune: tune every kernel's arms, persist the winners, and
prove the dispatched arm is the measured best.

For each registered kernel × tuning shape this suite benchmarks every
available arm (`repro.kernels.tuning.tune_kernel`), writes the winners to
the on-disk tuning cache (the same file `registry.resolve` consults — so a
full run of this suite IS the re-tune procedure), then re-resolves the
dispatch and emits one record per (kernel, shape):

  us_per_call      — the DISPATCHED arm's median (what production pays)
  within_best      — dispatched / tuner-chosen winner (<= 1.10 or dispatch
                     is broken)
  vs_raw_best      — dispatched / absolute-fastest arm; may exceed 1.0 up
                     to the tuner's MIN_SPEEDUP margin when a marginal
                     win was (deliberately) not worth leaving the default
  vs_interpret     — old hard-coded interpret-path median / dispatched
  vs_default       — the spec's safe jnp default median / dispatched

On this container's CPU backend the headline is vs_default: the
interpret-mode Pallas networks lower through XLA to static select chains
and beat the jnp sort-based paths on the hot shapes (e.g. windowed_merge
16x over the rank merge), which is exactly the per-platform choice the
registry exists to make.
"""

from __future__ import annotations

from benchmarks.common import emit
from repro.kernels import registry as REG
from repro.kernels import tuning


def run(quick: bool = False):
    iters = 6 if quick else 15
    cache = tuning.get_cache(reload=True)
    tuned = []  # (spec, coords, record)
    for spec in REG.REGISTRY.values():
        shapes = spec.tuning_shapes[:1] if quick else spec.tuning_shapes
        for coords in shapes:
            rec = tuning.tune_kernel(spec.name, coords, iters=iters)
            cache.put(spec.name, REG.sig(coords), rec)
            tuned.append((spec, coords, rec))
    path = cache.save()
    tuning.invalidate_cache()  # resolve() below sees the fresh winners
    print(f"# tuning cache -> {path}")

    for spec, coords, rec in tuned:
        sig = REG.sig(coords)
        timings = rec["timings"]
        dispatched = REG.resolve(spec.name, coords)
        disp_us = timings[dispatched]
        raw_best_us = min(timings.values())
        interp = [v for a, v in timings.items() if a.startswith("interpret")]
        fields = {
            "arm": dispatched,
            "winner": rec["arm"],
            "within_best": round(disp_us / rec["us"], 3),
            "vs_raw_best": round(disp_us / raw_best_us, 3),
            "timings": {a: round(v, 1) for a, v in timings.items()},
        }
        derived = (f"winner={rec['arm']};dispatched={dispatched};"
                   f"within_best={fields['within_best']:.2f};"
                   f"vs_raw_best={fields['vs_raw_best']:.2f}")
        if interp:
            fields["vs_interpret"] = round(min(interp) / disp_us, 3)
            derived += f";vs_interpret={fields['vs_interpret']:.2f}x"
        default = spec.default_for()
        if default in timings:
            fields["vs_default"] = round(timings[default] / disp_us, 3)
            derived += f";vs_default={fields['vs_default']:.2f}x"
        emit(f"kernels_autotune/{spec.name}/{sig}", disp_us, derived,
             **fields)
