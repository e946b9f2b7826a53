"""The fused window's named scopes (`repro.obs.profiling.LAYER_SCOPES`).

Each phase of `SmartPQ.step` / `run_window`, each schedule branch, the tail
compaction and each kernel dispatch runs under a `jax.named_scope`, so that
a device trace can attribute an op's time to its layer through the HLO
`op_name` the profiler embeds.  These tests pin that every scope reaches
the compiled program's metadata, and that a scope changes no program: the
StableHLO without debug info is byte-identical with the scopes nulled out.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.pqueue.ops import OP_DELETE_MIN, OP_INSERT
from repro.core.smartpq import MODE_AWARE, SmartPQ, SmartPQConfig
from repro.kernels import ops as KO
from repro.obs.profiling import LAYER_SCOPES

K, B = 4, 16


@pytest.fixture(scope="module")
def pq():
    return SmartPQ(SmartPQConfig(
        num_shards=8, capacity=1024, head_width=64, decision_interval=4,
        initial_mode=MODE_AWARE, eliminate=True,
    ))


def _window_inputs():
    lane = jnp.arange(B, dtype=jnp.int32)
    ops = jnp.broadcast_to(
        jnp.where(lane % 2 == 0, OP_INSERT, OP_DELETE_MIN), (K, B))
    keys = jnp.broadcast_to(lane * 7, (K, B)).astype(jnp.int32)
    rngs = jax.random.split(jax.random.key(0), K)
    return ops, keys, keys, rngs


def _lower_window(pq):
    ops, keys, vals, rngs = _window_inputs()
    return jax.jit(pq.run_window).lower(pq.init(), ops, keys, vals, rngs, 8)


def _lower_hold_scan(pq):
    """A scan over `SmartPQ.step` with no presorted log, like the hold
    model's window: the op-log sort runs inside the step."""
    ops, keys, vals, rngs = _window_inputs()

    def window(carry, xs):
        def body(c, x):
            o, k, v, r = x
            c, res = pq.step(c, o, k, v, r, B)
            return c, res.keys

        return jax.lax.scan(body, carry, xs)

    return jax.jit(window).lower(pq.init(), (ops, keys, vals, rngs))


LOWER = {"run_window": _lower_window, "hold_scan": _lower_hold_scan}


def _scopes(hlo_text: str) -> set:
    """The `pq.*` and `kernel.*` components of every `op_name`."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        out.update(c for c in name.split("/")
                   if c.startswith(("pq.", "kernel.")))
    return out


@pytest.fixture(scope="module")
def compiled_scopes(pq):
    return {name: _scopes(lower(pq).compile().as_text())
            for name, lower in LOWER.items()}


@pytest.mark.parametrize("program", sorted(LOWER))
def test_compiled_program_carries_every_scope(pq, compiled_scopes, program):
    scopes = compiled_scopes[program]
    expected = set(LAYER_SCOPES)
    if program == "hold_scan":  # no hoisted pre-sort outside run_window
        expected.discard("pq.presort")
    assert expected <= scopes, expected - scopes
    for schedule in pq.config.mode_schedules:
        assert f"pq.schedule.{schedule.name.lower()}" in scopes
    assert any(s.startswith("kernel.windowed_merge.") for s in scopes)
    assert any(s.startswith("kernel.elim_sort.") for s in scopes)
    assert all(s in LAYER_SCOPES or s.startswith(("pq.schedule.", "kernel."))
               for s in scopes), scopes


@pytest.mark.parametrize("program", sorted(LOWER))
def test_scopes_change_no_program(pq, program, monkeypatch):
    """Scopes are metadata only: with `jax.named_scope` nulled out the
    lowered program is byte-identical, so tracing off costs nothing."""
    lower = LOWER[program]
    scoped = lower(pq)
    assert "pq.decide" in scoped.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = lower(pq)
    assert "pq.decide" not in plain.as_text(debug_info=True)
    assert scoped.as_text(debug_info=False) == plain.as_text(debug_info=False)


def test_kernel_scope_drops_the_tuning_suffix():
    """`kernel.<kernel>.<arm>`: the arm without its `@axis=value` suffix."""
    keys = jnp.arange(8 * 16, dtype=jnp.int32).reshape(8, 16)[:, ::-1]
    lowered = jax.jit(
        lambda k: KO.elim_sort(k, k, arm="interpret@rows_per_block=8")
    ).lower(keys)
    text = lowered.as_text(debug_info=True)
    assert "kernel.elim_sort.interpret" in text
    assert "@rows_per_block" not in text
