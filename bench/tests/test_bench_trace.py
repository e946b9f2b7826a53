"""The trace reduction, on a small trace recorded on a TPU v5e: a jitted
loop whose body sorts, gathers and branches, run three times under the
harness's host spans (`bench.window`, `generate`, `dispatch`,
`readback`)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as R  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_small.xplane.pb"
SPANS = ("generate", "dispatch", "readback")


@pytest.fixture(scope="module")
def red():
    return R.reduce_file(str(FIXTURE), "bench.window", SPANS)


def test_busy_and_idle_add_up_to_the_window(red):
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s == pytest.approx(0.001963562, abs=1e-12)
    assert red.window_s == pytest.approx(0.008339501, abs=1e-12)
    idle = sum(s for _, s in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    assert 0 < red.idle_pct < 100


def test_own_times_add_up_to_busy_time(red):
    """A loop's body and a conditional's branch are nested in its event;
    each op counts only its own time, so nothing is counted twice."""
    assert sum(red.by_opcode.values()) == pytest.approx(red.busy_s,
                                                        rel=1e-9)
    assert {"sort", "while", "conditional", "fusion:kCustom"} <= set(
        red.by_opcode)
    assert red.by_opcode["sort"] == pytest.approx(4.789e-05, abs=1e-12)


def test_programs_and_gaps(red):
    prog = max(red.modules.values(), key=sum)
    assert len(prog) == 3
    assert {label for label, _ in red.gaps} <= set(SPANS) | {"other"}
    bd = red.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= R.TOP and len(bd["idle_gaps"]) <= R.TOP


@pytest.mark.parametrize("name, opcode", [
    ("%sort.10 = (s32[1,6784]{1,0:T(1,128)}, s32[1,6784]{1,0:T(1,128)S(1)})"
     " sort(s32[1,6784]{1,0:T(1,128)S(1)} %reshape.27), dimensions={1}",
     "sort"),
    ("%while.47 = (s32[]{:T(128)}, /*index=5*/s32[64]{0:T(128)}) "
     "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b", "while"),
    ("%fusion.416 = s32[20032]{0:T(1024)S(1)} fusion(s32[64,256] %p), "
     "kind=kCustom, calls=%fused_computation.4", "fusion:kCustom"),
    ("%copy.354 = s32[64,32512]{1,0:T(8,128)} copy(s32[64,32512] %g)",
     "copy"),
    ("jit_run_window(14787629882259073863)", "jit_run_window(14787629882259073863)"),
    ("sort.12", "sort"),
])
def test_opcode_is_read_from_the_instruction_text(name, opcode):
    assert R.opcode_of(name) == opcode


def test_self_times_of_nested_ops():
    ops = [(0, 10, "while"), (1, 4, "sort"), (4, 9, "conditional"),
           (5, 7, "fusion:kLoop"), (12, 13, "copy")]
    own = {op: t for _, _, op, t in R.self_times(ops)}
    assert own == {"while": 2, "sort": 3, "conditional": 3,
                   "fusion:kLoop": 2, "copy": 1}
    assert R.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
