"""Device time by scope (`bench/trace_scopes.py`), on two traces recorded on
a TPU v5e.

* `tpu_scoped.xplane.pb` (recorded by `fixtures/record_tpu_scoped.py`):
  two scans of the same shape, one under `pq.insert` and one under
  `pq.refill`, each nesting `pq.compact` and `kernel.windowed_merge.rank`
  and switching between two scoped `pq.schedule.*` branches, a sort under
  `pq.presort`, and a third program under `pq.decide`.
* `tpu_small.xplane.pb`: a program with no `pq.*` scope at all, as the
  program before the scopes were added.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as R  # noqa: E402
from bench import trace_scopes as S  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures"
SCOPED = FIXTURES / "tpu_scoped.xplane.pb"
UNSCOPED = FIXTURES / "tpu_small.xplane.pb"
SPANS = ("generate", "dispatch", "readback")


def _reduce(path):
    data = path.read_bytes()
    ops = S.reduce_bytes(data, "bench.window")
    return (S.hlo_op_names(data), ops, S.by_scope(ops),
            R.reduce_file(str(path), "bench.window", SPANS))


@pytest.fixture(scope="module")
def scoped():
    return _reduce(SCOPED)


@pytest.fixture(scope="module")
def unscoped():
    return _reduce(UNSCOPED)


def _seconds(ops, pred):
    return sum(o[4] for o in ops if pred(o))


def test_layer_times_add_up_to_busy_time(scoped):
    """Up to the trace's rounding of event times to whole nanoseconds."""
    _, ops, scopes, red = scoped
    assert sum(o[4] for o in ops) == pytest.approx(red.busy_s, abs=1e-8)
    layers = S.layers(scopes)
    assert set(layers) == {"pq.presort", "pq.insert", "pq.refill",
                           "pq.compact", "pq.schedule", "pq.decide",
                           S.UNSCOPED}
    assert sum(layers.values()) == pytest.approx(red.busy_s, abs=1e-8)
    shares = S.shares(scopes, red.busy_s)
    assert set(shares) == set(S.SHARES)
    partition = sum(v for k, v in shares.items()
                    if k != "windowed_merge_busy_pct")
    assert partition == pytest.approx(100.0, abs=1e-3)


def test_the_innermost_scope_wins(scoped):
    """The sorts under `pq.insert/pq.compact` count to `pq.compact` alone,
    and `pq.insert` keeps only what no inner layer scope claims."""
    _, ops, scopes, _ = scoped
    compact = [o for o in ops if "/pq.compact/" in o[3]]
    assert compact and all("/pq.insert/" in o[3] or "/pq.refill/" in o[3]
                           for o in compact)
    assert scopes["pq.compact"] == pytest.approx(
        _seconds(compact, lambda o: True), rel=1e-9)
    assert scopes["pq.insert"] == pytest.approx(_seconds(
        ops, lambda o: "/pq.insert/" in o[3] and "/pq.compact/" not in o[3]),
        rel=1e-9)
    assert scopes["pq.insert"] > 0 and scopes["pq.compact"] > 0


def test_a_branch_scope_rolls_up_into_its_parent(scoped):
    _, ops, scopes, _ = scoped
    branches = {k: v for k, v in scopes.items()
                if k.startswith("pq.schedule.")}
    assert set(branches) == {"pq.schedule.spray_herlihy",
                             "pq.schedule.hier"}
    assert all(v > 0 for v in branches.values())
    own = _seconds(ops, lambda o: S.scopes_of(o[3])[0] == "pq.schedule")
    assert scopes["pq.schedule"] == pytest.approx(
        own + sum(branches.values()), rel=1e-9)


def test_kernel_scopes_cut_across_the_layers(scoped):
    _, ops, scopes, red = scoped
    merge = [o for o in ops
             if S.scopes_of(o[3])[1] == "kernel.windowed_merge.rank"]
    assert {S.scopes_of(o[3])[0] for o in merge} == {"pq.insert",
                                                    "pq.refill"}
    assert scopes["kernel.windowed_merge.rank"] == pytest.approx(
        _seconds(merge, lambda o: True), rel=1e-9)
    share = S.shares(scopes, red.busy_s)["windowed_merge_busy_pct"]
    assert 0 < share < 100


def test_programs_with_the_same_instruction_names_do_not_mix(scoped):
    names, ops, scopes, _ = scoped
    ins, ref = (next(p for p in names if p.startswith(f"jit_scan_{n}("))
                for n in ("insert", "refill"))
    shared = [k for k in names[ins]
              if k in names[ref] and names[ins][k] != names[ref][k]]
    assert any(k.startswith("fusion") for k in shared)
    ran = {o[0] for o in ops}
    assert {ins, ref} <= ran
    for program, _, _, op_name, _ in ops:
        if program == ins:
            assert "pq.refill" not in op_name
        elif program == ref:
            assert "pq.insert" not in op_name
    assert scopes["pq.insert"] > 0 and scopes["pq.refill"] > 0


def test_a_fusion_with_no_op_name_takes_its_roots():
    """On the chip a gather fusion can carry no `op_name`, its root a
    bitcast with none either: the nearest operand of the root names it."""
    names = S.hlo_op_names(UNSCOPED.read_bytes())
    prog = next(p for p in names if p.startswith("jit_prog("))
    assert names[prog]["fusion.7"] == (
        "jit(prog)/while/body/closed_call/jit(take_along_axis)/gather")


def test_an_unscoped_program_is_all_unscoped(unscoped):
    """A trace of a program that names no scope (the program before the
    scopes): every op is `(unscoped)` and no share is reported."""
    names, ops, scopes, red = unscoped
    assert len(names) == 2
    assert set(scopes) == {S.UNSCOPED}
    assert scopes[S.UNSCOPED] == pytest.approx(red.busy_s, rel=1e-9)
    assert S.shares(scopes, red.busy_s) == {}


@pytest.mark.parametrize("op_name, layer, kernel", [
    ("jit(run_window)/while/body/closed_call/pq.insert/cond/"
     "kernel.windowed_merge.rank/jit(take_along_axis)/gather",
     "pq.insert", "kernel.windowed_merge.rank"),
    ("jit(w)/while/body/pq.refill/cond/pq.compact/jit(sort)/sort",
     "pq.compact", None),
    ("jit(w)/pq.schedule/cond/branch_2_fun/pq.schedule.hier/"
     "kernel.topk_smallest.argsort/jit(_topk_dispatch)/sort",
     "pq.schedule.hier", "kernel.topk_smallest.argsort"),
    ("jit(prog)/while/body/closed_call/jit(take_along_axis)/gather",
     None, None),
    ("", None, None),
])
def test_scopes_of_an_op_name(op_name, layer, kernel):
    assert S.scopes_of(op_name) == (layer, kernel)


@pytest.mark.parametrize("event, instruction", [
    ("%fusion.8 = s32[64,256]{1,0:T(8,128)} fusion(s32[64,256] %p), "
     "kind=kCustom, calls=%fused_computation.8", "fusion.8"),
    ("%while = (s32[]{:T(128)}, s32[64,256]) while((s32[]) %t)", "while"),
    ("sort.12", "sort.12"),
])
def test_instruction_of_an_event(event, instruction):
    assert S.instruction_of(event) == instruction


def test_by_scope_of_synthetic_ops():
    ops = [("p", "a", "sort", "x/pq.schedule/cond/pq.schedule.hier/s", 2.0),
           ("p", "b", "while", "x/pq.schedule/cond", 1.0),
           ("p", "c", "fusion:kCustom",
            "x/pq.insert/kernel.windowed_merge.rank/gather", 4.0),
           ("p", "d", "copy", "", 0.5)]
    assert S.by_scope(ops) == {"pq.schedule.hier": 2.0, "pq.schedule": 3.0,
                               "pq.insert": 4.0,
                               "kernel.windowed_merge.rank": 4.0,
                               S.UNSCOPED: 0.5}
    assert S.layers(S.by_scope(ops)) == {"pq.schedule": 3.0,
                                         "pq.insert": 4.0, S.UNSCOPED: 0.5}


def test_the_wire_reader():
    # field 1 varint 150 (two bytes), field 2 bytes "hi", field 3 packed
    # [3, 270], field 4 fixed64
    msg = (b"\x08\x96\x01" + b"\x12\x02hi" + b"\x1a\x03\x03\x8e\x02"
           + b"\x21" + bytes(8))
    fields = list(S._fields(msg))
    assert [f for f, _ in fields] == [1, 2, 3, 4]
    assert fields[0][1] == 150
    assert S._text(msg, fields[1][1]) == "hi"
    assert S._ints(msg, fields[2][1]) == [3, 270]
    assert S._ints(msg, 7) == [7]


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "trace_scopes.py"),
         "--workload", "pq1m.table3", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
