"""SmartPQ — the paper's adaptive priority queue (§3), TPU form, N modes.

Three key ideas of the paper, and where they live here:
  1. Every algorithmic mode operates on the *same* underlying concurrent
     structure  ->  all branches of `lax.switch` read/write the identical
     PQState pytree; the sharding never changes with the mode.
  2. A decision mechanism picks the mode  ->  packed decision tree evaluated
     on-device every `decision_interval` steps (paper: every second, host
     side; here: in-graph, zero host round-trip).
  3. Transitions need no synchronization point  ->  the mode is a traced
     int32 in the carry; "switching" is literally the predicate of
     `lax.switch` changing value between two steps of one compiled program.

N-mode architecture (generalized from the paper's 2-mode oblivious/aware
choice).  The mode set is `SmartPQConfig.mode_schedules`: a tuple of
`Schedule`s indexed by mode id, which is simultaneously (a) the classifier
class id, (b) the `lax.switch` branch index, and (c) the `make_mode_steps`
dict key.  Shipped modes:

    0 MODE_OBLIVIOUS -> SPRAY_HERLIHY  relaxed, collective-free spray
    1 MODE_MULTIQ    -> MULTIQ         relaxed MultiQueue: two-choice
                                       min-cache sampling, bounded rank error
    2 MODE_AWARE     -> HIER           exact Nuddle pod-delegation

Adding a fourth mode (e.g. elimination/combining a la Calciu et al.) is a
three-step recipe, no decision-plumbing changes:
  1. implement the schedule in `pqueue.schedules` and register it in
     `SCHEDULE_FNS` (plus `pqueue.dist` if it needs real collectives);
  2. append a class id for it in `classifier.features` (before
     CLASS_NEUTRAL, bumping NUM_MODES) and give `classifier.cost_model` a
     `_delete_cost_*` arm so training labels exist;
  3. append its Schedule to `mode_schedules`.  The switch, the stats loop,
     `make_mode_steps`, and the decision tree all size off NUM_MODES /
     len(mode_schedules) automatically.

Workload statistics (paper §5's future-work sketch — implemented here): the
step tracks completed insert/delete counts, min/max requested key, and the
caller-supplied active-client count, and derives Table-1 features on the fly.

Fused-window execution (`run_window` / `jit_run_window`): K steps roll into
ONE donated `lax.scan` whose body contains the full adaptive loop — jnp
featurization, on-device tree inference, the N-mode `lax.switch`, and the
schedule — so mode transitions happen mid-window without leaving the device
and per-operation cost amortizes K steps of dispatch into one.  In front of
the scan, the elimination/combining pre-pass sorts the whole (K, B)
operation log in one vectorized call (the sort is state-independent; only
the cutoff compare stays in the body), and matched insert/deleteMin pairs
are served without ever touching PQState.  The window trace is bit-identical
to K sequential `jit_step` calls (same code path, same rngs — tested), and
exact schedules remain bit-identical to the oracle linearization.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.classifier.dataset import make_training_set
from repro.core.classifier.features import (
    CLASS_AWARE,
    CLASS_MULTIQ,
    CLASS_NEUTRAL,
    CLASS_OBLIVIOUS,
    NUM_CLASSES,
    NUM_MODES,
    featurize_jnp,
)
from repro.core.classifier.inference import PackedTree, pack_tree, tree_predict
from repro.core.classifier.tree import DecisionTree, train_tree
from repro.core.pqueue import local as L
from repro.core.pqueue import ops as O
from repro.core.pqueue import schedules as SCH
from repro.core.pqueue.ops import OP_DELETE_MIN, OP_INSERT, insert
from repro.core.pqueue.schedules import DeleteResult, Schedule
from repro.core.pqueue.state import INF_KEY, PQState, make_state

# Mode encoding in the carry (== classifier class ids == switch branch ids).
MODE_OBLIVIOUS = CLASS_OBLIVIOUS  # 0: base algorithm directly (spray)
MODE_MULTIQ = CLASS_MULTIQ  # 1: relaxed MultiQueue (two-choice sampling)
MODE_AWARE = CLASS_AWARE  # 2: Nuddle delegation (hier)


class SmartPQStats(NamedTuple):
    """Replicated workload statistics (paper §5)."""

    step: jnp.ndarray  # () int32
    mode: jnp.ndarray  # () int32 — current algorithmic mode
    n_insert: jnp.ndarray  # () int32 ops since last decision
    n_delete: jnp.ndarray  # () int32
    min_key: jnp.ndarray  # () int32 smallest key requested so far
    max_key: jnp.ndarray  # () int32 largest
    transitions: jnp.ndarray  # () int32 — mode flips (overhead accounting)
    eliminated: jnp.ndarray  # () int32 — pairs served by the pre-pass
    rejected: jnp.ndarray  # () int32 — non-finite keys refused at admission
    mode_steps: jnp.ndarray  # (NUM_MODES,) int32 — steps spent per mode
    head_refills: jnp.ndarray  # () int32 — guarded hot-tier refill firings
    ring_deferred: jnp.ndarray  # () int32 — ring entries past their arrival
    # tick a window could not lane-admit yet (written by the serving
    # scheduler's fused scan; plain `step` threads it through unchanged)


class SmartPQCarry(NamedTuple):
    state: PQState
    stats: SmartPQStats


class WindowResult(NamedTuple):
    """Per-step delete outputs of a fused K-step window (state lives in the
    returned carry)."""

    keys: jnp.ndarray  # (K, B) ascending per step, INF-padded
    vals: jnp.ndarray  # (K, B)
    n_out: jnp.ndarray  # (K,)
    mode: jnp.ndarray  # (K,) mode AFTER each step (the on-device trace)


@dataclasses.dataclass(frozen=True)
class SmartPQConfig:
    num_shards: int = 64
    capacity: int = 4096
    # hot head tier width (None -> state.DEFAULT_HEAD_WIDTH, clamped to
    # capacity).  H-sizing rule: H >= batch + (ilog2(S)+1)^2 (see state.py).
    head_width: int | None = None
    npods: int = 2
    decision_interval: int = 8  # steps between classifier calls
    # Schedule per mode id — index == classifier class == switch branch.
    mode_schedules: Tuple[Schedule, ...] = (
        Schedule.SPRAY_HERLIHY,  # MODE_OBLIVIOUS
        Schedule.MULTIQ,  # MODE_MULTIQ
        Schedule.HIER,  # MODE_AWARE
    )
    initial_mode: int = MODE_OBLIVIOUS  # paper Fig. 8 line 106: default 1
    # Elimination/combining pre-pass (Calciu et al.): serve matched
    # insert/deleteMin pairs of a batch without touching PQState.  Exact for
    # exact schedules (ops.py docstring), envelope-tightening for relaxed
    # ones.  Off -> the plain insert-then-schedule step, bit for bit.
    eliminate: bool = True
    # Runtime guard tier: when True, validated callers (the serving
    # scheduler's tick/tick_window, `traces.replay`) run the host-side
    # invariant checker (`state.invariant_violations`) after every
    # step/window and surface a structured `InvariantViolation` — the
    # serving scheduler additionally checkpoints before the call and, on a
    # trip, rolls back and retries once in a conservative fallback (STRICT
    # schedule, elimination off) before raising the typed error.  Off
    # (default) costs nothing; on costs one host sync + one state copy per
    # validated call.
    validate: bool = False

    def __post_init__(self):
        assert len(self.mode_schedules) == NUM_MODES, (
            f"mode_schedules must give one Schedule per classifier mode "
            f"({NUM_MODES}); got {len(self.mode_schedules)} — did you add a "
            f"mode without appending its class id in classifier.features?"
        )


class SmartPQ:
    """Adaptive PQ facade.  Construct once (trains or accepts a tree), then
    drive `.step` (jittable, donatable), `.run_window` (K steps fused into
    one donated lax.scan — the dispatch-amortized serving path), or
    `make_mode_steps` (pre-compiled per-mode dispatch — for runtimes that
    prefer not to carry all branches)."""

    def __init__(
        self,
        config: SmartPQConfig = SmartPQConfig(),
        tree: Optional[DecisionTree] = None,
    ):
        self.config = config
        if tree is None:
            X, y = make_training_set()
            tree = train_tree(X, y, NUM_CLASSES, max_depth=8)
        self.tree = tree
        self.packed: PackedTree = pack_tree(tree)

    # -- lifecycle -----------------------------------------------------------

    def init(self) -> SmartPQCarry:
        c = self.config
        stats = SmartPQStats(
            step=jnp.int32(0),
            mode=jnp.int32(c.initial_mode),
            n_insert=jnp.int32(0),
            n_delete=jnp.int32(0),
            min_key=jnp.int32(INF_KEY),
            max_key=jnp.int32(0),
            transitions=jnp.int32(0),
            eliminated=jnp.int32(0),
            rejected=jnp.int32(0),
            mode_steps=jnp.zeros((NUM_MODES,), jnp.int32),
            head_refills=jnp.int32(0),
            ring_deferred=jnp.int32(0),
        )
        return SmartPQCarry(
            make_state(c.num_shards, c.capacity, head_width=c.head_width),
            stats,
        )

    # -- the adaptive step ----------------------------------------------------

    @functools.cached_property
    def jit_step(self):
        """`step` jitted with the carry DONATED: XLA aliases every PQState /
        stats buffer input->output (asserted via `utils.hlo.donation_aliases`
        in tests), so a steady-state step moves the queue zero times.  The
        caller must thread the returned carry and never reuse the argument
        (its buffers are deleted) — exactly the scan/serving-loop pattern."""
        return jax.jit(self.step, donate_argnums=(0,),
                       static_argnames=("return_features",))

    def step(
        self,
        carry: SmartPQCarry,
        ops: jnp.ndarray,  # (B,)
        keys: jnp.ndarray,  # (B,)
        vals: jnp.ndarray,  # (B,)
        rng: jax.Array,
        num_clients: jnp.ndarray | int | None = None,
        presorted: Tuple[jnp.ndarray, jnp.ndarray] | None = None,
        mode_override: jnp.ndarray | None = None,
        return_features: bool = False,
    ) -> Tuple[SmartPQCarry, DeleteResult] | Tuple[
        SmartPQCarry, DeleteResult, jnp.ndarray
    ]:
        """One bulk step: update stats -> (maybe) re-decide mode -> eliminate
        matched pairs -> apply the rest under the selected mode.  Pure
        function; jit/scan friendly.  `presorted` is the (sorted_keys,
        sorted_tags) insert log from `run_window`'s vectorized pre-pass —
        it is bit-identical to the in-step sort, just hoisted out of the
        scan.  `mode_override` (scalar int32, -1 = none) pins the mode for
        this step regardless of the classifier — the serving tier's
        graceful-degradation hook (force the relaxed MULTIQ mode under
        overload); None compiles the exact pre-override graph.
        `return_features` (static) appends the step's classifier feature
        vector (4,) float32 to the return — the observability layer's
        mode-transition trace attaches it to transition events; it is an
        extra OUTPUT of values the graph computes anyway, so the dispatch
        stream is untouched."""
        c = self.config
        state, stats = carry
        B = ops.shape[0]
        if num_clients is None:
            num_clients = c.num_shards
        num_clients = jnp.asarray(num_clients, jnp.int32)

        # Each phase of the step runs under a `jax.named_scope` named in
        # `repro.obs.profiling.LAYER_SCOPES`.  A scope is metadata only (the
        # HLO `op_name`): the compiled program is the same without it, and a
        # device trace can attribute each op's time to its phase.
        with jax.named_scope("pq.decide"):
            ins_mask = ops == OP_INSERT
            n_rejected = stats.rejected
            if jnp.issubdtype(jnp.asarray(keys).dtype, jnp.floating):
                # Admission-boundary sanitization: float key batches may carry
                # NaN/±inf — reject (-> INF sentinel, counted) instead of
                # letting IEEE sort semantics order them into the queue.  The
                # dtype test is trace-time: integer batches compile the exact
                # pre-sanitizer graph.
                keys, bad_keys = O.sanitize_keys(keys)
                n_rejected = n_rejected + jnp.sum(
                    bad_keys & ins_mask
                ).astype(jnp.int32)
                ins_mask = ins_mask & ~bad_keys
            b_ins = jnp.sum(ins_mask).astype(jnp.int32)
            b_del = jnp.sum(ops == OP_DELETE_MIN).astype(jnp.int32)

            batch_min = jnp.min(jnp.where(ins_mask, keys, INF_KEY))
            batch_max = jnp.max(jnp.where(ins_mask, keys, 0))
            n_insert = stats.n_insert + b_ins
            n_delete = stats.n_delete + b_del
            min_key = jnp.minimum(stats.min_key, batch_min)
            max_key = jnp.maximum(stats.max_key, batch_max)

            # -- decision (paper Fig. 8 decisionTree(), on-device) -----------
            do_decide = (stats.step % c.decision_interval) == 0
            total_ops = jnp.maximum(n_insert + n_delete, 1)
            key_range = jnp.where(
                min_key <= max_key, jnp.maximum(max_key - min_key, 1), 1
            )
            feats = featurize_jnp(
                num_clients,
                state.total_size,
                key_range,
                n_insert.astype(jnp.float32) / total_ops.astype(jnp.float32),
            )
            pred = tree_predict(self.packed, feats)
            # NEUTRAL (and any future >= NUM_MODES sentinel) keeps the mode; a
            # NEGATIVE class (possible only from a corrupted packed tree) must
            # not reach the switch either.
            keep = (~do_decide) | (pred >= NUM_MODES) | (pred < 0)
            new_mode = jnp.where(keep, stats.mode, pred).astype(jnp.int32)
            if mode_override is not None:
                ov = jnp.asarray(mode_override, jnp.int32)
                new_mode = jnp.where(ov >= 0, ov, new_mode)
            # Hard clamp before `lax.switch`: an out-of-range branch index —
            # whether from a corrupt tree label, a corrupt carry, or a bad
            # override — degrades to the nearest valid mode instead of UB.
            new_mode = jnp.clip(new_mode, 0, NUM_MODES - 1)
            transitions = stats.transitions + (new_mode != stats.mode).astype(jnp.int32)
            # Reset windowed op counters after each decision.
            n_insert = jnp.where(do_decide, 0, n_insert)
            n_delete = jnp.where(do_decide, 0, n_delete)

        # -- elimination/combining pre-pass ----------------------------------
        if c.eliminate:
            with jax.named_scope("pq.eliminate"):
                if presorted is None:
                    presorted = L.sort_op_log(
                        jnp.where(ins_mask, keys, INF_KEY))
                sk, stg = presorted
                elim_k, elim_v, n_elim, keep_lane = O.elim_split(
                    state, sk, stg, vals, b_del
                )
                ins_mask = ins_mask & keep_lane
                active = b_del - n_elim
        else:
            n_elim = jnp.int32(0)
            active = b_del

        # -- apply batch under the selected mode ------------------------------
        # ensure_head is mode-independent (same bound m=B for every branch),
        # so it hoists OUT of the switch; the branches then read/write only
        # the HotTier — the cold tail never crosses the switch boundary, so
        # the conditional's operand/result copies are head-sized, not
        # capacity-sized (the big CPU win of the fused window).
        with jax.named_scope("pq.insert"):
            state, dropped = insert(state, keys, vals, mask=ins_mask)
        with jax.named_scope("pq.refill"):
            # Count the refill BEFORE ensure_head consumes the predicate —
            # the same expression gates the lax.cond inside, so the counter
            # tracks actual guarded-refill firings, not an approximation.
            head_refills = stats.head_refills + SCH.head_refill_pred(
                state, B
            ).astype(jnp.int32)
            state = SCH.ensure_head(state, B)
        total = state.total_size

        def run(schedule: Schedule):
            fn = SCH.HOT_SCHEDULE_FNS[schedule]

            def branch(operand):
                hot_in, rng_ = operand
                with jax.named_scope(f"pq.schedule.{schedule.name.lower()}"):
                    return fn(hot_in, total, B, active, rng_, c.npods)

            return branch

        with jax.named_scope("pq.schedule"):
            hot, out_k, out_v, n_out = jax.lax.switch(
                new_mode,
                [run(s) for s in c.mode_schedules],
                (SCH.hot_tier(state), rng),
            )
        res = DeleteResult(SCH.attach_hot(state, hot), out_k, out_v, n_out)
        if c.eliminate:
            with jax.named_scope("pq.eliminate"):
                res = O.merge_eliminated(elim_k, elim_v, n_elim, res)

        new_stats = SmartPQStats(
            step=stats.step + 1,
            mode=new_mode,
            n_insert=n_insert,
            n_delete=n_delete,
            min_key=min_key,
            max_key=max_key,
            transitions=transitions,
            eliminated=stats.eliminated + n_elim,
            rejected=n_rejected,
            mode_steps=stats.mode_steps + (
                jnp.arange(NUM_MODES, dtype=jnp.int32) == new_mode
            ).astype(jnp.int32),
            head_refills=head_refills,
            ring_deferred=stats.ring_deferred,
        )
        out_carry = SmartPQCarry(res.state, new_stats)
        if return_features:
            return out_carry, res, feats
        return out_carry, res

    # -- the fused-window engine ----------------------------------------------

    @functools.cached_property
    def jit_run_window(self):
        """`run_window` jitted with the carry DONATED — the scan threads the
        PQState buffers in place, so a K-step window moves the queue zero
        times (asserted via `utils.hlo.donation_aliases` in tests).  Same
        threading contract as `jit_step`."""
        return jax.jit(self.run_window, donate_argnums=(0,))

    def run_window(
        self,
        carry: SmartPQCarry,
        ops: jnp.ndarray,  # (K, B)
        keys: jnp.ndarray,  # (K, B)
        vals: jnp.ndarray,  # (K, B)
        rngs: jax.Array,  # (K,) key array, one per step
        num_clients: jnp.ndarray | int | None = None,  # scalar or (K,)
        mode_override: jnp.ndarray | int | None = None,  # scalar or (K,)
    ) -> Tuple[SmartPQCarry, WindowResult]:
        """K adaptive steps fused into one `lax.scan` — ONE device dispatch
        for K * B operations.  The body is exactly `step` (decisions, mode
        switch, elimination), so the trace is bit-identical to K sequential
        `jit_step` calls with the same rngs; only the elimination pre-pass's
        operation-log sort is hoisted in front of the scan, where it
        vectorizes over the whole (K, B) window (Pallas match kernel on
        TPU).  Float key batches are sanitized once up front (non-finite
        lanes rejected into `stats.rejected`, exactly as `step` would
        per-batch); `mode_override` (scalar or (K,), -1 = none) pins the
        mode per step — the overload controller's degradation hook."""
        c = self.config
        K, B = ops.shape
        if num_clients is None:
            num_clients = c.num_shards
        nc = jnp.broadcast_to(
            jnp.asarray(num_clients, jnp.int32), (K,)
        )

        with jax.named_scope("pq.presort"):
            if jnp.issubdtype(jnp.asarray(keys).dtype, jnp.floating):
                keys, bad = O.sanitize_keys(keys)
                n_rej = jnp.sum(bad & (ops == OP_INSERT)).astype(jnp.int32)
                carry = carry._replace(
                    stats=carry.stats._replace(
                        rejected=carry.stats.rejected + n_rej
                    )
                )

            if c.eliminate:
                ins = ops == OP_INSERT
                sk, stg = L.sort_op_log(jnp.where(ins, keys, INF_KEY))
            else:  # placeholder lanes keep the scan xs structure static
                sk = jnp.zeros((K, B), jnp.int32)
                stg = jnp.zeros((K, B), jnp.int32)

        if mode_override is None:

            def body(cr, x):
                o, k, v, r, d, sk_t, stg_t = x
                cr2, res = self.step(
                    cr, o, k, v, r, d, presorted=(sk_t, stg_t)
                )
                return cr2, (res.keys, res.vals, res.n_out, cr2.stats.mode)

            xs = (ops, keys, vals, rngs, nc, sk, stg)
        else:
            ovs = jnp.broadcast_to(
                jnp.asarray(mode_override, jnp.int32), (K,)
            )

            def body(cr, x):
                o, k, v, r, d, sk_t, stg_t, ov = x
                cr2, res = self.step(
                    cr, o, k, v, r, d, presorted=(sk_t, stg_t),
                    mode_override=ov,
                )
                return cr2, (res.keys, res.vals, res.n_out, cr2.stats.mode)

            xs = (ops, keys, vals, rngs, nc, sk, stg, ovs)

        carry, (dk, dv, dn, dm) = jax.lax.scan(body, carry, xs)
        return carry, WindowResult(dk, dv, dn, dm)

    # -- the runtime guard tier -------------------------------------------------

    def validate_carry(self, carry: SmartPQCarry) -> None:
        """Run the host-side invariant checker over the carry's state and
        raise the first structured `InvariantViolation` found.  This is the
        `SmartPQConfig.validate` guard tier's primitive: one host sync per
        call — validated serving windows and `traces.replay` use it; the
        default (validate=False) path never does."""
        from repro.core.pqueue.state import invariant_violations

        viols = invariant_violations(carry.state, first_only=True)
        if viols:
            raise viols[0]

    # -- host-dispatch variant -------------------------------------------------

    def make_mode_steps(self):
        """One independently-jitted step function per mode + the host-side
        predictor.  State layout is identical between them, so the host
        dispatcher can flip modes between calls with zero copies — the same
        no-synchronization-point property, for runtimes that want smaller
        programs than the fused lax.switch one.  The state argument is
        donated (buffer-aliased in place); callers that need to keep a state
        across a call must `jax.tree.map(jnp.copy, state)` first."""
        c = self.config

        def _mk(schedule: Schedule):
            fn = SCH.SCHEDULE_FNS[schedule]

            @functools.partial(jax.jit, donate_argnums=(0,))
            def mode_step(state: PQState, ops, keys, vals, rng):
                B = ops.shape[0]
                ins_mask = ops == OP_INSERT
                b_del = jnp.sum(ops == OP_DELETE_MIN).astype(jnp.int32)
                active = b_del
                if c.eliminate:
                    sk, stg = L.sort_op_log(
                        jnp.where(ins_mask, keys, INF_KEY)
                    )
                    elim_k, elim_v, n_elim, keep_lane = O.elim_split(
                        state, sk, stg, vals, b_del
                    )
                    ins_mask = ins_mask & keep_lane
                    active = b_del - n_elim
                st, _ = insert(state, keys, vals, mask=ins_mask)
                res = fn(st, B, active, rng, c.npods)
                if c.eliminate:
                    res = O.merge_eliminated(elim_k, elim_v, n_elim, res)
                return res

            return mode_step

        return {mode: _mk(s) for mode, s in enumerate(c.mode_schedules)}

    def predict_mode_host(
        self, num_clients: int, size: int, key_range: int, insert_frac: float
    ) -> int:
        """Offline/debug inference only — the hot path never round-trips to
        the host: `step` (and the `run_window` scan body) evaluates the same
        packed tree on-device via `classifier.inference.tree_predict`."""
        from repro.core.classifier.features import featurize

        return int(self.tree.predict(featurize(num_clients, size, key_range, insert_frac))[0])


def carry_fingerprint(carry: SmartPQCarry) -> int:
    """CRC32 over the whole carry — the PQState's physical buffers
    (`state.state_fingerprint`) chained with every stats scalar.  The
    durability layer stamps this into snapshot manifests (an end-to-end
    integrity check on top of the per-shard file CRCs) and the crash
    recovery tests use it to assert an interrupted-then-replayed run
    reconverges bit-for-bit with an uninterrupted one."""
    import zlib

    import numpy as np

    from repro.core.pqueue.state import state_fingerprint

    crc = state_fingerprint(carry.state)
    for name, leaf in zip(SmartPQStats._fields, carry.stats):
        arr = np.ascontiguousarray(np.asarray(leaf))
        crc = zlib.crc32(arr.tobytes(), zlib.crc32(name.encode(), crc))
    return crc & 0xFFFFFFFF
