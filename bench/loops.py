"""The two closed loops a traffic file can name (its "loop" key).

A loop owns the window program the timed path runs, the inputs of each
window (from `bench/generate.py`), the readback of each window's outputs,
and how those inputs and outputs are fed to the reference (`bench/check.py`).
In both, the host dispatches a window, waits for it, reads back its
popped keys and counts, and only then sends the next window.

* `ReplayLoop`: pregenerated (K, lanes) op windows through
  `SmartPQ.jit_run_window`, the program's fused-window entry.
* `HoldLoop`: the hold model. A copy of the scan body of
  `repro.workloads.des.make_hold_engine`: step t inserts the keys step t-1
  popped, each plus its hold, then pops `lanes` minima; `SmartPQ.step`
  inside one donated `lax.scan` of K steps, its carry kept across windows.
"""

from __future__ import annotations

import numpy as np

from bench import generate as G


def _wrap_keys(data):
    import jax

    return jax.random.wrap_key_data(data)


class ReplayLoop:
    def __init__(self, pq, config: dict, traffic: dict, seed: int):
        self.pq, self.traffic, self.seed = pq, traffic, seed
        self.K = traffic["steps_per_window"]
        self.m = traffic["lanes"]  # the step's static deleteMin bound
        self.cycle = len(traffic["phases"])  # windows before the mix repeats

    def split_prefill(self, keys: np.ndarray):
        return keys, keys[:0]

    def start(self, pq_carry, _):
        return pq_carry

    def pq_carry(self, carry):
        return carry

    def inputs(self, w: int, stream: int = G.STREAM_WINDOW) -> dict:
        return G.replay_window(self.traffic, self.seed, w, stream)

    def dispatch(self, carry, x):
        return self.pq.jit_run_window(carry, x["ops"], x["keys"], x["vals"],
                                      _wrap_keys(x["rng"]), x["nc"])

    @staticmethod
    def readback(out) -> dict:
        import jax

        keys, n_out, mode = jax.device_get((out.keys, out.n_out, out.mode))
        return {"keys": keys, "n_out": n_out, "mode": mode}

    @staticmethod
    def issued(records, in_service) -> tuple:
        """(insert lanes, deleteMin lanes) the windows issued."""
        del in_service
        return (sum(int(np.sum(x["ops"] == G.OP_INSERT)) for x, _ in records),
                sum(int(np.sum(x["ops"] == G.OP_DELETE_MIN))
                    for x, _ in records))

    def begin_check(self, in_service) -> None:
        del in_service

    def check(self, checker, x: dict, y: dict) -> None:
        for t in range(self.K):
            ops = x["ops"][t]
            checker.step(x["keys"][t][ops == G.OP_INSERT],
                         int(np.sum(ops == G.OP_DELETE_MIN)),
                         y["keys"][t, :int(y["n_out"][t])], int(y["mode"][t]))


class HoldLoop:
    def __init__(self, pq, config: dict, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp

        self.pq, self.config, self.traffic, self.seed = (
            pq, config, traffic, seed)
        self.K = traffic["steps_per_window"]
        B = self.B = traffic["lanes"]
        self.m = 2 * B  # insert lanes + deleteMin lanes
        self.cycle = 1
        lane = jnp.arange(B, dtype=jnp.int32)
        del_ops = jnp.full((B,), G.OP_DELETE_MIN, jnp.int32)
        del_keys = jnp.full((B,), G.INF_KEY, jnp.int32)
        zeros = jnp.zeros((B,), jnp.int32)

        def window(carry, holds, rngs):
            def body(c, x):
                pqc, prev_k, prev_n = c
                holds_t, r = x
                valid = lane < prev_n
                ins_k = jnp.where(valid, jnp.where(valid, prev_k, 0) + holds_t,
                                  G.INF_KEY)
                ops = jnp.concatenate(
                    [jnp.where(valid, G.OP_INSERT, G.OP_NOP), del_ops])
                keys = jnp.concatenate([ins_k, del_keys])
                vals = jnp.concatenate([lane, zeros])
                pqc, res = pq.step(pqc, ops, keys, vals, r, B)
                n = jnp.minimum(res.n_out, B)
                return (pqc, res.keys[:B], n), (res.keys[:B], n,
                                                 pqc.stats.mode)

            return jax.lax.scan(body, carry, (holds, rngs))

        self.window = jax.jit(window, donate_argnums=(0,))

    def split_prefill(self, keys: np.ndarray):
        """The first `lanes` events are in service at the start: the first
        step reschedules them. The rest are prefilled."""
        return keys[self.B:], keys[:self.B]

    def start(self, pq_carry, in_service):
        import jax.numpy as jnp

        return (pq_carry, jnp.asarray(in_service, jnp.int32),
                jnp.int32(len(in_service)))

    @staticmethod
    def pq_carry(carry):
        return carry[0]

    def inputs(self, w: int, stream: int = G.STREAM_WINDOW) -> dict:
        return G.hold_window(self.config, self.traffic, self.seed, w, stream)

    def dispatch(self, carry, x):
        return self.window(carry, x["holds"], _wrap_keys(x["rng"]))

    @staticmethod
    def readback(out) -> dict:
        import jax

        keys, n_out, mode = jax.device_get(out)
        return {"keys": keys, "n_out": n_out, "mode": mode}

    def issued(self, records, in_service) -> tuple:
        """(insert lanes, deleteMin lanes) the windows issued: each step
        inserts what the step before popped, the first step the events
        in service."""
        popped = sum(int(np.sum(y["n_out"])) for _, y in records)
        last = int(records[-1][1]["n_out"][-1]) if records else 0
        return (len(in_service) + popped - last,
                len(records) * self.K * self.B)

    def begin_check(self, in_service) -> None:
        self._prev = [int(k) for k in in_service]

    def check(self, checker, x: dict, y: dict) -> None:
        for t in range(self.K):
            ins = np.asarray(self._prev, np.int64) + x["holds"][t, :len(
                self._prev)]
            self._prev = checker.step(ins, self.B,
                                      y["keys"][t, :int(y["n_out"][t])],
                                      int(y["mode"][t]))


LOOPS = {"replay": ReplayLoop, "hold": HoldLoop}
