"""Plain reference of one ESDP dispatch slot, written from the paper.

It imports nothing of the program under test (``repro``) and takes from it
nothing but the deployment's plain arrays (edges, requirements, capacities,
costs, valuation means and spreads), which the configuration's generator
makes.  The capacity-state encoding, the DP, the packing and the accounting
are built here from their definitions:

* admission and the bounded FIFO — arrivals on a port with no edge that
  fits the cluster are dead-lettered; a port's FIFO holds
  ``queue_capacity`` jobs (ids are arrival slots), and on overflow the
  port's oldest job is dropped;
* statistics — paper eqs. 13–15 with g(t) = ln(t+1) and the default δ(t),
  evaluated in float32 in the order the configuration states, with the
  finite dominance bonus (m+1)·⌈ξ²g/2⌉ for unexplored edges;
* Algorithm 2 — the layered budgeted DP over (capacity state, budget s)
  for all s at once, edges folded from the last to the first, an edge
  taken only when strictly better, s* = argmax over feasible s ≤ ξ·m of
  s + √V(s), then the walk from edge 0 upward;
* packing — one job per port (the port's best-ranked chosen edge) in the
  order utility desc, oldest head, least-loaded server, edge index, each
  start checked against the residual capacity;
* accounting — realized welfare, regret against the per-slot omniscient
  knapsack, the bandit statistics and the per-server dispatch share.

Integer work runs in numpy on the host.  The float32 statistics and the
valuations run in ``jax.numpy`` on the device the program uses, so that
division, logarithm and square root round as the program's do; the DP runs
there too, in plain ``jax.numpy`` with exact int32 values, so that it is
fast enough at S = 43,345.  ``precision="bfloat16"`` computes the float
statistics one precision lower: that is the control, which has to fail.
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference"]

NEG = -(2 ** 30)  # no selection reaches this budget; any 5 gains stay < 0
EMPTY = -1  # no job in this place of the FIFO
LEDGER = ("arrivals", "rejected", "blocked", "dropped", "shed", "admitted",
          "dispatched", "queue_len")


def _delta(t, one):
    """δ(t) = 1 / (ln(ln(t+1)+1)+1), the paper's default relaxation."""
    return one / (jnp.log(jnp.log(t + one) + one) + one)


class Reference:
    """The slot semantics of one deployment, driven free or teacher-forced."""

    def __init__(
        self,
        *,
        edges,
        A,
        c,
        cost,
        mu,
        sigma,
        alpha,
        T,
        queue_capacity,
        backpressure="drop_oldest",
        precision="float32",
    ):
        if backpressure != "drop_oldest":
            raise NotImplementedError(
                f"the reference implements drop_oldest only, not "
                f"{backpressure!r}")
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.edges = np.asarray(edges, np.int64)
        self.port = self.edges[:, 0]
        self.server = self.edges[:, 1]
        self.A = np.asarray(A, np.int64)
        self.c = np.asarray(c, np.int64)
        self.cost = np.asarray(cost, np.float32)
        self.mu = np.asarray(mu, np.float32)
        self.sigma = np.asarray(sigma, np.float32)
        self.E = self.edges.shape[0]
        self.P = int(self.port.max()) + 1 if self.E else 0
        self.R = int(self.server.max()) + 1 if self.E else 0
        self.m = max(1, math.ceil(alpha * self.E))
        self.Q = int(queue_capacity)
        self.low = precision == "bfloat16"
        self.fdt = jnp.bfloat16 if self.low else jnp.float32

        # capacity states: every vector 0 ≤ cap ≤ c, last type fastest
        caps = np.array(list(itertools.product(
            *[range(int(k) + 1) for k in self.c])), np.int64)
        self.C = caps.shape[0]
        strides = np.array([int(np.prod(self.c[k + 1:] + 1))
                            for k in range(len(self.c))], np.int64)
        self.feas = np.all(caps[None, :, :] >= self.A.T[:, None, :],
                           axis=2)  # (E, C): edge fits in that state
        self.off = (self.A.T * strides[None, :]).sum(axis=1)  # (E,)
        self.full = self.C - 1  # the state holding all of c
        self.port_ok = np.zeros(max(self.P, 1), bool)
        np.logical_or.at(self.port_ok, self.port, self.feas[:, self.full])
        # budget axis: ξ(T)·m + 1 rows, ξ(T) evaluated in float64
        delta_T = 1.0 / (math.log(math.log(T + 1.0) + 1.0) + 1.0)
        self.S = math.ceil(self.m / delta_T) * self.m + 1

    @classmethod
    def for_instance(cls, inst, T, engine, precision="float32"):
        """From a generated instance and the configuration's engine block."""
        return cls(edges=inst.edges, A=inst.A, c=inst.c, cost=inst.cost,
                   mu=inst.mu, sigma=inst.sigma, alpha=inst.alpha, T=T,
                   queue_capacity=engine["queue_capacity"],
                   backpressure=engine["backpressure"], precision=precision)

    # -- float work on the device -------------------------------------
    def _round(self, a):
        """Round float32 values to the statistics' precision."""
        if not self.low:
            return np.asarray(a, np.float32)
        return np.asarray(a, np.float32).astype(jnp.bfloat16).astype(
            np.float32)

    @functools.cached_property
    def _valuations(self):
        mu, cost, sigma = (jnp.asarray(self.mu), jnp.asarray(self.cost),
                           jnp.asarray(self.sigma))

        @jax.jit
        def fn(noise):
            mean = mu - cost  # every server at speed 1
            z = jnp.clip(mean + sigma * noise, 0.0, 1.0)
            return z, jnp.broadcast_to(jnp.clip(mean, 0.0, 1.0), z.shape)

        return fn

    def valuations(self, noise):
        """(realized z (N, E), true means (N, E)) for the slots' noise."""
        z, v = self._valuations(jnp.asarray(noise, jnp.float32))
        return self._round(z), np.asarray(v, np.float32)

    @functools.cached_property
    def _solve(self):
        m, S, C, fdt = self.m, self.S, self.C, self.fdt
        feas = jnp.asarray(self.feas)
        off = jnp.asarray(self.off, jnp.int32)
        full = self.full

        @jax.jit
        def fn(n, sumz, t0, elig):
            one, two = jnp.asarray(1.0, fdt), jnp.asarray(2.0, fdt)
            t = (t0 + 1).astype(fdt)
            xi = jnp.ceil(m / _delta(t, one)).astype(jnp.int32)
            g = jnp.log(t + one)
            xif = xi.astype(fdt)
            nf = jnp.maximum(n, 1).astype(fdt)
            vhat = jnp.where(n > 0, sumz.astype(fdt) / nf, 0.0).astype(fdt)
            ups = jnp.ceil(xif * vhat).astype(jnp.int32)
            bonus = (m + 1) * jnp.ceil(xif * xif * g / two).astype(jnp.int32)
            sig = jnp.where(n > 0, jnp.ceil(xif * xif * g / (two * nf)).astype(
                jnp.int32), bonus)
            s_limit = xi * m

            take_ok = feas & elig[:, None]  # (E, C)
            v0 = jnp.full((C, S), NEG, jnp.int32).at[:, 0].set(0)

            def fold(V, edge):
                u, gain, ok, o = edge
                # V[c, max(s-u, 0)]: a start past the pad clamps to 0,
                # which is right, since then every s maps to s' = 0
                padded = jnp.concatenate(
                    [jnp.broadcast_to(V[:, :1], (C, S)), V], axis=1)
                shifted = jax.lax.dynamic_slice(padded, (0, S - u), (C, S))
                src = jnp.roll(shifted, o, axis=0)  # state c − off_e
                take = jnp.where(ok[:, None], src + gain, NEG)
                return jnp.maximum(V, take), take > V

            V, dec = jax.lax.scan(
                fold, v0, (ups[::-1], sig[::-1], take_ok[::-1], off[::-1]))
            dec = dec[::-1]
            row = V[full]
            s_idx = jnp.arange(S, dtype=jnp.int32)
            ok = (row >= 0) & (s_idx <= s_limit)
            score = s_idx.astype(jnp.float32) + jnp.sqrt(
                jnp.maximum(row, 0).astype(jnp.float32))
            s_star = jnp.argmax(jnp.where(ok, score, -jnp.inf)).astype(
                jnp.int32)

            def walk(pos, edge):
                s, cs = pos
                plane, u, o = edge
                d = plane[cs, s]
                return (jnp.where(d, jnp.maximum(s - u, 0), s),
                        jnp.where(d, cs - o, cs)), d.astype(jnp.int32)

            _, x = jax.lax.scan(walk, (s_star, jnp.int32(full)),
                                (dec, ups, off))
            return x, vhat.astype(jnp.float32)

        return fn

    def solve(self, n, sumz, t0, elig):
        """Algorithm-1 step 8: the DP's chosen edges and the mean estimates."""
        x, vhat = self._solve(jnp.asarray(n, jnp.int32),
                              jnp.asarray(sumz, jnp.float32),
                              jnp.int32(t0), jnp.asarray(elig))
        return np.asarray(x), np.asarray(vhat)

    # -- integer work on the host -------------------------------------
    def fresh(self):
        """(queue, n, sumz, load) at the start of a trace."""
        return (np.full((self.P, self.Q), EMPTY, np.int64),
                np.zeros(self.E, np.int64), np.zeros(self.E, np.float32),
                np.zeros(self.R, np.int64))

    def admit(self, queue, arrived_raw, t0):
        """Dead-letter, enqueue and drop; returns (queue, counts)."""
        arrived_raw = np.asarray(arrived_raw, bool)
        arrived = arrived_raw & self.port_ok
        q = queue.copy()
        counts = {"arrivals": int(arrived_raw.sum()),
                  "rejected": int((arrived_raw & ~self.port_ok).sum()),
                  "blocked": 0, "dropped": 0, "shed": 0, "admitted": 0}
        for l in np.flatnonzero(arrived):
            if (q[l] >= 0).sum() >= self.Q:
                q[l] = np.append(q[l, 1:], EMPTY)
                counts["dropped"] += 1
            q[l, (q[l] >= 0).sum()] = t0
            counts["admitted"] += 1
        return q, counts

    def eligible(self, queue, t0):
        """(elig (E,), age (P,)): an edge is open when its port has a head."""
        has = queue[:, 0] >= 0
        age = np.where(has, t0 - queue[:, 0], 0)
        return has[self.port], age

    def pack(self, x_raw, elig, vhat, age, load):
        """One job per port, capacity-checked in priority order."""
        E = self.E
        cand = (np.asarray(x_raw) > 0) & elig
        order = np.lexsort((np.arange(E), load[self.server],
                            -age[self.port].astype(np.float32),
                            -np.asarray(vhat, np.float32)))
        rank = np.empty(E, np.int64)
        rank[order] = np.arange(E)
        best = np.full(self.P, E, np.int64)
        np.minimum.at(best, self.port, np.where(cand, rank, E))
        chosen = cand & (rank == best[self.port])
        res = self.c.copy()
        x = np.zeros(E, np.int64)
        for e in order:
            if chosen[e] and np.all(res >= self.A[:, e]):
                x[e] = 1
                res -= self.A[:, e]
        return x

    def settle(self, state, q2, x, z_t):
        """Apply one slot's dispatch x; returns (state, sw, share, served)."""
        _, n, sumz, load = state
        served = np.zeros(self.P, bool)
        np.logical_or.at(served, self.port, x > 0)
        q3 = np.where(served[:, None],
                      np.concatenate([q2[:, 1:], np.full((self.P, 1), EMPTY)],
                                     axis=1), q2)
        gain = (x * z_t).astype(np.float32)
        sw = np.sum(gain, dtype=np.float32)
        share = np.zeros(self.R, np.float32)
        np.add.at(share, self.server,
                  (x / max(int(x.sum()), 1)).astype(np.float32))
        n = n + x
        sumz = self._round(sumz + gain)
        load = load + np.bincount(self.server, weights=x,
                                  minlength=self.R).astype(np.int64)
        return (q3, n, sumz, load), sw, share, served

    def oracle(self, v_true, elig):
        """(N, E) per-slot optimum of max vᵀx s.t. Ax ≤ c over open edges."""
        N, E = v_true.shape
        states = np.arange(self.C)
        V = np.zeros((N, self.C), np.float32)
        decs = [None] * E
        for e in reversed(range(E)):
            src = np.clip(states - self.off[e], 0, None)
            take = V[:, src] + v_true[:, e:e + 1]
            ok = self.feas[e][None, :] & elig[:, e:e + 1]
            take = np.where(ok, take, np.float32(-1e30))
            decs[e] = take > V
            V = np.maximum(V, take)
        cs = np.full(N, self.full)
        x = np.zeros((N, E), np.int64)
        rows = np.arange(N)
        for e in range(E):
            d = decs[e][rows, cs]
            x[:, e] = d
            cs = np.where(d, cs - self.off[e], cs)
        return x

    # -- runs ---------------------------------------------------------
    def replay(self, arrivals, noise):
        """Run one trace free from a fresh state; per-slot outputs."""
        T = arrivals.shape[0]
        z, v_true = self.valuations(noise)
        state = self.fresh()
        out = {k: np.zeros(T, np.int64) for k in LEDGER}
        out.update(sw=np.zeros(T, np.float32),
                   share=np.zeros((T, self.R), np.float32))
        x_all = np.zeros((T, self.E), np.int64)
        elig_all = np.zeros((T, self.E), bool)
        for t0 in range(T):
            q2, counts = self.admit(state[0], arrivals[t0], t0)
            elig, age = self.eligible(q2, t0)
            x_raw, vhat = self.solve(state[1], state[2], t0, elig)
            x = self.pack(x_raw, elig, vhat, age, state[3])
            state, sw, share, served = self.settle(state, q2, x, z[t0])
            for k, v in counts.items():
                out[k][t0] = v
            out["dispatched"][t0] = served.sum()
            out["queue_len"][t0] = (state[0] >= 0).sum()
            out["sw"][t0], out["share"][t0] = sw, share
            x_all[t0], elig_all[t0] = x, elig
        x_star = self.oracle(v_true, elig_all)
        out["regret"] = (np.sum(v_true * x_star, axis=1, dtype=np.float32)
                         - np.sum(v_true * x_all, axis=1, dtype=np.float32))
        out["routed"] = out["admitted"][:, None].copy()
        out["n"], out["sumz"] = state[1][None, :], state[2][None, :]
        out["x"] = x_all
        return out

    def follow(self, arrivals, noise, x_prog, solve_at):
        """Teacher-forced over one trace: the state follows the program's
        own dispatches ``x_prog`` (N, E), the ledger is recomputed for every
        slot, and the dispatch is solved afresh at the slots ``solve_at``.

        Returns (per-slot ledger dict, {slot: reference dispatch},
        final n, final sumz)."""
        N = x_prog.shape[0]
        z, _ = self.valuations(noise)
        state = self.fresh()
        out = {k: np.zeros(N, np.int64) for k in LEDGER}
        solved = {}
        want = set(int(i) for i in solve_at)
        for t0 in range(N):
            q2, counts = self.admit(state[0], arrivals[t0], t0)
            if t0 in want:
                elig, age = self.eligible(q2, t0)
                x_raw, vhat = self.solve(state[1], state[2], t0, elig)
                solved[t0] = self.pack(x_raw, elig, vhat, age, state[3])
            x = np.asarray(x_prog[t0], np.int64)
            state, _, _, served = self.settle(state, q2, x, z[t0])
            for k, v in counts.items():
                out[k][t0] = v
            out["dispatched"][t0] = served.sum()
            out["queue_len"][t0] = (state[0] >= 0).sum()
        return out, solved, state[1], state[2]
