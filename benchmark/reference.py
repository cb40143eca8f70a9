"""The plain reference: Nomad's generic (service) scheduler for the job
shapes the benchmark sends, written from the published algorithm
(scheduler/stack.go, rank.go, spread.go, select.go) over plain arrays.

It imports nothing of the program and takes nothing the program made
except the answers it is asked to judge: the order in which evaluations
committed (that order is part of the answer) and, per evaluation, which
node each named allocation landed on.  Fleet, resident allocations and
job specifications are the benchmark's own, made from the seed.

Semantics, per evaluation of a freshly registered job, in commit order:

* candidates: ready nodes of the job's datacenters, in registration
  order, shuffled by ``numpy.random.default_rng(s).permutation`` with
  ``s = random.Random(server_seed).randrange(2**32)`` (the deployment
  states its scheduler seed; every evaluation starts from it);
* per placement, walk the shuffled ring from where the last placement
  stopped; skip nodes the ask does not fit on (cpu, memory, disk after
  the node's reserved share); score = mean of the appended terms:
  binpack ``(20 - 10^freeCpu - 10^freeMem) / 18`` clamped to [0, 18]/18
  with ``10^x`` rounded through float32, job anti-affinity
  ``-(collisions + 1) / count`` when the node already holds the job,
  node affinity ``matched / total`` weight when non-zero, spread boost
  ``(desired - used) / desired * weight / sum`` when non-zero;
* emit at most ``limit`` scored nodes (``max(2, ceil(log2 N))``, or all
  of them when the job has a spread or an affinity), diverting up to 3
  nodes that score <= 0 to the end; the first strict maximum wins;
* the placement joins the plan and is seen by the next one.

``precision`` is the control's switch: ``"float64"`` is the reference;
``"float32"`` and ``"bfloat16"`` round every score term to that type,
the precisions below the one a configuration states.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field

import numpy as np

from .world import World

MAX_SKIP = 3
SKIP_THRESHOLD = 0.0
BINPACK_MAX = 18.0


@dataclass(frozen=True)
class JobSpec:
    """What the scheduler reads of a job payload (one task group)."""

    job_id: str
    group: str
    count: int
    cpu: int
    mem: int
    disk: int
    datacenters: tuple
    # (attribute, weight, ((value, percent), ...))
    spreads: tuple = ()
    # (ltarget, operand, rtarget, weight)
    affinities: tuple = ()
    # ``${node.unique.name} = n<i>``: the one node left feasible, or -1
    only_node: int = -1

    @staticmethod
    def from_payload(payload: dict) -> "JobSpec":
        groups = payload["task_groups"]
        if len(groups) != 1:
            raise ValueError("the reference places one task group a job")
        tg = groups[0]
        only_node = -1
        for c in list(payload.get("constraints") or []) + list(
            tg.get("constraints") or []
        ):
            what = (c["ltarget"], c["operand"], c["rtarget"])
            if what == ("${attr.kernel.name}", "=", "linux"):
                continue  # every node of the fleet is linux
            if (
                what[:2] == ("${node.unique.name}", "=")
                and only_node < 0
                and what[2][:1] == "n"
                and what[2][1:].isdigit()
            ):
                only_node = int(what[2][1:])
                continue
            raise ValueError(f"constraint outside the reference: {c}")
        if tg.get("networks") or tg.get("volumes"):
            raise ValueError("networks/volumes are outside the reference")
        cpu = mem = 0
        for task in tg["tasks"]:
            res = task["resources"]
            if res.get("networks") or res.get("devices"):
                raise ValueError("task networks/devices outside the reference")
            if task.get("constraints") or task.get("affinities"):
                raise ValueError("task constraints outside the reference")
            cpu += int(res["cpu"])
            mem += int(res["memory_mb"])
        if tg.get("affinities") or tg.get("spreads"):
            raise ValueError("group-level affinity/spread outside the reference")
        spreads = tuple(
            (
                s["attribute"],
                int(s["weight"]),
                tuple((t["value"], int(t["percent"])) for t in s["targets"]),
            )
            for s in payload.get("spreads") or []
        )
        affinities = tuple(
            (a["ltarget"], a["operand"], a["rtarget"], int(a["weight"]))
            for a in payload.get("affinities") or []
        )
        for attr, _w, _t in spreads:
            if attr != "${node.datacenter}":
                raise ValueError(f"spread attribute outside the reference: {attr}")
        for lt, op, _rt, _w in affinities:
            if lt != "${node.datacenter}" or op != "=":
                raise ValueError("affinity outside the reference")
        return JobSpec(
            job_id=payload["id"],
            group=tg["name"],
            count=int(tg["count"]),
            cpu=cpu,
            mem=mem,
            disk=int(tg["ephemeral_disk"]["size_mb"]),
            datacenters=tuple(payload["datacenters"]),
            spreads=spreads,
            affinities=affinities,
            only_node=only_node,
        )


def visit_limit(n_nodes: int) -> int:
    """Service jobs: max(2, ceil(log2 N)) (stack.go:77)."""
    limit = 2
    if n_nodes > 0:
        limit = max(limit, int(math.ceil(math.log2(n_nodes))))
    return limit


def _rounder(precision: str):
    if precision == "float64":
        return lambda x: x
    if precision == "float32":
        return lambda x: np.asarray(x).astype(np.float32).astype(np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        return lambda x: np.asarray(x).astype(bf16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


@dataclass
class RefCluster:
    world: World
    server_seed: int
    precision: str = "float64"
    used_cpu: np.ndarray = field(init=False)
    used_mem: np.ndarray = field(init=False)
    used_disk: np.ndarray = field(init=False)

    def __post_init__(self) -> None:
        w = self.world
        n = w.n_nodes
        rc, rm, rd = w.reserved
        self.cap_cpu = (w.node_cpu - rc).astype(np.int64)
        self.cap_mem = (w.node_mem - rm).astype(np.int64)
        self.cap_disk = (w.node_disk - rd).astype(np.int64)
        self.used_cpu = np.bincount(
            w.alloc_node, weights=w.alloc_cpu, minlength=n
        ).astype(np.int64)
        self.used_mem = np.bincount(
            w.alloc_node, weights=w.alloc_mem, minlength=n
        ).astype(np.int64)
        self.used_disk = (
            np.bincount(w.alloc_node, minlength=n).astype(np.int64)
            * w.alloc_disk
        )
        self._orders: dict = {}
        self._where: dict = {}  # datacenters -> {node: ring position}
        self._q = _rounder(self.precision)

    # -- the shuffled ring ---------------------------------------------

    def _order(self, datacenters: tuple) -> np.ndarray:
        order = self._orders.get(datacenters)
        if order is None:
            w = self.world
            member = np.isin(
                w.node_dc,
                [i for i, d in enumerate(w.datacenters) if d in datacenters],
            )
            nodes = np.flatnonzero(member)
            s = random.Random(self.server_seed).randrange(2**32)
            perm = np.random.default_rng(s).permutation(len(nodes))
            order = nodes[perm]
            self._orders[datacenters] = order
        return order

    # -- scores --------------------------------------------------------

    def _pow10(self, x):
        return self._q(
            np.float32(np.power(10.0, np.asarray(x, np.float64))).astype(
                np.float64
            )
        )

    def _binpack(self, idx, job: JobSpec):
        """Normalised binpack score of nodes ``idx`` with the ask added."""
        q = self._q
        ucpu = (self.used_cpu[idx] + job.cpu).astype(np.float64)
        umem = (self.used_mem[idx] + job.mem).astype(np.float64)
        free_cpu = q(1.0 - q(ucpu / self.cap_cpu[idx].astype(np.float64)))
        free_mem = q(1.0 - q(umem / self.cap_mem[idx].astype(np.float64)))
        total = q(self._pow10(free_cpu) + self._pow10(free_mem))
        score = np.clip(q(20.0 - total), 0.0, BINPACK_MAX)
        return q(score / BINPACK_MAX)

    def _fits(self, idx, job: JobSpec):
        """Feasible and with room for the ask.  A node the constraint
        rules out and one that is full are both passed over before the
        limit counts them (feasible.go and rank.go sit under select.go)."""
        fits = (
            (self.used_cpu[idx] + job.cpu <= self.cap_cpu[idx])
            & (self.used_mem[idx] + job.mem <= self.cap_mem[idx])
            & (self.used_disk[idx] + job.disk <= self.cap_disk[idx])
        )
        if job.only_node >= 0:
            fits = fits & (np.asarray(idx) == job.only_node)
        return fits

    def _penalty(self, idx, job: JobSpec, coll: dict):
        """Job anti-affinity term and whether it is appended."""
        if not coll:
            return 0.0, 0
        c = np.fromiter((coll.get(int(i), 0) for i in idx), np.int64, len(idx))
        pen = np.where(
            c > 0,
            self._q(-1.0 * (c + 1).astype(np.float64) / float(job.count)),
            0.0,
        )
        return pen, (c > 0).astype(np.float64)

    def _affinity(self, idx, job: JobSpec):
        """Node-affinity term and whether it is appended."""
        if not job.affinities:
            return 0.0, 0
        w = self.world
        sum_w = sum(abs(float(a[3])) for a in job.affinities)
        matched = np.zeros(len(idx), np.float64)
        for _lt, _op, rtarget, weight in job.affinities:
            if rtarget in w.datacenters:
                hit = w.node_dc[idx] == w.datacenters.index(rtarget)
                matched = matched + np.where(hit, float(weight), 0.0)
        aff = self._q(matched / sum_w)
        return np.where(matched != 0.0, aff, 0.0), (matched != 0.0).astype(
            np.float64
        )

    def _spread(self, idx, job: JobSpec, dc_used: dict):
        """Spread boost and whether it is appended."""
        if not job.spreads:
            return 0.0, 0
        w = self.world
        sum_weights = float(sum(s[1] for s in job.spreads))
        boost_dc = np.zeros(len(w.datacenters), np.float64)
        for _attr, weight, targets in job.spreads:
            desired = {}
            sum_desired = 0.0
            for value, percent in targets:
                d = (float(percent) / 100.0) * float(job.count)
                desired[value] = d
                sum_desired += d
            if 0 < sum_desired < float(job.count):
                desired["*"] = float(job.count) - sum_desired
            for k, dc in enumerate(w.datacenters):
                want = desired.get(dc, desired.get("*"))
                if want is None:
                    boost_dc[k] -= 1.0
                    continue
                used = float(dc_used.get(dc, 0) + 1)
                boost_dc[k] += ((want - used) / want) * (
                    float(weight) / sum_weights
                )
        boost = self._q(boost_dc)[w.node_dc[idx]]
        return boost, (boost != 0.0).astype(np.float64)

    def _mean(self, binpack, pen, aff, boost):
        """Mean of the appended terms, summed left to right in the
        order the scheduler appends them (a term that is not appended
        adds an exact 0.0)."""
        q = self._q
        total = q(q(q(binpack + pen[0]) + aff[0]) + boost[0])
        return q(total / (1.0 + pen[1] + aff[1] + boost[1]))

    def _scores(self, idx, job: JobSpec, coll: dict, dc_used: dict):
        """Final scores of nodes ``idx`` (array)."""
        return self._mean(
            self._binpack(idx, job),
            self._penalty(idx, job, coll),
            self._affinity(idx, job),
            self._spread(idx, job, dc_used),
        )

    # -- one evaluation ------------------------------------------------

    def place(self, job: JobSpec, served=None):
        """Place ``job.count`` allocations of a new job, placement by
        placement.  Returns (picks, gaps): the node the reference chooses
        for each allocation index (-1 where nothing fits) and, where
        ``served`` is given, how far the served node's score lies below
        the best candidate's, relative (0.0 where they are the same
        node; ``inf`` where the served node is no candidate at all).

        With ``served`` (node index per allocation index, -1 for none)
        each placement is judged against the state the served history
        implies: the SERVED node joins the plan and the cluster's usage,
        not the reference's own choice.  Without it the reference's own
        placements do."""
        order = self._order(job.datacenters)
        if job.affinities or job.spreads:
            return self._place_full(order, job, served)
        limit = visit_limit(len(order))
        coll: dict = {}
        offset = 0
        picks, gaps = [], []
        for k in range(job.count):
            node, offset, emitted = self._pick_limited(
                order, offset, job, coll, {}, limit
            )
            picks.append(node)
            take = node
            if served is not None:
                take = served[k]
                gaps.append(self._gap(node, take, dict(emitted)))
            if take >= 0:
                self.commit(take, job)
                coll[take] = coll.get(take, 0) + 1
        return picks, gaps

    @staticmethod
    def _gap(best: int, served: int, scores: dict) -> float:
        if served == best:
            return 0.0
        if served < 0 or best < 0 or served not in scores:
            return math.inf
        top = scores[best]
        return (top - scores[served]) / max(abs(top), 1e-300)

    def commit(self, node: int, job: JobSpec, sign: int = 1) -> None:
        """One allocation of ``job`` onto (or, sign=-1, off) ``node``."""
        self.used_cpu[node] += sign * job.cpu
        self.used_mem[node] += sign * job.mem
        self.used_disk[node] += sign * job.disk

    def _place_full(self, order, job: JobSpec, served=None):
        """Every candidate scored for every placement (spread/affinity
        lift the limit), so each placement takes one whole turn of the
        ring from its start.  Between placements only the chosen node's
        usage and the per-datacenter counts change, so the ring's
        binpack scores are kept and patched."""
        n = len(order)
        where = self._where.get(job.datacenters)
        if where is None:
            where = {int(node): at for at, node in enumerate(order.tolist())}
            self._where[job.datacenters] = where
        fit = self._fits(order, job)
        binpack = np.asarray(self._binpack(order, job), np.float64).copy()
        aff = self._affinity(order, job)
        pen_v = np.zeros(n, np.float64)
        pen_on = np.zeros(n, np.float64)
        coll: dict = {}
        dc_used: dict = {}
        picks, gaps = [], []
        for k in range(job.count):
            pos_fit = np.flatnonzero(fit)
            at = -1
            if len(pos_fit):
                boost = self._spread(order, job, dc_used)
                full = self._mean(binpack, (pen_v, pen_on), aff, boost)
                sc = full[pos_fit]
                low = np.flatnonzero(sc <= SKIP_THRESHOLD)[:MAX_SKIP]
                if len(low):
                    keep = np.ones(len(sc), bool)
                    keep[low] = False
                    seq = np.concatenate([np.flatnonzero(keep), low])
                    at = int(pos_fit[seq[int(np.argmax(sc[seq]))]])
                else:
                    at = int(pos_fit[int(np.argmax(sc))])
            node = int(order[at]) if at >= 0 else -1
            picks.append(node)
            if served is not None:
                take = served[k]
                at_s = where.get(take, -1) if take >= 0 else -1
                if take == node:
                    gaps.append(0.0)
                elif at < 0 or at_s < 0 or not fit[at_s]:
                    gaps.append(math.inf)
                else:
                    top = float(full[at])
                    gaps.append(
                        (top - float(full[at_s])) / max(abs(top), 1e-300)
                    )
                node, at = take, at_s
            if node < 0 or at < 0:
                continue
            self.commit(node, job)
            coll[node] = coll.get(node, 0) + 1
            dc = self.world.datacenters[int(self.world.node_dc[node])]
            dc_used[dc] = dc_used.get(dc, 0) + 1
            one = order[at : at + 1]
            fit[at] = bool(self._fits(one, job)[0])
            binpack[at] = float(np.asarray(self._binpack(one, job))[0])
            p1 = self._penalty(one, job, coll)
            pen_v[at] = float(p1[0][0])
            pen_on[at] = float(p1[1][0])
        return picks, gaps

    def _pick_limited(self, order, offset, job, coll, dc_used, limit):
        """The limit walk (select.go:35).  The ring is read in blocks
        scored at once: nothing changes while one placement is chosen."""
        n = len(order)
        start = offset % n if n else 0
        block = limit + 2 * MAX_SKIP + 2
        pulled = 0  # nodes drawn from the ring for this placement
        buf: list = []  # scored (node, score) of fitting nodes, in ring order
        buf_end = [0]  # ring positions scored so far

        def source_next():
            nonlocal pulled
            while True:
                while not buf:
                    if buf_end[0] >= n:
                        pulled = n
                        return None
                    k1 = min(n, buf_end[0] + block)
                    ks = np.arange(buf_end[0], k1)
                    idx = order[(start + ks) % n]
                    fit = self._fits(idx, job)
                    if fit.any():
                        sc = self._scores(idx[fit], job, coll, dc_used)
                        buf.extend(
                            zip(ks[fit].tolist(), idx[fit].tolist(), sc.tolist())
                        )
                    buf_end[0] = k1
                k, node, score = buf.pop(0)
                pulled = k + 1
                return node, score

        skipped: list = []
        skipped_at = 0

        def next_option():
            nonlocal skipped_at
            opt = source_next()
            if opt is None and skipped_at < len(skipped):
                opt = skipped[skipped_at]
                skipped_at += 1
            return opt

        best = None
        emitted = 0
        seen_opts: list = []
        while emitted < limit:
            opt = next_option()
            if opt is None:
                break
            while (
                opt is not None
                and opt[1] <= SKIP_THRESHOLD
                and len(skipped) < MAX_SKIP
            ):
                skipped.append(opt)
                opt = source_next()
            emitted += 1
            if opt is None:
                opt = next_option()
                if opt is None:
                    break
            seen_opts.append(opt)
            if best is None or opt[1] > best[1]:
                best = opt
        return (-1 if best is None else best[0]), start + pulled, seen_opts
