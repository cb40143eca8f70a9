"""The one general traffic generator: a stream of job registrations
made from a configuration's job and a traffic file's parameters.

A traffic file (``traffic/<name>.json``) holds data only:

    loop          "closed" (a fixed number of registrations in flight,
                  a finished slot refilled at once) or "open" (evenly
                  spaced registrations at a fixed rate)
    in_flight     closed loop: registrations in flight
    rate_per_s    open loop: registrations a second
    warmup_evals  closed loop: completions before the window may open
    warmup_s      open loop: seconds of the stream before the window
    senders       HTTP connections the generator holds open
    mix           job variants: [{"share": k, "count": c | null}, ...];
                  ``count`` overrides the configuration's job count
    probe_ramp    set-up: closed loops of count-1 copies of the job,
                  [[in flight, completions], ...] in turn, so that the
                  launch shapes compile on the cheapest evaluations

The seed orders the stream and never resamples how much work it holds:
the stream is a repetition of one block in which variant ``k`` appears
``share_k`` times, and the seed only permutes each block.  Every prefix
of whole blocks of every seed holds the same job shapes in the same
proportions.
"""
from __future__ import annotations

import copy
import json

import numpy as np

ID_MARK = "@@JOB-ID@@"
PIN_TARGET = "${node.unique.name}"


class JobStream:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.template = config["job"]
        self.mix = traffic.get("mix") or [{"share": 1, "count": None}]
        self.block = [
            k for k, v in enumerate(self.mix) for _ in range(int(v["share"]))
        ]
        self.seed = int(seed)
        self._variants = [self._variant(v.get("count")) for v in self.mix]
        self._perm_cache: dict = {}

    def _variant(self, count):
        """(payload dict with the id mark, placements, body halves)."""
        payload = copy.deepcopy(self.template)
        payload["id"] = ID_MARK
        if count is not None:
            payload["task_groups"][0]["count"] = int(count)
        placements = sum(int(tg["count"]) for tg in payload["task_groups"])
        head, tail = json.dumps({"Job": payload}).split(ID_MARK)
        return payload, placements, head.encode(), tail.encode()

    def _variant_at(self, i: int) -> int:
        b, pos = divmod(i, len(self.block))
        if len(self.block) == 1:
            return self.block[0]
        perm = self._perm_cache.get(b)
        if perm is None:
            perm = np.random.default_rng([self.seed, 0x57EA, b]).permutation(
                len(self.block)
            )
            self._perm_cache[b] = perm
        return self.block[int(perm[pos])]

    def job_id(self, i: int, prefix: str = "job") -> str:
        return f"{prefix}-{i:07d}"

    def payload(self, i: int, prefix: str = "job") -> dict:
        """The i-th registration's job, as the client sends it."""
        payload = copy.deepcopy(self._variants[self._variant_at(i)][0])
        payload["id"] = self.job_id(i, prefix)
        return payload

    def body(self, i: int, prefix: str = "job") -> bytes:
        """The same job as the bytes of ``POST /v1/jobs``."""
        _p, _n, head, tail = self._variants[self._variant_at(i)]
        return head + self.job_id(i, prefix).encode() + tail

    def placements(self, i: int) -> int:
        return self._variants[self._variant_at(i)][1]

    def shape_counts(self, n: int) -> dict:
        """How many of the first ``n`` registrations each variant has."""
        out: dict = {}
        for i in range(n):
            k = self._variant_at(i)
            out[k] = out.get(k, 0) + 1
        return out


class ProbeStream:
    """Count-1 copies of a stream's first variant, each pinned to one
    node by ``${node.unique.name} = <name>``: the same launch shapes as
    the job itself, on the cheapest evaluation there is.  A launch shape
    that is not compiled yet sends its evaluations down the host path,
    which then scores one node and not the fleet.  ``pins``: node names,
    one a probe in turn."""

    def __init__(self, stream: JobStream, pins) -> None:
        self.template = copy.deepcopy(stream._variants[0][0])
        for tg in self.template["task_groups"]:
            tg["count"] = 1
        self.pins = list(pins)

    def payload(self, i: int, prefix: str = "probe") -> dict:
        payload = copy.deepcopy(self.template)
        payload["id"] = f"{prefix}-{i:07d}"
        payload["constraints"] = list(payload.get("constraints") or []) + [{
            "ltarget": PIN_TARGET, "operand": "=",
            "rtarget": self.pins[i % len(self.pins)],
        }]
        return payload

    def body(self, i: int, prefix: str = "probe") -> bytes:
        return json.dumps({"Job": self.payload(i, prefix)}).encode()

    def placements(self, i: int) -> int:
        return len(self.template["task_groups"])
