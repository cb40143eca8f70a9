#!/usr/bin/env python3
"""Render eval flight-recorder traces as indented terminal waterfalls.

Input is the JSON the server serves at ``/v1/traces/<eval_id>`` (one
trace), ``/v1/traces?full=1`` (a list), or the cluster-scope
``/v1/cluster/traces[/<ref>]`` fan-in shapes.  Sources: an HTTP(S)
URL, a file path, or ``-`` for stdin.

    python tools/trace_report.py http://127.0.0.1:4646/v1/traces/abc123
    python tools/trace_report.py 'http://127.0.0.1:4646/v1/traces?full=1&slow_ms=50'
    curl -s .../v1/cluster/traces/abc123 | python tools/trace_report.py -

Output per trace: a header line (eval id, outcome, total duration,
span/drop counts), for a folded trace the eval's life split by layer
(the server's ``LAYER_OF`` table; the layers sum to the life), and one
row per span — offset from the trace root (where the eval was created:
the start of ``ingress.register``, or of ``broker.wait``), a
per-server lane tag, a depth-indented name, the span duration, its
self time (what the span owned of the eval's life: its duration less
what its children cover), a proportional bar, and the non-default
attributes — so a slow eval reads as a waterfall:

    trace 53a1b2#7 outcome=speculative 12.41ms spans=12
      layers: broker=9.80ms pipeline_wait=1.10ms bw_host=0.90ms ...
        0.00ms  [leader  ]  broker.wait               9.80ms  self=9.80ms  ====
        9.80ms  [leader  ]  broker.dequeue            0.00ms
       10.10ms  [leader  ]  replay.commit             1.20ms  self=0.31ms  ==
       10.15ms  [leader  ]    plan.queue_wait         0.08ms  self=0.08ms  =
        ...

The rows are the trace's ONE tree, depth first: a span sits under the
span that caused it (its ``parent``) even when another thread recorded
it — ``plan.evaluate`` (plan-verifier thread) under the ``replay.commit``
that submitted the plan, ``replay.speculate`` (pool thread) under the
``batch_worker.fetch`` whose rows it replays — and siblings run in
start order.

Stitched cross-server traces get one lane per ``server_id``: spans a
follower recorded and shipped back carry that follower's id in the
lane column, spans the serving server recorded itself show in the
``leader`` lane.  Remote segments are re-anchored onto the leader's
clock via wall-time deltas, so a span that lands before the trace
root or past its end is flagged ``CLOCK-SKEW?`` rather than silently
reordered — the gap is real evidence of clock disagreement between
the two servers, not of time travel.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List

BAR_WIDTH = 24
# remote segments are wall-clock re-anchored; offsets outside the
# trace's own [0, total] envelope by more than this many ms are
# flagged as clock-skew suspects instead of being trusted
SKEW_EPS_MS = 0.05


def _load(source: str):
    if source == "-":
        return json.load(sys.stdin)
    if source.startswith(("http://", "https://")):
        from urllib.request import urlopen

        with urlopen(source) as resp:  # noqa: S310 — operator tool
            return json.loads(resp.read())
    with open(source) as fh:
        return json.load(fh)


def _depths(spans: List[Dict]) -> Dict[int, int]:
    by_id = {s["id"]: s for s in spans}
    depths: Dict[int, int] = {}

    def depth(sid: int) -> int:
        if sid in depths:
            return depths[sid]
        parent = by_id[sid].get("parent")
        d = 0 if parent is None or parent not in by_id else (
            depth(parent) + 1
        )
        depths[sid] = d
        return d

    for s in spans:
        depth(s["id"])
    return depths


def _tree_order(spans: List[Dict]) -> List[Dict]:
    """Depth first over the cause links, siblings by start: a
    cross-thread child lands under its cause, not wherever its offset
    falls among another thread's spans."""
    ids = {s["id"] for s in spans}
    children: Dict[object, List[Dict]] = {}
    for s in sorted(spans, key=lambda s: s["off_ms"]):
        parent = s.get("parent")
        children.setdefault(
            parent if parent in ids else None, []
        ).append(s)
    out: List[Dict] = []
    stack = list(reversed(children.get(None, [])))
    while stack:
        s = stack.pop()
        out.append(s)
        stack.extend(reversed(children.get(s["id"], [])))
    return out


def _fmt_attrs(attrs: Dict) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(attrs.items()))


def _lane(span: Dict, local: str) -> str:
    return (span.get("attrs") or {}).get("server_id") or local


def _skew_suspect(span: Dict, total) -> bool:
    off = span.get("off_ms", 0.0)
    if off < -SKEW_EPS_MS:
        return True
    dur = span.get("dur_ms")
    if total is not None and dur is not None:
        return off + dur > total + SKEW_EPS_MS
    return False


def render_trace(trace: Dict) -> str:
    """One trace -> waterfall text (no trailing newline)."""
    spans = _tree_order(trace.get("spans") or [])
    total = trace.get("duration_ms")
    # lane name for spans the serving server recorded itself: the
    # cluster endpoint stamps the winning server as "server"
    local = trace.get("server") or "leader"
    lanes = {_lane(s, local) for s in spans}
    multi_lane = len(lanes) > 1
    skew = sum(1 for s in spans if _skew_suspect(s, total))
    header = (
        f"trace {trace.get('trace_id', trace.get('eval_id', '?'))} "
        f"outcome={trace.get('outcome')} "
        + (f"{total:.2f}ms " if total is not None else "(in flight) ")
        + f"spans={len(spans)}"
    )
    if multi_lane:
        header += f" servers={len(lanes)}"
    if trace.get("dropped"):
        header += f" dropped={trace['dropped']}"
    if trace.get("orphans"):
        header += f" ORPHANS={trace['orphans']}"
    if skew:
        header += f" CLOCK-SKEW-SUSPECT={skew}"
    if trace.get("servers"):
        # cluster fan-in pick: which peers answered the query
        reach = trace["servers"]
        bad = sorted(a for a, st in reach.items() if st != "ok")
        header += f"\n  fan-in: asked={len(reach)}" + (
            f" unreachable={','.join(bad)}" if bad else ""
        )
    if trace.get("layers_ms"):
        # a folded trace: where the eval's life went, by layer
        header += "\n  layers: " + " ".join(
            f"{layer}={ms:.2f}ms"
            for layer, ms in trace["layers_ms"].items()
            if ms
        )
    if trace.get("attrs"):
        header += "\n  " + _fmt_attrs(trace["attrs"])
    lines = [header]
    depths = _depths(spans)
    name_w = max(
        (len(s["name"]) + 2 * depths[s["id"]] for s in spans),
        default=0,
    )
    lane_w = max((len(lane) for lane in lanes), default=0)
    scale = total if total else 1.0
    # the self-time column exists only for a folded trace
    self_w = 16 if any(s.get("self_ms") for s in spans) else 0
    for s in spans:
        dur = s.get("dur_ms")
        bar = ""
        if dur and scale:
            bar = "=" * max(1, round(dur / scale * BAR_WIDTH))
        name = "  " * depths[s["id"]] + s["name"]
        dur_txt = f"{dur:.2f}ms" if dur is not None else "OPEN"
        lane_txt = (
            f"[{_lane(s, local):<{lane_w}}]  " if multi_lane else ""
        )
        self_ms = s.get("self_ms")
        self_txt = (
            f"self={self_ms:.2f}ms" if self_ms and dur else ""
        )
        row = (
            f"  {s['off_ms']:9.2f}ms  {lane_txt}{name:<{name_w}}  "
            f"{dur_txt:>10}  {self_txt:<{self_w}}{bar:<{BAR_WIDTH}}"
        )
        extras = dict(s.get("attrs") or {})
        if multi_lane:
            extras.pop("server_id", None)  # shown as the lane tag
        if s.get("cpu_ms") is not None:
            extras["cpu_ms"] = round(s["cpu_ms"], 3)
        if s.get("thread"):
            extras["thread"] = s["thread"]
        if extras:
            row += f"  {_fmt_attrs(extras)}"
        if _skew_suspect(s, total):
            row = row.rstrip() + "  CLOCK-SKEW?"
        lines.append(row.rstrip())
    return "\n".join(lines)


def render(payload) -> str:
    """A trace dict or a list of them (summaries allowed) -> text."""
    if isinstance(payload, dict) and isinstance(
        payload.get("traces"), list
    ):
        # /v1/cluster/traces fan-in envelope: unwrap, keep the
        # per-server reachability as a trailer
        parts = [render(payload["traces"])]
        reach = payload.get("servers") or {}
        bad = sorted(a for a, st in reach.items() if st != "ok")
        if reach:
            parts.append(
                f"fan-in: asked={len(reach)}"
                + (f" unreachable={','.join(bad)}" if bad else "")
            )
        return "\n\n".join(p for p in parts if p)
    if isinstance(payload, list):
        parts = []
        for entry in payload:
            if isinstance(entry.get("spans"), list):
                parts.append(render_trace(entry))
            else:
                # listing without ?full=1: summaries only
                dur = entry.get("duration_ms")
                where = (
                    f" server={entry['server']}"
                    if entry.get("server")
                    else ""
                )
                parts.append(
                    f"trace {entry.get('trace_id')} "
                    f"outcome={entry.get('outcome')} "
                    + (
                        f"{dur:.2f}ms "
                        if dur is not None
                        else "(in flight) "
                    )
                    + f"spans={entry.get('spans')}"
                    + where
                    + " (fetch /v1/traces/<eval_id> for the waterfall)"
                )
        return "\n\n".join(parts)
    return render_trace(payload)


def main(argv: List[str]) -> int:
    if len(argv) != 2 or argv[1] in ("-h", "--help"):
        print(__doc__, file=sys.stderr)
        return 2
    print(render(_load(argv[1])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
