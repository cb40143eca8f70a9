"""The process's cycle collector while a server serves.

CPython's collector stops every thread while it walks.  A serving
process holds a large heap that lives as long as the server (the fleet,
its resident allocations, every committed placement), and the default
collector walks all of it again each time the objects that survived
young collections pass a quarter of the heap's size.  While at least one
``Server`` runs, the process's collector follows three rules instead:

* a young collection runs every ``YOUNG`` net allocations, not 700;
* whatever survives a full collection is frozen (``gc.freeze``, O(1)),
  so no later collection walks it again.  A frozen object that loses its
  last reference is still freed by its reference count; only cyclic
  garbage among frozen objects waits for
* ``reclaim_at_idle``: when a worker finds the broker empty after a
  freeze, everything is unfrozen and collected once, and the survivors
  are frozen again.  A backlog never reaches that call, so the busy path
  never pays for a walk of the whole heap.

The policy is reference-counted (``hold`` / ``release``): tests and
clusters run several servers in one process, and with none started the
collector is the interpreter's default.  Its two counts are plain module
integers (``COLLECTOR_COUNTERS``), read by a metrics registry through
``counts``; nothing here takes a clock or counts the heap.
"""
from __future__ import annotations

import gc
import threading
from typing import Dict, Optional, Tuple

# a young collection every this many net allocations of tracked objects
# (the interpreter's default is 700; PERF.md section 6 has the sweep)
YOUNG = 10_000
# full collections whose survivors were frozen; reclaims at idle
COLLECTOR_COUNTERS = ("gc.freezes", "gc.reclaims")

_lock = threading.Lock()  # hold, release and a reclaim, one at a time
_holders = 0
_found: Optional[Tuple[int, ...]] = None  # thresholds the policy found
_freezes = 0
_reclaims = 0
_pending = False  # a freeze since the last reclaim
_reclaiming = False


def _freeze_survivors(phase: str, info: dict) -> None:
    """gc.callbacks entry: a full collection's survivors are frozen.
    The reclaim's own collection re-freezes without counting, or every
    idle beat would reclaim again."""
    global _freezes, _pending
    if phase != "stop" or info["generation"] != 2:
        return
    gc.freeze()
    if not _reclaiming:
        _freezes += 1
        _pending = True


def hold() -> None:
    """A server starts: the first holder installs the policy."""
    global _holders, _found
    with _lock:
        _holders += 1
        if _holders == 1:
            _found = gc.get_threshold()
            gc.set_threshold(YOUNG, *_found[1:])
            gc.callbacks.append(_freeze_survivors)


def release() -> None:
    """A server stops: the last holder gives the interpreter back its
    collector — nothing frozen, its thresholds, no callback."""
    global _holders, _pending
    with _lock:
        if _holders == 0:
            return
        _holders -= 1
        if _holders:
            return
        gc.callbacks.remove(_freeze_survivors)
        gc.unfreeze()
        gc.set_threshold(*_found)
        _pending = False


def reclaim_at_idle() -> None:
    """A worker's dequeue came back empty: after a freeze, walk the
    whole heap once.  One worker reclaims at a time; the others go on."""
    global _pending, _reclaiming, _reclaims
    if not _pending or not _lock.acquire(blocking=False):
        return
    try:
        if not _pending:  # another worker reclaimed first
            return
        _pending = False
        _reclaiming = True
        walks = gc.get_stats()[2]["collections"]
        gc.unfreeze()
        gc.collect()  # _freeze_survivors re-freezes what survives
        if gc.get_stats()[2]["collections"] == walks:
            # another thread's collection was under way, and gc.collect
            # returned without walking: freeze back, try the next beat
            gc.freeze()
            _pending = True
            return
        _reclaims += 1
    finally:
        _reclaiming = False
        _lock.release()


def counts() -> Dict[str, float]:
    """COLLECTOR_COUNTERS as they stand, for a metrics registry to read."""
    return {
        COLLECTOR_COUNTERS[0]: float(_freezes),
        COLLECTOR_COUNTERS[1]: float(_reclaims),
    }
