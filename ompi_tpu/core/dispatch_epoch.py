"""The dispatch epoch: one process-wide integer that invalidates every
memoized collective route.

``Communicator.allreduce`` memoizes the plan the tuned decision layer
routed for a ``(shape, dtype, op)`` (its *lane*, docs/DESIGN.md §2) and
serves a repeat call after one compare of the entry's epoch against
``value``. Every state change that could route the same call elsewhere,
or that adds a per-call hook the lane would skip, calls ``bump()``:

- a config mutation (``core/config``: set, set_if_unset, a params file,
  a reset);
- circuit-breaker activity (``coll/breaker``: a failure, a success that
  closes a tier, a restore, a reset);
- a health-ledger transition (``health/ledger``);
- a content change of the schedule cache (``coll/sched/cache``) and an
  SLO target change (``coll/sched/slo``);
- arming or disarming faultline (``ft/inject``);
- enabling the memchecker or MONITOR (cvars, so a config mutation) and
  enabling or disabling the sanitizer (``analysis/sanitizer``);
- re-selecting a communicator's coll vtable.

Readers load ``value`` without a lock (one module attribute): a bump
that races a lookup is seen by the next call. A memo entry records the
epoch it was read under *before* its route was built, so a bump during
the build leaves the entry stale, never fresh.
"""

from __future__ import annotations

import threading

value = 0
_lock = threading.Lock()


def bump() -> None:
    """Invalidate every memoized route in the process."""
    global value
    with _lock:
        value += 1
