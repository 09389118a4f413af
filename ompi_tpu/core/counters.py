"""Software performance counters (SPC) + performance-variable registry.

TPU-native equivalent of Open MPI's SPC counters (reference:
ompi/runtime/ompi_spc.h:55- enum of per-op counters, SPC_RECORD at each API
entry e.g. ompi/mpi/c/allreduce.c:51) exported through an MPI_T-pvar-like
registry (reference: opal/mca/base/mca_base_pvar.c, ompi/mpi/tool/).

Counters are cheap process-local accumulators; a session can snapshot and
diff them (the MPI_T pvar handle start/stop/read model). Timer-class
counters accumulate seconds.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Iterator, Optional

#: MPI_T pvar classes (reference: mca_base_pvar.h MCA_BASE_PVAR_CLASS_*).
#: Scalar counters carry their class in the unit field; histograms are
#: their own class.
PVAR_COUNTER = "counter"
PVAR_WATERMARK = "watermark"
PVAR_TIMER = "timer"
PVAR_HISTOGRAM = "histogram"

#: unit -> scalar pvar class (hwm() registers unit="max", timer()
#: registers unit="seconds"; everything else is an event counter).
_UNIT_CLASS = {"max": PVAR_WATERMARK, "seconds": PVAR_TIMER}


def pvar_class_of(unit: str) -> str:
    """The MPI_T class tag for a scalar counter's unit."""
    return _UNIT_CLASS.get(unit, PVAR_COUNTER)


class Counter:
    __slots__ = ("name", "description", "unit", "value", "_lock")

    def __init__(self, name: str, description: str = "", unit: str = "count"):
        self.name = name
        self.description = description
        self.unit = unit
        self.value: float = 0
        self._lock = threading.Lock()

    def add(self, amount: float = 1) -> None:
        with self._lock:
            self.value += amount

    def set(self, value: float) -> None:
        with self._lock:
            self.value = value

    def read(self) -> float:
        return self.value


class Histogram:
    """Log-bucketed latency histogram — a new pvar class alongside the
    counter/watermark/timer classes (the reference's MPI_T pvar classes,
    mca_base_pvar.h). Bucket ``b`` counts samples whose duration in
    nanoseconds falls in ``[2^b, 2^(b+1))``, so 64 buckets span 1 ns to
    ~584 years with ~2x resolution — enough to read p50/p99 off a
    latency distribution without storing samples. Percentiles
    interpolate linearly inside the winning bucket, clamped to the
    observed min/max."""

    __slots__ = ("name", "description", "unit", "counts", "count",
                 "total", "min", "max", "_lock")

    NBUCKETS = 64

    def __init__(self, name: str, description: str = "",
                 unit: str = "seconds"):
        self.name = name
        self.description = description
        self.unit = unit
        self.counts = [0] * self.NBUCKETS
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        self.record_ns(int(seconds * 1e9))

    def record_ns(self, ns: int) -> None:
        if ns < 1:
            ns = 1
        b = ns.bit_length() - 1
        if b >= self.NBUCKETS:
            b = self.NBUCKETS - 1
        s = ns * 1e-9
        with self._lock:
            self.counts[b] += 1
            self.count += 1
            self.total += s
            if s < self.min:
                self.min = s
            if s > self.max:
                self.max = s

    def percentile(self, q: float) -> float:
        """Approximate q-quantile in seconds (0 when empty)."""
        with self._lock:
            if self.count == 0:
                return 0.0
            target = q * self.count
            seen = 0.0
            for b, n in enumerate(self.counts):
                if n == 0:
                    continue
                if seen + n >= target:
                    frac = (target - seen) / n
                    lo = float(1 << b)
                    est = (lo + frac * lo) * 1e-9  # within [2^b, 2^(b+1))
                    # never outside what was observed (one sample: exact)
                    return min(max(est, self.min), self.max)
                seen += n
            return self.max

    def snapshot(self) -> dict[str, float]:
        p50 = self.percentile(0.50)
        p99 = self.percentile(0.99)
        with self._lock:
            n = self.count
            return {
                "count": n,
                "mean": self.total / n if n else 0.0,
                "min": self.min if n else 0.0,
                "max": self.max,
                "p50": p50,
                "p99": p99,
            }

    def cumulative_buckets(self) -> list[tuple[float, int]]:
        """(upper_bound_seconds, cumulative_count) per occupied prefix
        of the bucket array — the Prometheus histogram exposition shape
        (``le`` labels are inclusive upper bounds; bucket ``b`` spans
        [2^b, 2^(b+1)) ns, so its bound is 2^(b+1) ns). Trailing empty
        buckets are dropped; the exporter appends the +Inf bucket."""
        with self._lock:
            counts = list(self.counts)
            total = self.count
        out: list[tuple[float, int]] = []
        seen = 0
        for b, n in enumerate(counts):
            seen += n
            out.append((float(1 << (b + 1)) * 1e-9, seen))
            if seen >= total:
                break
        return out


class CounterRegistry:
    def __init__(self) -> None:
        self._counters: dict[str, Counter] = {}
        self._histograms: dict[str, Histogram] = {}
        self._lock = threading.Lock()
        self.enabled = True

    def counter(
        self, name: str, description: str = "", unit: str = "count"
    ) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = Counter(name, description, unit)
                self._counters[name] = c
            return c

    def record(self, name: str, amount: float = 1) -> None:
        # Hot path (several calls per message): skip the registry lock
        # for the overwhelmingly-common already-registered case — dict
        # get is atomic under the GIL, and a racing first registration
        # just falls through to the locked counter() path.
        if self.enabled:
            c = self._counters.get(name)
            if c is None:
                c = self.counter(name)
            c.add(amount)

    def hwm(self, name: str, value: float) -> None:
        """High-watermark counter: keeps the max ever observed (the
        reference's SPC watermark-class variables, ompi_spc.h)."""
        if not self.enabled:
            return
        c = self.counter(name, unit="max")
        with c._lock:
            if value > c.value:
                c.value = value

    @contextmanager
    def timer(self, name: str) -> Iterator[None]:
        """Accumulate wall seconds into `<name>_seconds` — timer-class
        counters are distinct from event counters of the same base name
        (the reference's SPC keeps separate timer-variant counters too)."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.counter(f"{name}_seconds", unit="seconds").add(
                time.perf_counter() - t0
            )

    def histogram(
        self, name: str, description: str = "", unit: str = "seconds"
    ) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = Histogram(name, description, unit)
                self._histograms[name] = h
            return h

    def record_latency(self, name: str, seconds: float) -> None:
        """Histogram-class pvar record; same lock-dodging fast path as
        record() for the already-registered case."""
        if self.enabled:
            h = self._histograms.get(name)
            if h is None:
                h = self.histogram(name)
            h.record(seconds)

    def histogram_snapshots(self) -> dict[str, dict[str, float]]:
        with self._lock:
            hists = list(self._histograms.values())
        return {h.name: h.snapshot() for h in sorted(hists,
                                                     key=lambda h: h.name)}

    def get_histogram(self, name: str) -> Optional[Histogram]:
        """The registered histogram, or None — read-side accessor for
        the MPI_T surface and the Prometheus exporter (which needs the
        raw buckets, not just the percentile snapshot)."""
        return self._histograms.get(name)

    def histogram_dump(self) -> list[dict]:
        """dump() for the histogram pvar class: one entry per
        histogram, carrying the percentile snapshot."""
        with self._lock:
            hists = sorted(self._histograms.values(),
                           key=lambda h: h.name)
        return [
            {
                "name": h.name,
                "unit": h.unit,
                "description": h.description,
                "snapshot": h.snapshot(),
            }
            for h in hists
        ]

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return {n: c.value for n, c in self._counters.items()}

    def dump(self) -> list[dict]:
        with self._lock:
            return [
                {
                    "name": c.name,
                    "value": c.value,
                    "unit": c.unit,
                    "description": c.description,
                }
                for c in sorted(self._counters.values(), key=lambda c: c.name)
            ]

    def reset_for_testing(self) -> None:
        with self._lock:
            self._counters.clear()
            self._histograms.clear()


SPC = CounterRegistry()


class PvarSession:
    """MPI_T-style session: snapshot at start, diff on read.

    Covers both pvar classes: ``read()`` is the scalar-counter delta
    view it always was; ``read_histograms()`` is the histogram-class
    analog — per-histogram sample-count deltas since session start,
    with the *current* percentile estimates attached (percentiles do
    not subtract, so the distribution shown is cumulative while the
    count delta scopes it to this session's window)."""

    def __init__(self, registry: CounterRegistry = SPC) -> None:
        self._registry = registry
        self._base = registry.snapshot()
        self._base_hist = registry.histogram_snapshots()

    def read(self) -> dict[str, float]:
        now = self._registry.snapshot()
        return {
            k: v - self._base.get(k, 0)
            for k, v in now.items()
            if v != self._base.get(k, 0)
        }

    def read_histograms(self) -> dict[str, dict[str, float]]:
        out = {}
        for name, snap in self._registry.histogram_snapshots().items():
            base = self._base_hist.get(name, {})
            delta = snap["count"] - base.get("count", 0)
            if delta:
                out[name] = dict(snap, count=delta)
        return out

    def reset(self) -> None:
        self._base = self._registry.snapshot()
        self._base_hist = self._registry.histogram_snapshots()
