"""Pluggable owner Schedules — who communicates at tick k.

A Schedule turns a PRNG key into the (T,) i_k owner sequence the engines
scan over. All three variants are jit/vmap-safe, so multi-seed statistics
stay one vmap away.

Device contract: `draw` MUST return a device-resident (T,) int32 array
from jax ops only (no host materialization) — the fused multi-round driver
(`Federation.run_rounds`) feeds it straight into a `lax.scan`, so a
schedule that round-trips through numpy would reintroduce the per-round
host sync the driver exists to remove. `as_owner_seq` is the shared
normalizer that enforces the dtype/shape of hand-rolled sequences.

  UniformSchedule           — line 3 of Algorithm 1: i.i.d. uniform draws
                              (the distributional shortcut for symmetric
                              rate-1 Poisson clocks).
  PoissonSchedule           — the continuous-time simulation itself, for
                              communication-timing studies (Figs. 3/9).
  AvailabilityTraceSchedule — beyond-paper: geographically-scattered owners
                              that only answer inside per-owner availability
                              windows of a recurring period (e.g. business
                              hours across timezones). Ticks still arrive
                              from superposed Poisson clocks; the mark is
                              drawn uniformly among the owners whose window
                              contains that instant.

DP-FTRL-style participation schedules (see PAPERS.md) are further
implementations of the same one-method protocol.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.federation.clocks import (Schedule, poisson_schedule,
                                     uniform_schedule)


@runtime_checkable
class ScheduleProtocol(Protocol):
    def draw(self, key, n_owners: int, horizon: int) -> jax.Array:
        """(T,) int32 DEVICE owner sequence (jit-safe jax ops only)."""
        ...


def as_owner_seq(seq, n_owners: int) -> jax.Array:
    """Normalize an owner sequence to the engines' (T,) int32 device form,
    validating statically-known bounds (host lists fail fast here instead
    of as an out-of-range gather inside the scan)."""
    seq = jnp.asarray(seq)
    if seq.ndim != 1:
        raise ValueError(f"owner sequence must be 1-D, got {seq.shape}")
    if not jnp.issubdtype(seq.dtype, jnp.integer):
        raise ValueError(f"owner sequence must be integer, got {seq.dtype}")
    if isinstance(seq, jax.core.Tracer):
        return seq.astype(jnp.int32)
    if seq.size and (_read_int(seq.min()) < 0
                     or _read_int(seq.max()) >= n_owners):
        raise ValueError(
            f"owner sequence out of range for {n_owners} owners")
    return seq.astype(jnp.int32)


def _read_int(x) -> int:
    """One blocking device->host read, a `host_sync` span in a trace."""
    with jax.profiler.TraceAnnotation("host_sync"):
        return int(x)


# ------------------ schedule analysis: conflict-free groups ----------------
# Rounds touching DISTINCT owners only interact through theta_L (each reads
# and writes its own bank row), so a run of consecutive rounds with no
# repeated owner can execute as one owner-parallel batch. These two helpers
# are the host-side analysis pass behind `Federation.run_rounds(...,
# owner_parallel=True)`: partition the (K,) sequence into maximal
# conflict-free groups, then pack the groups into the rectangular
# (n_groups, G_max) index/mask arrays the grouped driver scans over.

def partition_conflict_free(owner_seq,
                            max_group: Optional[int] = None
                            ) -> List[Tuple[int, int]]:
    """Greedy maximal partition of a CONCRETE (K,) owner sequence into
    consecutive (start, length) groups with all-distinct owners.

    Greedy left-to-right is optimal here (fewest groups): a group ends
    exactly when the next owner would repeat — ending it earlier can never
    reduce the group count. `max_group` caps group length (max_group=1
    degenerates to the sequential schedule). Host-side by design: this is
    the schedule-analysis pass, run once per dispatch, not per round."""
    seq = np.asarray(owner_seq)
    if seq.ndim != 1:
        raise ValueError(f"owner sequence must be 1-D, got {seq.shape}")
    if max_group is not None and max_group < 1:
        raise ValueError(f"max_group must be >= 1, got {max_group}")
    groups: List[Tuple[int, int]] = []
    start, seen = 0, set()
    for k, o in enumerate(seq.tolist()):
        if o in seen or (max_group is not None and k - start >= max_group):
            groups.append((start, k - start))
            start, seen = k, {o}
        else:
            seen.add(o)
    if len(seq) > start:
        groups.append((start, len(seq) - start))
    return groups


def auto_max_group(owner_seq, step_overhead: float = 4.0,
                   cap: int = 16) -> int:
    """Pick the owner-parallel group cap from the schedule's own repeat
    statistics (the `max_group="auto"` default of `Federation.run_rounds`).

    Every candidate cap c is scored on the CONCRETE sequence by actually
    partitioning it (empirical owner-repeat statistics, not a
    distributional model): a dispatch costs ~one scan step per group —
    each paying a fixed overhead of `step_overhead` member-compute units
    (the (N, P) bank loop-carry copy dominates it at MLP scale on CPU) —
    plus the vmapped member compute, padded to c slots. Minimizing
    n_groups(c) * (c + step_overhead) therefore trades padding waste
    against step count; `cap` bounds the search. Ties go to the SMALLER
    cap: less padding at equal cost. Returns 1 when grouping cannot win
    (e.g. a single-owner schedule), which the session routes to the
    strictly sequential scan.

    Candidates come from a FIXED ladder (1,2,3,4,6,8,12,16), not every
    integer: the chosen cap is also the member-axis shape the session
    compiles the grouped program for, and schedule-drawn dispatches pick
    a fresh cap every call — a dense candidate range would recompile the
    whole K-round program on nearly every dispatch, while the ladder
    bounds the jit cache at its own size (and the host-side scoring at
    |ladder| partitions)."""
    seq = np.asarray(owner_seq)
    if seq.size == 0:
        return 1
    longest = max(length for _, length in partition_conflict_free(seq))
    best_c, best_cost = 1, float("inf")
    for c in (1, 2, 3, 4, 6, 8, 12, 16):
        if c > min(longest, cap):
            break
        n_g = len(partition_conflict_free(seq, c))
        cost = n_g * (c + step_overhead)
        if cost < best_cost:
            best_c, best_cost = c, cost
    return best_c


def pack_groups(groups: List[Tuple[int, int]]
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(start, length) groups -> (idx, valid), both (n_groups, G_max).

    `idx[g, j]` is the ROUND index of member j of group g (so `a[idx]`
    gathers any (K,)-leading array into group-major layout); padding
    repeats round index 0 with `valid=False` — the grouped driver masks
    padded members out of every write."""
    if not groups:
        return (np.zeros((0, 1), np.int32), np.zeros((0, 1), bool))
    gmax = max(length for _, length in groups)
    idx = np.zeros((len(groups), gmax), np.int32)
    valid = np.zeros((len(groups), gmax), bool)
    for g, (start, length) in enumerate(groups):
        idx[g, :length] = np.arange(start, start + length)
        valid[g, :length] = True
    return idx, valid


@dataclasses.dataclass(frozen=True)
class UniformSchedule:
    def draw(self, key, n_owners: int, horizon: int) -> jax.Array:
        return uniform_schedule(key, n_owners, horizon).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class PoissonSchedule:
    rate: float = 1.0

    def draw_with_times(self, key, n_owners: int, horizon: int) -> Schedule:
        return poisson_schedule(key, n_owners, horizon, self.rate)

    def draw(self, key, n_owners: int, horizon: int) -> jax.Array:
        return self.draw_with_times(key, n_owners, horizon).owners.astype(
            jnp.int32)


@dataclasses.dataclass(frozen=True)
class AvailabilityTraceSchedule:
    """Per-owner availability windows over a recurring period.

    windows[i] = (start, end) as fractions of `period` in [0, 1);
    wrap-around windows (start > end) model e.g. an owner whose business
    hours straddle the period boundary. If no owner is available at a tick
    (a gap in the trace), every owner is considered available so the clock
    keeps ticking — the learner never idles on an empty federation.

    `trace` replays a RECORDED owner sequence instead of sampling one
    (tiled to the horizon if shorter): deterministic replay of a
    production availability log, e.g. for chaos/regression studies. The
    ids are validated against the windowed owner count AT CONSTRUCTION —
    an out-of-range id would otherwise scatter with mode='drop' inside
    the fused scan and silently lose the round.
    """
    windows: Tuple[Tuple[float, float], ...]
    period: float = 24.0
    rate: float = 1.0
    trace: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.trace is None:
            return
        trace = tuple(int(o) for o in self.trace)
        if not trace:
            raise ValueError("an empty trace cannot schedule any round")
        n = len(self.windows)
        bad = sorted({o for o in trace if not 0 <= o < n})
        if bad:
            raise ValueError(
                f"trace owner ids {bad} out of range for the {n} windowed "
                "owners — inside the fused scan an out-of-range id would "
                "scatter with mode='drop' and silently lose the round")
        object.__setattr__(self, "trace", trace)

    def _tiled(self, horizon: int) -> jax.Array:
        """The recorded trace tiled to `horizon`, as a DEVICE int32 array,
        cached on the instance keyed by horizon.

        draw() used to rebuild the tiling with `np.resize` and re-upload
        it on EVERY dispatch — O(horizon) host work and one
        host->device transfer per call for a bit-identical result. The
        cache keeps one device copy per distinct horizon for the
        instance's lifetime (sessions dispatch a fixed k_rounds, so in
        practice that is one entry). Mutating a frozen dataclass's
        `__dict__` is deliberate: `_tiled_cache` is not a field, so
        equality/hash/replace semantics are untouched."""
        cache = self.__dict__.get("_tiled_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_tiled_cache", cache)
        out = cache.get(horizon)
        if out is None:
            out = jnp.asarray(np.resize(
                np.asarray(self.trace, np.int32), horizon))
            cache[horizon] = out
        return out

    def draw_with_times(self, key, n_owners: int, horizon: int) -> Schedule:
        if len(self.windows) != n_owners:
            raise ValueError(
                f"{len(self.windows)} windows for {n_owners} owners")
        k_time, k_pick = jax.random.split(key)
        times = poisson_schedule(k_time, n_owners, horizon, self.rate).times
        if self.trace is not None:
            return Schedule(times, self._tiled(horizon))
        inside = self.available(times, fallback=True)            # (T, N)
        gumbel = jax.random.gumbel(k_pick, (horizon, n_owners))
        owners = jnp.argmax(jnp.where(inside, gumbel, -jnp.inf),
                            axis=1).astype(jnp.int32)
        return Schedule(times, owners)

    def draw(self, key, n_owners: int, horizon: int) -> jax.Array:
        return self.draw_with_times(key, n_owners, horizon).owners

    def trace_ring(self, chunk: int = 4096) -> "TraceRing":
        """A streaming view of the recorded trace (see TraceRing) —
        multi-hour traces feed the engines chunk-by-chunk instead of
        materializing the whole tiled (K,) sequence device-side."""
        if self.trace is None:
            raise ValueError("trace_ring needs a recorded trace")
        return TraceRing(self.trace, chunk=chunk)

    def available(self, times: jax.Array,
                  fallback: bool = False) -> jax.Array:
        """(T, N) availability mask at the given instants.

        fallback=True applies the same everyone-available escape hatch at
        trace gaps that draw_with_times uses, so the mask matches what the
        draw actually sampled from; fallback=False is the raw window
        membership (for tests/plots)."""
        phase = (times / self.period) % 1.0
        starts = jnp.asarray([w[0] for w in self.windows])
        ends = jnp.asarray([w[1] for w in self.windows])
        inside = jnp.where(
            starts <= ends,
            (phase[:, None] >= starts) & (phase[:, None] < ends),
            (phase[:, None] >= starts) | (phase[:, None] < ends))
        if fallback:
            inside = jnp.where(inside.any(axis=1, keepdims=True), inside,
                               True)
        return inside


class TraceRing:
    """Device-resident ring buffer over a recorded availability trace.

    Multi-hour production traces reach tens of millions of rounds;
    materializing the whole tiled (K,) owner sequence device-side per
    dispatch (what ``AvailabilityTraceSchedule.draw`` does) costs memory
    and upload time proportional to the TRACE, not the dispatch. The
    ring streams it instead: the host keeps the raw trace, the device
    holds ONE `chunk`-sized int32 buffer, and

      * ``next(k)`` returns the next consecutive (k,) int32 device
        window — a single ``lax.dynamic_slice`` whose offset is a traced
        operand, so every same-k call shares one compiled executable —
        uploading a fresh chunk only when the cursor crosses a chunk
        boundary (one host->device transfer per `chunk` rounds);
      * ``window(k)`` is the HOST peek the paging prefetcher keys on:
        the owner ids the next dispatch will touch, with no cursor
        advance and no device sync.

    Wrap semantics match ``np.resize`` tiling (the trace repeats
    end-to-end), so a session replaying through the ring sees the exact
    sequence ``AvailabilityTraceSchedule.draw`` would hand it
    (property-tested in tests/test_property.py).
    """

    def __init__(self, trace, chunk: int = 4096):
        trace = np.asarray(trace, np.int32).reshape(-1)
        if trace.size == 0:
            raise ValueError("an empty trace cannot schedule any round")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self._trace = trace
        self.chunk = int(chunk)
        self.cursor = 0                 # absolute position in the tiling
        self._chunk_start = 0           # absolute start of resident chunk
        self._buf: Optional[jax.Array] = None

    def __len__(self) -> int:
        return int(self._trace.size)

    @property
    def resident_bytes(self) -> int:
        """Device bytes the ring holds — O(chunk), independent of the
        trace length (asserted by the paged-bank benchmarks)."""
        return 0 if self._buf is None else int(self._buf.nbytes)

    def window(self, k: int) -> np.ndarray:
        """(k,) int32 HOST view of the next k owner ids (no advance)."""
        if k < 0:
            raise ValueError(f"k must be >= 0, got {k}")
        return np.take(self._trace, self.cursor + np.arange(k),
                       mode="wrap")

    def _refill(self, start: int) -> None:
        idx = (start + np.arange(self.chunk)) % self._trace.size
        self._buf = jnp.asarray(self._trace[idx])
        self._chunk_start = start

    def next(self, k: int) -> jax.Array:
        """The next consecutive (k,) int32 DEVICE owner window; advances
        the cursor. k larger than the chunk degrades to one direct
        upload of exactly k ids (correct, just unbuffered) — size the
        chunk at or above the dispatch length to stay on the ring."""
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if k > self.chunk:
            out = jnp.asarray(self.window(k))
            self.cursor += k
            return out
        if (self._buf is None
                or self.cursor + k > self._chunk_start + self.chunk):
            self._refill(self.cursor)
        off = self.cursor - self._chunk_start
        out = jax.lax.dynamic_slice(self._buf,
                                    (jnp.asarray(off, jnp.int32),), (k,))
        self.cursor += k
        return out
