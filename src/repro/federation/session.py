"""Federation: the one session surface over every path in the repo.

A Federation is the paper's central object — a learner interacting
one-on-one with N private DataOwners under per-owner budgets and Theorem-1
noise. One construction serves every workload:

    fed = Federation(owners, FederationConfig(horizon=1000, sigma=2e-5))

    # convex (LinearProblem, lax.scan fast path; Figs. 2/6/8)
    trace = fed.run(key, prob)                  # ledgered single session
    traces = fed.run(key, prob, n_runs=100)     # vmapped percentile stats

    # deep models (jitted bank-sharded path)
    step = fed.make_step(loss_fn)
    state = fed.init_state(params)
    state, metrics = fed.step(state, batch, owner_idx, key)

    # fused driver: K rounds per dispatch, accounting on-device
    state, metrics = fed.run_rounds(state, batches, owner_seq, key)
    fed.reconcile(state)                        # fold device ledger -> host

    fed.ledger()                                # per-owner spend + refusals

The Mechanism (noise calibration + internal PrivacyAccountant) and the
Schedule (who communicates when) are pluggable; budget-exhausted owners are
refused AT THIS LAYER — a refused round is a no-op for model state and is
reported in the ledger, so accounting can never drift from the noise that
was actually emitted. The fused `run_rounds` driver makes the same
refusal decision on-device (DeviceLedger masking inside the scan) and
`reconcile()` folds it back into the host accountant bit-exactly. The
synchronous all-owners-per-round DP baseline is the same surface with
strategy="sync".
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.federation.config import FederationConfig
from repro.federation.convex import (Algo1Trace, SyncTrace, scan_engine,
                                     stack_gram, sync_scan_engine)
from repro.federation.deep import (AsyncDPConfig, AsyncDPState, init_state,
                                   init_state_flat, make_fused_rounds,
                                   make_group_rounds, make_sync_dp_step,
                                   make_train_step)
from repro.federation.dp_sgd import PrivatizerConfig
from repro.federation.faults import (DROP, OK, FaultPlan, FaultPolicy,
                                     as_fault_codes, fault_tick)
from repro.federation.flatten import ParamFlat
from repro.federation.linear import LinearProblem
from repro.federation.mechanisms import Mechanism, make_mechanism
from repro.federation.owners import DataOwner
from repro.federation.schedules import (ScheduleProtocol, TraceRing,
                                        UniformSchedule, as_owner_seq,
                                        auto_max_group, pack_groups,
                                        partition_conflict_free)
from repro.federation.staleness import (LatencyPlan, StalenessPolicy,
                                        as_tick_times, merge_timeout_codes,
                                        staleness_tick)

_STRATEGIES = ("async", "sync")


class Federation:
    def __init__(self, owners: Sequence[DataOwner], config: FederationConfig,
                 *, mechanism: Union[str, Mechanism] = "paper",
                 schedule: Optional[ScheduleProtocol] = None,
                 strategy: str = "async",
                 cap_slack: Optional[float] = None,
                 tree_depth: Optional[int] = None,
                 fault_policy: Optional[FaultPolicy] = None,
                 staleness: Optional[StalenessPolicy] = None):
        if strategy not in _STRATEGIES:
            raise ValueError(f"strategy must be one of {_STRATEGIES}")
        self.owners = list(owners)
        self.config = config
        self.schedule = schedule if schedule is not None else UniformSchedule()
        self.strategy = strategy
        # fault_policy arms the in-graph fault layer (deep path only):
        # states grow FaultState counters, drivers accept fault codes, and
        # owners exceeding the policy's fault budget are quarantined.
        # None keeps every driver tracing the fault-free program verbatim.
        self.fault_policy = fault_policy
        # staleness arms the async-runtime layer (deadlines -> TIMEOUT,
        # retry backoff, decayed inertia). It rides on the fault algebra,
        # so a staleness-only federation auto-arms a never-quarantine
        # fault policy — faults become expressible but nothing changes
        # until codes are actually injected.
        self.staleness = staleness
        if staleness is not None and fault_policy is None:
            self.fault_policy = FaultPolicy(max_faults=2**30, window=2**30)
        self.mechanism = make_mechanism(mechanism, self.owners, config,
                                        cap_slack=cap_slack,
                                        tree_depth=tree_depth)
        self._step_fn = None
        self._fused_fn = None
        self._group_fn = None
        self._tick_fn = None
        self._stale_tick_fn = None
        self._pack_params = False
        self._bank_dtype = None
        self._mesh = None
        self._pager = None
        self._ran = False

    def _claim_session(self):
        # The jitted engines start from fresh per-owner counters, so a
        # second ledgered run would emit responses the cumulative ledger
        # refuses — budget spend and accounting would silently drift apart.
        if self._ran:
            raise RuntimeError(
                "this Federation already ran its ledgered session; use "
                "n_runs for statistical replicas or build a new Federation "
                "to renegotiate budgets")
        self._ran = True

    @property
    def n_owners(self) -> int:
        return len(self.owners)

    def ledger(self) -> Dict[int, Dict]:
        return self.mechanism.ledger()

    def _authorize_many(self, owner_idx: int, count: int) -> int:
        bulk = getattr(self.mechanism, "authorize_many", None)
        if bulk is not None:
            return bulk(owner_idx, count)
        return sum(self.mechanism.authorize(owner_idx)
                   for _ in range(count))

    def _reject_tree(self, engine: str):
        # The convex/sync scan engines draw INDEPENDENT per-round noise in
        # one fused trace; they carry no noise-tree state, so running them
        # under a tree mechanism would silently emit the wrong mechanism.
        if getattr(self.mechanism, "tree_depth", None) is not None:
            raise ValueError(
                f"{engine} draws independent per-round noise; the tree "
                f"mechanism needs the deep path (make_step/run_rounds)")

    # ------------------------- convex fast path ---------------------------
    def _gram(self):
        if any(o.gram is None for o in self.owners):
            raise ValueError("convex path needs Gram payloads on every "
                             "owner (DataOwner.from_arrays/from_gram)")
        return stack_gram([o.gram for o in self.owners])

    def run(self, key, problem: LinearProblem,
            n_runs: Optional[int] = None) -> Algo1Trace:
        """Run the asynchronous session on a LinearProblem.

        n_runs=None runs ONE ledgered session (every response — and
        refusal — lands in .ledger()). n_runs=k vmaps k statistical
        replicas for percentile figures; replicas model hypothetical
        re-runs, so they are NOT ledgered.
        """
        if self.strategy != "async":
            raise ValueError("run() is the async path; use run_sync()")
        self._reject_tree("the convex scan engine")
        A, b, n_i = self._gram()
        scales = self.mechanism.scales(p=problem.G.shape[0])
        cfg = self.config

        def run_one(k):
            return scan_engine(k, problem, A, b, n_i, scales,
                               horizon=cfg.horizon, rho=cfg.rho,
                               sigma=cfg.sigma, lr_scale=cfg.lr_scale,
                               draw=self.schedule.draw,
                               cap=self.mechanism.cap)

        if n_runs is None:
            self._claim_session()
            trace = run_one(key)
            counts = np.bincount(np.asarray(trace.owners_seq),
                                 minlength=self.n_owners)
            for i, c in enumerate(counts):
                self._authorize_many(i, int(c))
            return trace
        return jax.vmap(run_one)(jax.random.split(key, n_runs))

    def run_sync(self, key, problem: LinearProblem,
                 lr: float, n_runs: Optional[int] = None) -> SyncTrace:
        """The synchronous all-owners-per-round baseline on the same
        surface (strategy='sync' federations only)."""
        if self.strategy != "sync":
            raise ValueError("run_sync() needs strategy='sync'")
        self._reject_tree("the synchronous scan engine")
        if self.mechanism.cap is not None:
            raise ValueError(
                "per_owner_rounds is an asynchronous composition: the sync "
                "engine queries every owner all T rounds, so a capped noise "
                "scale would violate the owners' budgets; use 'paper' or "
                "'strict'")
        A, b, n_i = self._gram()
        scales = self.mechanism.scales(p=problem.G.shape[0])
        cfg = self.config

        def run_one(k):
            return sync_scan_engine(k, problem, A, b, n_i, scales,
                                    horizon=cfg.horizon, lr=lr)

        if n_runs is None:
            self._claim_session()
            trace = run_one(key)
            for i in range(self.n_owners):
                self._authorize_many(i, cfg.horizon)
            return trace
        return jax.vmap(run_one)(jax.random.split(key, n_runs))

    # -------------------------- deep-model path ---------------------------
    def as_async_config(self, privatizer: Optional[PrivatizerConfig] = None
                        ) -> AsyncDPConfig:
        """The low-level engine config this session implies."""
        xi = max(o.xi for o in self.owners)
        cfg = self.config
        cap = self.mechanism.cap
        return AsyncDPConfig(
            n_owners=self.n_owners, horizon=cfg.horizon, rho=cfg.rho,
            sigma=cfg.sigma,
            epsilons=tuple(o.epsilon for o in self.owners),
            owner_sizes=tuple(o.n for o in self.owners),
            xi=xi, theta_max=cfg.theta_max,
            privatizer=privatizer or PrivatizerConfig(xi=xi),
            lr_scale=cfg.lr_scale,
            caps=None if cap is None else (cap,) * self.n_owners,
            tree_depth=getattr(self.mechanism, "tree_depth", None),
            fault_policy=self.fault_policy,
            staleness=self.staleness)

    def init_state(self, params, pack_params: Optional[bool] = None,
                   bank_dtype=None, mesh=None) -> AsyncDPState:
        """Build the deep-path training state. `pack_params=None` follows
        the flag given to make_step (default tree); True packs the model
        into the flat-buffer representation (ParamFlat theta_L + one
        (N, P) bank matrix) that the flat round engine runs on.
        `bank_dtype` (flat states only, None follows make_step) narrows
        the bank storage — bf16 halves the dominant state memory and the
        fused scan's carry traffic at the cost of quantized owner copies,
        and the strings "int8"/"fp8" build the error-feedback quantized
        bank (~4x below f32; see flatten.QuantBank). f32 keeps the
        bit-parity contract. `mesh` (flat states only,
        None follows make_step) lays the buffers out across the device
        mesh under repro.sharding.rules.flat_shardings — bank rows over
        the data axes, P like the model."""
        pack = self._pack_params if pack_params is None else pack_params
        if pack:
            if bank_dtype is None:
                bank_dtype = self._bank_dtype
            if mesh is None:
                mesh = self._mesh
            state = init_state_flat(params, self.as_async_config(),
                                    bank_dtype=bank_dtype, mesh=mesh)
        else:
            # the make_step-configured bank dtype/mesh are simply
            # irrelevant to a tree state; only an EXPLICIT request here
            # is an error
            if bank_dtype is not None:
                raise ValueError("bank_dtype is a flat-engine option; "
                                 "pass pack_params=True")
            if mesh is not None:
                raise ValueError("mesh sharding is a flat-engine option; "
                                 "pass pack_params=True")
            state = init_state(params, self.as_async_config())
        snapshot = getattr(self.mechanism, "device_ledger", None)
        if snapshot is not None:
            # In-graph authorization must refuse exactly where the host
            # would: seed the device counters from the live accountant.
            ledger = snapshot()
            if mesh is not None:
                ledger = jax.device_put(
                    ledger, jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec()))
            state = state._replace(ledger=ledger)
        return state

    def init_paged_state(self, params, n_hot: int, bank_dtype=None,
                         mesh=None, cold_dir=None) -> AsyncDPState:
        """Flat-engine state whose owner bank is PAGED: an n_hot-row
        device-resident working set over a host cold tier, so resident
        bytes are O(n_hot * P) independent of N (see
        federation.paging). The pager is attached to this session —
        `step()` and `run_rounds()` prefetch the rows each dispatch
        touches automatically, and every driver resolves owner -> hot
        slot in-graph (no host sync inside the scan). With n_hot >=
        n_owners the paged engine reproduces the flat engine
        bit-for-bit. Requires a flat make_step (pack_params=True).
        `cold_dir` puts the cold tier on disk (lazy memmap); None keeps
        it in host memory."""
        if not self._pack_params:
            raise ValueError("the paged bank is a flat-engine option; "
                             "call make_step(..., pack_params=True) first")
        if bank_dtype is None:
            bank_dtype = self._bank_dtype
        if mesh is None:
            mesh = self._mesh
        from repro.federation.paging import init_paged_state
        state, pager = init_paged_state(params, self.as_async_config(),
                                        n_hot, bank_dtype=bank_dtype,
                                        mesh=mesh, cold_dir=cold_dir)
        self._pager = pager
        snapshot = getattr(self.mechanism, "device_ledger", None)
        if snapshot is not None:
            ledger = snapshot()
            if mesh is not None:
                ledger = jax.device_put(
                    ledger, jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec()))
            state = state._replace(ledger=ledger)
        return state

    @property
    def pager(self):
        """The OwnerPager attached by init_paged_state (None for
        non-paged sessions) — exposes resident_ids, stats, flush()."""
        return self._pager

    def params_of(self, state: AsyncDPState):
        """The central model as a pytree, whichever representation the
        state carries (flat buffers are unpacked)."""
        theta = state.theta_L
        return theta.unpack() if isinstance(theta, ParamFlat) else theta

    def make_step(self, loss_fn, *,
                  privatizer: Optional[PrivatizerConfig] = None,
                  lr: Optional[float] = None, n_params: Optional[int] = None,
                  jit: bool = True, donate: bool = False,
                  pack_params: bool = False, bank_dtype=None, mesh=None,
                  unroll: int = 1):
        """Build (and cache for .step()) the jitted per-round function.

        async: step(state, batch, owner_idx, key) -> (state, metrics)
        sync:  step(params, batches, key[, weights]) -> params  (needs lr)
        n_params feeds dimension-aware mechanisms (e.g. 'strict').

        pack_params=True opts `init_state` into the flat-buffer engine
        (the model packed into one contiguous (P,) f32 buffer, the bank a
        single (N, P) matrix). The built step functions serve BOTH
        representations — they dispatch on the state — so this flag only
        selects what `init_state` constructs. Default off: the pytree
        path stays the reference.

        `bank_dtype` narrows the bank storage (see init_state): a real
        dtype (bf16) stores quantized rows densely; the strings
        "int8"/"fp8" (or a flatten.BankCodec) build the error-feedback
        QUANTIZED bank — ~4x below f32 resident bytes and scan-carry
        traffic. `donate=True` donates the state through the dispatch
        boundary (the K-round scan then reuses the bank's buffers instead
        of allocating a second copy — pair it with the quantized bank for
        the full in-place carry win; the passed-in state is consumed).
        `unroll` (async only) unrolls the fused scan body by that factor —
        identical results, fewer loop-carry copies per round on XLA:CPU
        (measured +24% at unroll=4, MLP scale).

        `mesh` (flat engine only) makes the whole round engine
        sharding-native: `init_state` places theta_L/bank under the
        repro.sharding.rules.flat_shardings layout and every driver pins
        that layout inside its scan body with with_sharding_constraint,
        so K rounds run distributed with no host transfer of the bank.
        A 1x1 mesh reproduces the unsharded engine bit-for-bit.

        Deep-path sensitivity is the privatizer's ENFORCED clip norm, not
        each owner's nominal Xi_i — clipping to a norm above an owner's
        bound would otherwise under-noise that owner.
        """
        if mesh is not None and not pack_params:
            raise ValueError("mesh sharding is a flat-engine option; "
                             "pass pack_params=True")
        self._pack_params = pack_params
        self._bank_dtype = bank_dtype
        self._mesh = mesh
        if self.fault_policy is not None and self.strategy == "async":
            # Host-protocol rounds that never enter the step graph
            # (drops, refusals) still advance the fault window exactly as
            # the fused driver's in-graph tick would.
            pol = self.fault_policy
            self._tick_fn = jax.jit(
                lambda fs, i, f: fault_tick(fs, jnp.int32(i), jnp.bool_(f),
                                            pol, active=jnp.bool_(True)))
        if self.staleness is not None and self.strategy == "async":
            # Host-masked rounds (quarantine, retry, drop, refusal) must
            # advance the staleness clock exactly as the fused driver's
            # in-graph tick does: same scatter, flags all-False except
            # is_retry, so ages stay driver-order-free.
            spol = self.staleness
            self._stale_tick_fn = jax.jit(
                lambda ss, i, r: staleness_tick(
                    ss, jnp.int32(i), ss.clock, is_retry=jnp.bool_(r),
                    apply=jnp.bool_(False), timed=jnp.bool_(False),
                    policy=spol, active=jnp.bool_(True), ticks=1))
        acfg = self.as_async_config(privatizer)
        scales = self.mechanism.scales(p=n_params,
                                       clip_norm=acfg.privatizer.xi)
        donate_args = (0,) if donate else ()
        if self.strategy == "sync":
            if lr is None:
                raise ValueError("sync strategy needs an explicit lr")
            step = make_sync_dp_step(loss_fn, acfg, lr, scales=scales)
        else:
            step = make_train_step(loss_fn, acfg, scales=scales, mesh=mesh)
            fused = make_fused_rounds(loss_fn, acfg, scales=scales,
                                      mesh=mesh, unroll=unroll)
            group = make_group_rounds(loss_fn, acfg, scales=scales,
                                      mesh=mesh)
            self._fused_fn = (jax.jit(fused, donate_argnums=donate_args)
                              if jit else fused)
            self._group_fn = (jax.jit(group, donate_argnums=donate_args)
                              if jit else group)
        if jit:
            step = jax.jit(step, donate_argnums=donate_args)
        self._step_fn = step
        return step

    def _require_step(self):
        if self._step_fn is None:
            raise RuntimeError("call make_step(loss_fn) before step()")
        return self._step_fn

    def step(self, state: AsyncDPState, batch, owner_idx, key,
             fault_code: Optional[int] = None
             ) -> Tuple[AsyncDPState, Dict[str, Any]]:
        """One ledgered asynchronous round. A budget-exhausted owner is
        refused: model state (central AND bank) is returned untouched and
        the refusal is recorded in the ledger.

        With a fault-armed federation (fault_policy set), `fault_code`
        injects one of faults.OK/DROP/STALE/NONFINITE_GRAD/
        CORRUPT_PAYLOAD/TIMEOUT into the round. The host mirrors the
        fused driver's outcome order exactly: quarantined owners are
        masked before anything else (no epsilon, no refusal, no window
        tick); with a staleness-armed federation an owner in backoff is
        masked next (a retried round — the learner never sends the
        query, so no epsilon and no fault-window contact); a DROP on an
        exhausted owner is a refusal (the budget check precedes the
        contact); a plain DROP costs no epsilon; every answered round is
        charged at response time even if the in-graph guards then reject
        it (metrics['faulted']) or the deadline already passed
        (metrics['timed_out'])."""
        if self.strategy != "async":
            raise ValueError("step() is the async path; use sync_round()")
        step_fn = self._require_step()
        i = int(owner_idx)
        if self._pager is not None:
            # make this round's row resident before dispatch; refusal/
            # quarantine paths tolerate the (bit-exact) extra residency
            state = self._pager.prefetch(state, np.asarray([i]))
        if state.faults is None:
            if fault_code is not None:
                raise ValueError(
                    "fault injection needs a fault-armed state; build the "
                    "Federation with fault_policy=FaultPolicy(...)")
            if not self.mechanism.authorize(i):
                return state, {"refused": True, "owner": i}
            new_state, metrics = step_fn(state, batch, jnp.int32(i), key)
            metrics = dict(metrics)
            metrics.update(refused=False, owner=i)
            return new_state, metrics

        fc = OK if fault_code is None else int(fault_code)
        flags = {"refused": False, "dropped": False, "faulted": False,
                 "quarantined": False, "timed_out": False, "owner": i}
        stale_armed = (state.stale is not None
                       and self._stale_tick_fn is not None)
        if stale_armed:
            flags["retried"] = False

        def ticked(st, retry=False):
            # host-masked rounds still advance the staleness clock —
            # same scatter as the fused in-graph tick, so ages stay
            # driver-order-free
            if not stale_armed:
                return st
            return st._replace(
                stale=self._stale_tick_fn(st.stale, i, retry))

        if bool(state.faults.quarantined[i]):
            # masked before any budget decision; the fused tick is also
            # inactive for quarantined owners, so no window advance
            self.mechanism.record_quarantined(i)
            return ticked(state), dict(flags, quarantined=True)
        if stale_armed and int(state.stale.cooldown[i]) > 0:
            # in backoff: a masked re-dispatch. The learner never sends
            # the query — no epsilon, no budget decision, and no fault-
            # window contact — and one cooldown round burns.
            self.mechanism.record_retried(i)
            return ticked(state, retry=True), dict(flags, retried=True)
        if fc == DROP:
            if self.mechanism.exhausted(i):
                # refusal takes precedence: the budget check happens
                # before the contact could be lost
                self.mechanism.authorize(i)      # records the refusal
                faults = self._tick_fn(state.faults, i, False)
                return (ticked(state._replace(faults=faults)),
                        dict(flags, refused=True))
            self.mechanism.record_dropped(i)     # no answer -> no epsilon
            faults = self._tick_fn(state.faults, i, True)
            return (ticked(state._replace(faults=faults)),
                    dict(flags, dropped=True))
        if not self.mechanism.authorize(i):
            faults = self._tick_fn(state.faults, i, False)
            return (ticked(state._replace(faults=faults)),
                    dict(flags, refused=True))
        new_state, metrics = step_fn(state, batch, jnp.int32(i), key,
                                     jnp.int8(fc))
        metrics = dict(metrics)
        if bool(metrics["faulted"]):
            self.mechanism.record_faulted(i)     # epsilon already charged
        timed = bool(metrics.get("timed_out", False))
        if timed:
            self.mechanism.record_timed_out(i)   # answered late: epsilon
        metrics.update(flags, faulted=bool(metrics["faulted"]),
                       timed_out=timed)
        return new_state, metrics

    def run_rounds(self, state: AsyncDPState, batches, owner_seq=None,
                   key=None, *, faults=None, latency=None, times=None,
                   owner_parallel: bool = False,
                   max_group: Union[int, str, None] = "auto"
                   ) -> Tuple[AsyncDPState, Dict[str, Any]]:
        """K asynchronous rounds in ONE dispatch (lax.scan over the jitted
        deep step, authorization decided on-device).

        `batches` leaves carry a leading (K,) round axis (round k consumes
        owner i_k's microbatch). `owner_seq` is a (K,) int32 device
        sequence; None draws it from the pluggable Schedule; a
        `schedules.TraceRing` streams a long availability trace in
        chunks — the call consumes the next K entries without ever
        materializing the full trace on device. Per-round keys
        are `jax.random.split(key, K)` — drive a per-round `step()` loop
        with the same split and it reproduces this call bit-for-bit
        (params, bank, and granted-round metrics).

        Host-sync contract: one dispatch costs AT MOST one device->host
        copy of the (K,) owner sequence, shared by every host-side
        consumer (the paged-bank prefetch, `auto_max_group`, and the
        conflict-free partition); with none of those enabled a
        schedule-drawn sequence never leaves the device.

        Budget-exhausted owners are refused IN-GRAPH via the state's
        DeviceLedger: a refused round is a no-op on model state exactly as
        in `step()`. Refusals accumulate on-device; call `reconcile(state)`
        afterwards to fold them into `ledger()` — until then the host
        accountant lags the device by the rounds of this call.

        `owner_parallel=True` batches non-conflicting rounds: the schedule
        is partitioned host-side into maximal groups of consecutive rounds
        with DISTINCT owners (`schedules.partition_conflict_free`;
        `max_group` caps group size) and the grouped driver runs
        group-at-a-time, vmapping the round over each group's members with
        one theta_L inertia reduction per group. `max_group="auto"` (the
        default) picks the cap per dispatch from the sequence's own
        owner-repeat statistics (`schedules.auto_max_group`: padding waste
        vs per-step bank-carry overhead; caps come from a fixed ladder,
        so re-tuning every dispatch cannot churn the jit cache beyond the
        ladder size); None means unbounded maximal groups; an int is a
        hard cap. Ledger spend (and therefore the
        privacy accounting) is exactly the sequential scan's; theta_L
        trajectories deviate boundedly for groups larger than one (see
        `make_group_rounds`). When every group has size 1 the sequential
        scan runs — bit-for-bit identical output.

        metrics are stacked (K,) round-order arrays either way (refused
        mask, owner, clip_frac, max_grad_norm, grad_noise_scale).

        `faults` (fault-armed federations only) injects per-round faults
        in-graph: a `FaultPlan` draws one int8 code per round
        deterministically from this call's key (domain-separated from the
        round keys, so the same key reproduces the same faults on every
        driver), or pass a (K,) code array to replay a recorded trace.
        Fault outcomes land in the device ledger's dropped/faulted/
        quarantined columns and fold back on `reconcile(state)`.

        `latency` (staleness-armed federations only) models response
        TIME: a `staleness.LatencyPlan` draws one latency per round from
        this call's key (STALE_SALT stream — disjoint from the round
        keys and fault codes, so every driver sees the same runtime), or
        pass a (K,) array to replay recorded latencies. Rounds later
        than the policy deadline upgrade to TIMEOUT in the fault-code
        trace (`staleness.merge_timeout_codes`) — answered-late, epsilon
        spent, update masked — and land in the ledger's timed_out
        column. `times` supplies per-round arrival instants (e.g.
        `Schedule.draw_with_times(...).times`) that tighten each round's
        effective deadline to the gap before the next tick; with
        latency armed, owner_seq=None, and a schedule that exposes
        `draw_with_times`, the times are drawn alongside the owner
        sequence automatically.

        Under `jax.profiler` the call writes host spans into the trace:
        `run_rounds` around the whole call, one `host_sync` around each
        blocking device->host read (a caller-given sequence's bounds
        check reads its min and max; the shared host copy is one more),
        and `launch` around the call into the jitted driver until it
        returns. With the profiler off they cost about a microsecond.
        """
        with jax.profiler.TraceAnnotation("run_rounds"):
            return self._run_rounds(state, batches, owner_seq, key, faults,
                                    latency, times, owner_parallel,
                                    max_group)

    def _run_rounds(self, state, batches, owner_seq, key, faults, latency,
                    times, owner_parallel, max_group):
        if self.strategy != "async":
            raise ValueError("run_rounds() is the async path")
        if key is None:
            raise ValueError("run_rounds needs an explicit PRNG key")
        self._require_step()
        if self._fused_fn is None:
            raise RuntimeError("call make_step(loss_fn) before run_rounds()")
        # Host-sync contract: everything below shares ONE host copy of
        # the owner sequence (`host_seq`), materialized lazily and at
        # most once per call. The pager's prefetch, auto_max_group and
        # partition_conflict_free all read it; the schedule-drawn path
        # with none of those enabled never syncs at all.
        seq_host = None
        user_times = times is not None

        def host_seq() -> np.ndarray:
            nonlocal seq_host
            if seq_host is None:
                with jax.profiler.TraceAnnotation("host_sync"):
                    seq_host = np.asarray(owner_seq)
            return seq_host

        if isinstance(owner_seq, TraceRing):
            # streamed availability trace: peek the window host-side for
            # the pager, then advance the ring — the device sequence is
            # a chunk-buffer slice, never the materialized (K,) trace
            ring = owner_seq
            k = jax.tree_util.tree_leaves(batches)[0].shape[0]
            seq_host = np.asarray(ring.window(k), np.int32)
            if seq_host.size and (seq_host.min() < 0
                                  or seq_host.max() >= self.n_owners):
                raise ValueError("trace names owners outside this "
                                 f"federation (n_owners={self.n_owners})")
            owner_seq = ring.next(k).astype(jnp.int32)
        elif owner_seq is None:
            # schedule-drawn: in-range by construction, stays on-device
            # (as_owner_seq's bounds check would force a host sync here)
            k_sched, key = jax.random.split(key)
            k = jax.tree_util.tree_leaves(batches)[0].shape[0]
            draw_wt = getattr(self.schedule, "draw_with_times", None)
            if latency is not None and times is None and draw_wt is not None:
                # the schedule's own wall clock feeds the deadline model:
                # arrival gaps tighten per-round deadlines (times are
                # non-decreasing by construction, no host check needed)
                sched = draw_wt(k_sched, self.n_owners, k)
                times = sched.times
                owner_seq = sched.owners.astype(jnp.int32)
            else:
                owner_seq = self.schedule.draw(k_sched, self.n_owners,
                                               k).astype(jnp.int32)
        else:
            owner_seq = as_owner_seq(owner_seq, self.n_owners)
        k_rounds = owner_seq.shape[0]
        if user_times:
            # hand-rolled times validate like hand-rolled sequences
            # (schedule-drawn times are in-contract by construction)
            times = as_tick_times(times, k_rounds)
        if self._pager is not None:
            # page in every row this dispatch touches (evicting stale
            # rows to the cold tier) before the scan launches
            state = self._pager.prefetch(state, host_seq())
        fault_codes = None
        if faults is not None:
            if state.faults is None:
                raise ValueError(
                    "fault injection needs a fault-armed state; build the "
                    "Federation with fault_policy=FaultPolicy(...)")
            if isinstance(faults, FaultPlan):
                # drawn from THIS key (salted fold-in keeps the stream
                # disjoint from the per-round keys split below), so fixed
                # key -> identical faults on every driver
                fault_codes = faults.draw(key, k_rounds)
            else:
                fault_codes = as_fault_codes(faults, k_rounds)
        if latency is not None:
            if self.staleness is None:
                raise ValueError(
                    "latency modeling needs a staleness-armed Federation; "
                    "pass staleness=StalenessPolicy(...) at construction")
            if state.faults is None:
                raise ValueError(
                    "latency injection needs a fault-armed state (TIMEOUT "
                    "is a fault code); rebuild the state from this "
                    "staleness-armed federation")
            # drawn from THIS key (STALE_SALT fold-in keeps the latency
            # stream disjoint from the fault codes and the round keys),
            # so fixed key -> identical timeouts on every driver
            lat = (latency.draw(key, owner_seq)  # dpcheck: ignore[DPC105]
                   if isinstance(latency, LatencyPlan)
                   else jnp.asarray(latency, jnp.float32))
            if fault_codes is None:
                fault_codes = jnp.full((k_rounds,), OK, jnp.int8)
            fault_codes = merge_timeout_codes(
                fault_codes, lat, self.staleness.deadline, times=times)
        # same key as FaultPlan.draw by contract: draw folds in
        # FAULT_SALT, so the fault stream never touches the round keys
        keys = jax.random.split(key, k_rounds)  # dpcheck: ignore[DPC105]
        codes = () if fault_codes is None else (fault_codes,)
        if not owner_parallel:
            with jax.profiler.TraceAnnotation("launch"):
                out = self._fused_fn(state, batches, owner_seq, keys, *codes)
            return out

        # schedule analysis is a host-side pass over the shared host
        # copy: at most one device->host sync per dispatch, not one per
        # consumer (previously auto_max_group and the partition each
        # pulled the full (K,) sequence)
        if max_group == "auto":
            max_group = auto_max_group(host_seq())
        groups = partition_conflict_free(host_seq(), max_group)
        if all(length <= 1 for _, length in groups):
            # every group is a single round: the sequential scan IS the
            # grouped execution, bit-for-bit
            with jax.profiler.TraceAnnotation("launch"):
                out = self._fused_fn(state, batches, owner_seq, keys, *codes)
            return out
        idx, valid = pack_groups(groups)
        # Shape-stabilize for the jit cache: schedule-drawn partitions
        # give a different (n_groups, G_max) almost every dispatch, and
        # each new shape would recompile the whole K-round program. Pad
        # the member axis to max_group (its natural cap; next power of
        # two when unbounded) and the group axis to the next multiple of
        # 4. Padded members are masked; padded groups are pure shape
        # padding — the driver's fori_loop stops at the TRACED real group
        # count, so they never execute (and never pay the (N, P) bank
        # loop-carry copy a scanned no-op step used to cost).
        n_g, gmax = idx.shape
        gpad = (max_group if max_group is not None
                else 1 << max(gmax - 1, 0).bit_length())
        rows = -(-n_g // 4) * 4
        idx = np.pad(idx, ((0, rows - n_g), (0, gpad - gmax)))
        valid = np.pad(valid, ((0, rows - n_g), (0, gpad - gmax)))
        with jax.profiler.TraceAnnotation("launch"):
            state, gm = self._group_fn(state, batches, owner_seq, keys,
                                       jnp.asarray(idx), jnp.asarray(valid),
                                       jnp.int32(n_g), *codes)
        # group-major (n_groups, G_max) -> round-order (K,): groups are
        # consecutive and in order, so the valid entries flatten in order
        order = np.flatnonzero(valid.reshape(-1))
        metrics = {name: v.reshape((-1,) + v.shape[2:])[order]
                   for name, v in gm.items()}
        return state, metrics

    def reconcile(self, state: AsyncDPState) -> Dict[int, Dict]:
        """Fold the state's device ledger back into the host accountant
        (bit-exact, drift raises) and return the updated ledger()."""
        if state.ledger is None:
            raise ValueError("state carries no device ledger")
        fold = getattr(self.mechanism, "reconcile", None)
        if fold is None:
            raise NotImplementedError(
                f"mechanism {self.mechanism.name!r} has no reconcile()")
        return fold(state.ledger)

    # --------------------------- crash-resume ------------------------------
    def save_session(self, directory, state: AsyncDPState,
                     step: Optional[int] = None) -> int:
        """Checkpoint the device state AND the host accountant together.

        Atomically writes the full AsyncDPState (params, bank, ledger,
        tree, fault counters, staleness counters) plus the mechanism's
        dispatch journal — everything `reconcile` depends on — so a
        process killed any time after this call resumes via
        `restore_session` with exactly the accounting the crashed
        process had. A PAGED state checkpoints both tiers: resident
        rows are flushed so the cold tier is authoritative, then its
        materialized rows ride in the same atomic npz shard as the hot
        state (never-written rows reconstruct from the default row for
        free). Returns the step the checkpoint was filed under
        (state.step when not given)."""
        from repro.checkpoint import save_checkpoint
        if step is None:
            step = int(state.step)
        extra = {}
        aux = None
        if self._pager is not None:
            # flush first: after this the cold tier holds the exact bits
            # of every resident row, so checkpointing its written rows
            # (plus the hot state above) captures the whole bank
            self._pager.flush(state, only_dirty=False)
            aux = {}
            for name, store in self._pager.stores.items():
                ids = store.written_ids
                aux[f"cold/{name}/ids"] = ids
                aux[f"cold/{name}/rows"] = store.read_rows(ids)
            extra["paging"] = {"stores": sorted(self._pager.stores),
                               "dtypes": {n: str(s.dtype) for n, s
                                          in self._pager.stores.items()},
                               "n_hot": self._pager.n_hot}
        exp = getattr(self.mechanism, "export_journal", None)
        if exp is not None:
            extra["journal"] = exp()
        save_checkpoint(directory, step, state, extra=extra or None,
                        aux_arrays=aux)
        return int(step)

    def restore_session(self, directory, like: AsyncDPState,
                        step: Optional[int] = None) -> AsyncDPState:
        """Restore a save_session checkpoint into THIS federation.

        `like` is a template state (e.g. a fresh `init_state(params)`)
        supplying structure, dtypes, and static metadata. The mechanism's
        journal is replayed first, rewinding the host accountant to the
        saved baselines, and the restored ledger adopts the journaled
        snapshot generation — so `reconcile` after resume folds exactly
        the deltas the crashed process had not yet folded, never
        double-counting epsilon. The federation must be built from the
        same owners/config as the one that saved. Restoring into a
        PAGED session (init_paged_state before this call, so `like` and
        the cold stores exist) wipes the stores and replays the
        checkpoint's cold-tier rows, then re-syncs the pager's host
        mirrors to the restored page table — the paged state resumes
        bit-exactly on every storage codec."""
        from repro.checkpoint import (latest_step, load_aux_arrays,
                                      load_checkpoint, load_manifest)
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint under {directory!r}")
        manifest = load_manifest(directory, step)
        paging = (manifest.get("extra") or {}).get("paging")
        if self._pager is None and paging is not None:
            raise ValueError(
                "checkpoint holds a paged bank; call init_paged_state "
                "first so this session has a pager and cold stores to "
                "restore into")
        if self._pager is not None and paging is None:
            raise ValueError(
                "checkpoint carries no cold-tier snapshot (saved from "
                "a non-paged session); restore it into a non-paged "
                "state instead")
        state = load_checkpoint(directory, step, like)
        if self._pager is not None:
            mine = {"stores": sorted(self._pager.stores),
                    "dtypes": {n: str(s.dtype) for n, s
                               in self._pager.stores.items()}}
            theirs = {"stores": paging["stores"],
                      "dtypes": paging.get("dtypes", mine["dtypes"])}
            if mine != theirs:
                raise ValueError(
                    f"checkpoint cold tier has stores {theirs} but this "
                    f"session pages {mine} — codec/tree configuration "
                    "mismatch")
            aux = load_aux_arrays(directory, step)
            for name, store in self._pager.stores.items():
                # wipe first: rows written AFTER the save must read as
                # the default row again, exactly as at save time
                store.clear()
                ids = aux[f"cold/{name}/ids"]
                if ids.size:
                    store.write_rows(ids, aux[f"cold/{name}/rows"])
            self._pager.adopt(state)
        journal = (manifest.get("extra") or {}).get("journal")
        if journal is not None:
            rest = getattr(self.mechanism, "restore_journal", None)
            if rest is None:
                raise NotImplementedError(
                    f"mechanism {self.mechanism.name!r} cannot replay the "
                    "checkpoint's dispatch journal")
            rest(journal)
            if state.ledger is not None:
                # sid is static pytree metadata, so it came from `like`,
                # not the checkpoint — adopt the journaled generation
                state = state._replace(
                    ledger=state.ledger.replace(sid=int(journal["sid"])))
        return state

    def sync_round(self, params, batches, key):
        """One ledgered synchronous round: every live owner contributes;
        exhausted owners are zero-weighted out. A fully-refused round is a
        no-op (the regularizer must not keep shrinking a model nobody is
        training)."""
        if self.strategy != "sync":
            raise ValueError("sync_round() needs strategy='sync'")
        step_fn = self._require_step()
        live = [self.mechanism.authorize(i) for i in range(self.n_owners)]
        if not any(live):
            return params
        return step_fn(params, batches, key,
                       jnp.asarray(live, jnp.float32))
