"""Decode serving loop: continuous batched greedy decoding against a KV/state
cache — the vLLM-style harness the paper's LL mode targets (§VI-C). Tracks
the serving metrics of Table VII: output tok/s, TTFT, ITL/TPOT.

``pipeline_depth > 1`` turns on the host-level rendering of the paper's
double-buffered decode (runtime/decode.py holds the EP-level one): up to
``depth`` decode steps stay in flight before the host blocks on the oldest,
so step *i+1*'s dispatch work overlaps step *i*'s device execution instead
of serializing on a per-step ``block_until_ready``. Greedy next-token
sampling feeds device-to-device, so no readback sits on the critical path.

EPLB serving hook: with ``MoESpec.track_expert_heat`` the decode state
carries per-logical-expert routed-token counters ("expert_heat"); ``serve``
folds them into ``ServeMetrics`` (load imbalance alongside latency), and
``rebalance_every > 0`` swaps the expert placement between decode steps —
the heat drives the greedy rebalancer (core/placement.py), the serve step is
re-jitted for the new (static) placement, and the token stream is unchanged
because placement only moves *where* experts compute.

Adopt-once physical weights (``MoESpec.params_physical``): the server keeps
expert weights in the ACTIVE placement's physical slot order and rebinds
them host-side exactly once per adoption boundary
(``checkpoint.adopt_expert_params``, old buffers donated so peak memory
stays ~one set of expert weights) — the per-step in-graph logical->physical
gather is skipped, so placed steady-state decode matches the
placement=None per-step cost. Token parity with the per-step-expansion mode
is pinned by tests/test_runtime.py. Compiled serve steps are cached per
placement and BOUNDED to {current, previous}: a server that swaps hundreds
of times must not accumulate compiled executables.

Elastic fault tolerance (docs/DESIGN.md §9): a ``FaultDetector`` (fed by a
deterministic ``FaultInjector`` in tests/benches, by the transport layer in
production) is polled at every decode-step boundary. On a detected rank
death the server drains the pipeline, builds a DEGRADED placement that packs
every expert onto the survivors (the dead rank's row is all EMPTY — zero
slots, zero traffic), re-adopts weights by collapsing through the masked old
placement (reads only surviving replicas — zero data loss whenever the dead
rank's experts had replicas elsewhere), re-jits the step, and keeps serving
on N-1 ranks. When no live replica exists the recovery warns
``DegradedRecovery`` loudly and falls back to checkpoint restore
(``ckpt_dir``) or raises — never silent corruption. A rejoin re-expands to a
full-width placement at the next boundary; the placement-salted routing hash
force-rebuilds handles exactly once per transition, after which the fast
path resumes. The greedy token stream is placement-invariant, so surviving-
rank decode tokens are bitwise-identical to an uninterrupted run
(tests/test_elastic.py). With ``min_replicas >= 2`` (the fault-domain
replica floor) the checkpoint fallback becomes unreachable for any single
correlated failure — every adopted placement keeps that many replicas of
every expert on distinct ranks and distinct fault domains (pods), and is
shrink-feasibility-prechecked at adoption, so even a whole pod dying at
one boundary recovers through the masked rebind with zero restores
(``ServeMetrics.checkpoint_restores``, asserted in bench_fault).

Preemption (``runtime/fault.py PreemptionGuard``): SIGTERM/SIGINT is polled
at the same boundaries — the server drains in-flight steps, writes a
placement-tagged checkpoint (``ckpt_dir``), and returns cleanly with
``preempted=True`` instead of dying mid-collective. A ``StragglerWatchdog``
watches the ITL stream and its flag count lands in ``ServeMetrics``."""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (adopt_expert_params, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.core import placement as PL
from repro.models import get_model
from repro.models.config import ArchConfig
from repro.parallel.sharding import arch_rules, init_from_specs
from repro.runtime.fault import (DegradedRecovery, FaultDetector,
                                 PreemptionGuard, StragglerWatchdog)
from repro.runtime.steps import (make_paged_serve_step, make_serve_step,
                                 paged_serve_state_specs, serve_state_specs)
from repro.runtime.telemetry import (NULL_SERIES, NULL_TRACER, json_safe,
                                     recording)


@dataclasses.dataclass
class ServeMetrics:
    ttft_s: float
    itl_mean_s: float
    itl_p99_s: float
    output_tok_s: float
    total_tokens: int
    # --- continuous-batching percentiles (ContinuousDecodeServer only;
    # per-REQUEST distributions under real admission, not batch means) ---
    ttft_p50_s: float | None = None
    ttft_p95_s: float | None = None
    ttft_p99_s: float | None = None
    itl_p50_s: float | None = None
    itl_p95_s: float | None = None
    requests_completed: int | None = None
    serve_steps: int | None = None
    # paged-KV accounting: allocator high-water vs the dense B x S_max
    # reservation the fixed-batch engine would have pinned (both in pages)
    pages_peak: int | None = None
    pages_dense_equiv: int | None = None
    per_request: list | None = None        # per-request ttft/itl records
    # --- EPLB load counters (None when the config doesn't track heat) ---
    expert_heat: list | None = None        # per-logical-expert routed tokens
    heat_max_mean: float | None = None     # max/mean per-expert load ratio
    rank_heat_max_mean: float | None = None  # max/mean per-EP-rank load
    # --- elastic fault tolerance (runtime/fault.py; docs/DESIGN.md §9) ---
    degraded_steps: int = 0                # decode steps served with <N alive
    recovery_count: int = 0                # shrink + expand transitions taken
    recovery_latency_s: float | None = None  # total wall time inside recovery
    recovery_events: list | None = None    # per-transition records (dicts)
    checkpoint_restores: int = 0           # recoveries that needed a restore
    #                                        (0 under a satisfied replica
    #                                        floor — the bench asserts it)
    alive_ranks: list | None = None        # EP ranks alive at end of serve
    stragglers_flagged: int = 0            # watchdog outlier ITL steps
    preempted: bool = False                # SIGTERM drain-and-checkpoint exit
    # --- telemetry (runtime/telemetry.py; None when tracing is off) ---
    timeline: dict | None = None           # Tracer.summary(): per-span count
    #                                        + total seconds aggregates
    series: list | None = None             # TimeSeries rows (per-window and,
    #                                        continuous engine, per-step)

    def as_dict(self):
        # json_safe: the telemetry rows (and any caller-added fields) may
        # carry numpy scalars — as_dict feeds json.dumps in benches/CI
        return json_safe(dataclasses.asdict(self))


class DecodeServer:
    def __init__(self, cfg: ArchConfig, batch: int, max_len: int, mesh=None,
                 params=None, seed=0, pipeline_depth: int = 1,
                 rebalance_every: int = 0, num_redundant_experts: int = 0,
                 fault_injector=None, fault_detector: FaultDetector | None = None,
                 miss_threshold: int = 2, ckpt_dir: str | None = None,
                 min_replicas: int = 1, fault_domains=None,
                 max_slots_per_rank: int | None = None,
                 tracer=None, series=None, heat_decay: float = 0.0):
        self.cfg, self.mesh, self.batch = cfg, mesh, batch
        self.pipeline_depth = max(int(pipeline_depth), 1)
        # telemetry (runtime/telemetry.py): host-side, boundary-scoped only —
        # spans/rows wrap code that ALREADY runs at step boundaries, so
        # tracing on vs off is bitwise-identical on the token stream (pinned
        # by tests/test_telemetry.py). None -> shared no-op singletons.
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.series = NULL_SERIES if series is None else series
        self._win_itls: list[float] = []    # ITLs since the last window row
        # heat decay for the rebalancer's tracker: >0 fades old windows so
        # the placement tracks DRIFTING load instead of the all-time sum
        self.heat_decay = float(heat_decay)
        # EPLB: swap expert placements every `rebalance_every` decode steps,
        # driven by the tracked heat (requires MoESpec.track_expert_heat)
        self.rebalance_every = int(rebalance_every)
        self.num_redundant_experts = int(num_redundant_experts)
        # fault-domain replica floor (docs/DESIGN.md §9): every adopted
        # placement keeps >= min_replicas replicas of every expert on
        # distinct ranks (and distinct fault domains when the topology
        # permits), so ANY single correlated failure — up to a whole pod —
        # recovers through the zero-data-loss masked rebind, never a
        # checkpoint restore. fault_domains=None derives pod boundaries
        # from the EP mesh geometry (core/plan.py rank_pod arithmetic).
        self.min_replicas = int(min_replicas)
        if self.min_replicas < 1:
            raise ValueError(f"min_replicas={min_replicas} must be >= 1")
        self.max_slots_per_rank = max_slots_per_rank
        self.fault_domains = fault_domains
        if self.rebalance_every and not (cfg.moe and cfg.moe.track_expert_heat):
            raise ValueError("rebalance_every requires an MoE config with "
                             "track_expert_heat=True (the heat drives the "
                             "rebalancer)")
        self.placements: list = []          # placements adopted, in order
        self._sched = None
        self._heat_drained = None           # float64 totals of drained counters
        self._rank_loads = None             # [N] float64 per-rank load, summed
        #                                     under the placement ACTIVE when
        #                                     each window's heat accrued
        # --- elastic fault tolerance (docs/DESIGN.md §9) ---
        # the injector is the deterministic test/bench fault source; the
        # detector is the serving-boundary heartbeat monitor (production
        # feeds it from the transport layer and passes it in directly)
        self.ckpt_dir = ckpt_dir
        self._injector = fault_injector
        self._detector = fault_detector
        self.recoveries: list[dict] = []    # shrink/expand transition records
        self._degraded_steps = 0
        self._recovery_wall_s = 0.0
        self._ckpt_restores = 0
        self.preempted = False
        self.guard = PreemptionGuard()      # SIGTERM/SIGINT -> drain + ckpt
        self.watchdog = StragglerWatchdog(
            tracer=self.tracer if self.tracer.enabled else None)
        n = self._ep_size()
        if (fault_injector is not None or fault_detector is not None):
            if not (cfg.moe and n > 1):
                raise ValueError("fault tolerance requires an MoE config on "
                                 "an EP mesh (ep extent > 1) — rank death is "
                                 "an EP-placement event")
            if self._detector is None:
                self._detector = FaultDetector(n,
                                               miss_threshold=miss_threshold)
            elif self._detector.num_ranks != n:
                raise ValueError(
                    f"fault_detector watches {self._detector.num_ranks} "
                    f"ranks but the EP extent is {n}")
        if self.rebalance_every or self._detector is not None:
            if self.rebalance_every and n <= 1:
                pass                        # rebalance hook inert off-mesh
            elif n > 1:
                if (cfg.moe.num_experts + self.num_redundant_experts) % n:
                    raise ValueError(
                        f"num_experts={cfg.moe.num_experts} + "
                        f"num_redundant_experts={self.num_redundant_experts} "
                        f"must divide by the EP extent {n}")
                if cfg.moe.placement is None and cfg.moe.num_experts % n:
                    raise ValueError(
                        f"num_experts={cfg.moe.num_experts} must divide by "
                        f"the EP extent {n} for the contiguous initial "
                        "placement — pass an explicit MoESpec.placement")
                if self.fault_domains is None and self.min_replicas > 1:
                    self.fault_domains = self._derived_domains(n)
                if self.min_replicas > 1:
                    E = cfg.moe.num_experts
                    if self.num_redundant_experts < E * (self.min_replicas - 1):
                        raise ValueError(
                            f"min_replicas={self.min_replicas} floor needs "
                            f"num_redundant_experts >= E*(min_replicas-1) = "
                            f"{E * (self.min_replicas - 1)}, got "
                            f"{self.num_redundant_experts}")
                    if cfg.moe.placement is not None:
                        # gate at adoption: the INITIAL placement must already
                        # satisfy the floor and survive any single correlated
                        # failure — infeasibility surfaces here, not during a
                        # recovery
                        PL.validate_floor(cfg.moe.placement,
                                          self.min_replicas,
                                          self.fault_domains,
                                          where="initial placement")
                        PL.assert_shrink_feasible(
                            E, cfg.moe.placement.num_redundant, n,
                            domains=self.fault_domains,
                            min_replicas=self.min_replicas,
                            max_slots_per_rank=self.max_slots_per_rank,
                            placement=cfg.moe.placement)
                self._sched = PL.RebalanceScheduler(
                    cfg.moe.num_experts, n,
                    num_redundant=self.num_redundant_experts,
                    decay=self.heat_decay,
                    initial=cfg.moe.placement,
                    min_replicas=self.min_replicas,
                    domains=self.fault_domains,
                    max_slots_per_rank=self.max_slots_per_rank)
        self.model = get_model(cfg)
        self.params_physical = bool(cfg.moe and cfg.moe.params_physical)
        # Caller-supplied ``params`` must already match the config's weight
        # layout: logical [E, ...] normally, cfg.moe.placement's physical
        # slot order under params_physical (convert with
        # checkpoint.adopt_expert_params, or restore_checkpoint(placement=
        # cfg.moe.placement), which validates against the recorded
        # fingerprint). Raw arrays carry no layout metadata, so a
        # wrongly-ordered tree with the RIGHT row count (e.g. logical
        # weights under a pure-permutation placement) cannot be detected
        # here — the checkpoint path is the validated way in. Under
        # params_physical the server also takes OWNERSHIP of the tree:
        # adoption boundaries donate the old expert buffers (slot count
        # permitting), so the caller's original arrays may be deleted.
        if params is None:
            # random init ALWAYS goes through the logical [E, ...] spec —
            # per-slot init under a redundant placement would give replicas
            # of one expert different weights, breaking the replica
            # invariant. Physical mode then adopts the initial placement
            # once (logical -> physical expansion, host-level). Expert
            # weights shard over the EP axis (arch_rules), each shard built
            # on its own device.
            init_cfg = self._logical_cfg()
            params = init_from_specs(jax.random.PRNGKey(seed),
                                     self.model.params_spec(init_cfg), mesh,
                                     arch_rules(init_cfg))
            if self.params_physical and cfg.moe.placement is not None:
                params = adopt_expert_params(
                    params, self.model.params_spec(init_cfg),
                    None, cfg.moe.placement)
        self.params = params
        self.state = self._init_state(batch, max_len)
        # compiled serve steps, keyed by placement, bounded to
        # {current, previous} — see _compiled_step
        self._step_cache: collections.OrderedDict = collections.OrderedDict()
        self.step = self._compiled_step()

    # ---- engine hooks (ContinuousDecodeServer overrides both) ----

    def _init_state(self, batch: int, max_len: int):
        """Zeroed decode state for this engine's layout (dense KV caches)."""
        st_spec, _ = serve_state_specs(self.cfg, batch, max_len)
        return jax.tree.map(
            jnp.zeros_like,
            init_from_specs(jax.random.PRNGKey(1), st_spec, self.mesh))

    def _step_factory(self):
        """Uncompiled serve step for this engine's layout. _compiled_step
        jits THIS — so placement re-jits, fault recoveries, and the bounded
        step cache work identically for the dense and paged engines."""
        return make_serve_step(self.cfg, self.mesh)

    def _logical_cfg(self) -> ArchConfig:
        """This server's config with the expert-weight layout forced logical
        (spec metadata for init and for locating expert axes at adoption)."""
        if not self.params_physical:
            return self.cfg
        return dataclasses.replace(
            self.cfg, moe=dataclasses.replace(self.cfg.moe,
                                              params_physical=False))

    def _compiled_step(self):
        """Compiled serve step for the CURRENT placement. Cached per
        placement and bounded to two entries (current + previous): each
        compiled executable pins device buffers, so an unbounded per-swap
        cache is a leak on a long-lived rebalancing server. Today a
        placement key never recurs (the scheduler version-bumps every
        changed table and _maybe_rebalance early-returns on an unchanged
        one), so the previous entry is a one-window grace retention, not a
        reuse path — the cache-hit branch is defensive; what matters is
        the bound."""
        key = self.cfg.moe.placement if self.cfg.moe else None
        if key in self._step_cache:
            self._step_cache.move_to_end(key)
        else:
            self._step_cache[key] = jax.jit(
                self._step_factory(), donate_argnums=(1,))
            while len(self._step_cache) > 2:
                self._step_cache.popitem(last=False)
        return self._step_cache[key]

    # ---- EPLB hook: heat-driven placement swaps between steps ----

    def _device_heat(self):
        if isinstance(self.state, dict) and "expert_heat" in self.state:
            return np.asarray(jax.device_get(self.state["expert_heat"]),
                              np.float64)
        return None

    def _tracked_heat(self):
        """[E] float64 per-expert routed-token totals: the live on-device
        counter plus everything drained at rebalance boundaries (draining
        keeps the f32 device counter at per-window magnitude, so a
        long-lived server never hits f32 integer saturation)."""
        dev = self._device_heat()
        if dev is None:
            return None
        return dev if self._heat_drained is None else self._heat_drained + dev

    def _ep_size(self) -> int:
        m = self.cfg.moe
        if not m or self.mesh is None:
            return 0
        import math
        sizes = [self.mesh.shape[a] for a in m.ep_axis
                 if a in self.mesh.shape]
        return math.prod(sizes) if sizes else 0

    def _derived_domains(self, n: int):
        """Fault domains from the EP mesh geometry — same derivation as
        ``EpGroup.fault_domains()``: a hierarchical EP axis makes the pod
        (``rank // inner_size``, `core/plan.py rank_pod`) the correlated-
        failure unit; a flat axis leaves every rank its own domain."""
        m = self.cfg.moe
        sizes = [self.mesh.shape[a] for a in m.ep_axis
                 if a in self.mesh.shape]
        inner = sizes[-1] if sizes else n
        if len(sizes) > 1 and n // inner > 1:
            return PL.domains_from_geometry(n, inner)
        return PL.trivial_domains(n)

    def _record_window(self, step_idx: int, kind: str, dev, rl):
        """One time-series row for a heat window that just ended (rebalance
        or recovery boundary). Strictly host-side: ``dev``/``rl`` are the
        host arrays the boundary ALREADY drained — recording never adds a
        device sync. Drains the per-window ITL buffer either way."""
        imb = None if rl is None else PL.imbalance(rl)
        if self.tracer.enabled and imb is not None:
            self.tracer.counter("rank_imbalance", float(imb))
        itls = self._win_itls
        self._win_itls = []
        if not self.series.enabled:
            return
        self.series.record(
            kind=kind, step=step_idx,
            window_tokens=None if dev is None else float(dev.sum()),
            heat_max_mean=None if dev is None else PL.imbalance(dev),
            imbalance=imb,
            rank_loads=None if rl is None else [float(x) for x in rl],
            itl_mean_s=float(np.mean(itls)) if itls else None,
            alive=(len(self._detector.alive)
                   if self._detector is not None else None),
            stragglers_flagged=self.watchdog.flagged,
            watchdog_rebased=self.watchdog.rebased,
            placements_adopted=len(self.placements))

    def _maybe_rebalance(self, step_idx: int):
        """Every ``rebalance_every`` steps: drain the device heat counter
        into the host-side float64 totals, fold it into the shared
        ``RebalanceScheduler``, and — only when the table actually changed —
        adopt the new placement and re-jit the serve step. The placement
        only moves *where* experts compute — weights are rebound in-graph
        per step (logical mode, models/moe.py) or once right here at the
        adoption boundary (``params_physical``) — so the greedy token
        stream is unchanged either way (pinned by tests)."""
        if (self._sched is None or not self.rebalance_every
                or (step_idx + 1) % self.rebalance_every):
            return
        dev = self._device_heat()
        if dev is None:
            return
        with self.tracer.span("serve.rebalance", step=step_idx):
            self._sched.observe(dev)
            self._heat_drained = (dev if self._heat_drained is None
                                  else self._heat_drained + dev)
            # attribute this window's per-rank load to the placement it
            # actually ran under, BEFORE any swap — rank_heat_max_mean then
            # reports the imbalance experienced, not what the final
            # placement would have had
            rl = PL.rank_loads(dev, self.cfg.moe.placement,
                               self._sched.num_ranks)
            self._rank_loads = (rl if self._rank_loads is None
                                else self._rank_loads + rl)
            self._record_window(step_idx, "rebalance", dev, rl)
            self.state["expert_heat"] = jnp.zeros_like(
                self.state["expert_heat"])
            pl = self._sched.advance()
            old = self.cfg.moe.placement
            if pl is old:
                return              # unchanged table: keep the compiled step
            self.cfg = dataclasses.replace(
                self.cfg, moe=dataclasses.replace(self.cfg.moe, placement=pl))
            self.placements.append(pl)
            self.tracer.instant("placement_swap", step=step_idx,
                                version=len(self.placements))
            if self.params_physical:
                # adopt-once: rebind the physical expert weights from the
                # old placement's slot order to the new one, HOST-LEVEL and
                # exactly once per adoption (old buffers donated — peak
                # memory ~one set of expert weights). The re-jitted step
                # then runs with zero per-step expansion cost.
                with self.tracer.span("serve.adopt", step=step_idx):
                    self.params = adopt_expert_params(
                        self.params,
                        self.model.params_spec(self._logical_cfg()),
                        old, pl)
            self.step = self._compiled_step()

    # ---- elastic fault tolerance: detect -> shrink/expand -> re-adopt ----

    def _poll_faults(self, step_idx: int):
        """Advance the injected fault schedule (tests/benches) and poll the
        detector at a step boundary. Returns the FaultReport when something
        newly died or rejoined, else None. Detection only — the caller
        drains any in-flight pipeline before handing the report to
        ``_recover`` (recovery re-jits the step; in-flight tokens must land
        under the placement that issued them).

        Coalescing: the detector is re-polled until a quiet poll, and every
        report from this boundary merges into ONE (``FaultReport.merge`` —
        dedup, died+rejoined cancels). However many ranks die at a boundary
        — a whole pod at once, or stragglers declared across back-to-back
        polls while the wall clock advances a ``timeout_s`` detector — the
        caller sees a single report and takes a single degraded-placement
        transition: one fingerprint bump, one handle rebuild, one weight
        adoption, not one per dead rank."""
        if self._detector is None:
            return None
        if self._injector is not None:
            self._injector.advance(step_idx)
            for r in range(self._detector.num_ranks):
                if self._injector.is_alive(r):
                    self._detector.heartbeat(r, step_idx)
        merged = self._detector.poll(step_idx)
        while merged:
            more = self._detector.poll(step_idx)
            if not more:
                break
            merged = merged.merge(more)
        if not merged:
            return None
        self.tracer.instant("fault_detected", step=step_idx,
                            died=list(merged.died),
                            rejoined=list(merged.rejoined))
        return merged

    def _poll_boundary(self, step_idx: int):
        """The fault poll and the rebalance check of one unpipelined step
        boundary (the ``serve.poll`` span): recover on a fault report,
        else take the periodic rebalance."""
        with self.tracer.span("serve.poll"):
            report = self._poll_faults(step_idx)
            if report is not None:
                # recovery drains the heat window and advances the
                # placement itself — a coinciding periodic boundary would
                # just dedup to the same table
                self._recover(step_idx, report)
            else:
                self._maybe_rebalance(step_idx)

    def _recover(self, step_idx: int, report):
        """One shrink or expand transition (docs/DESIGN.md §9). Drains the
        heat window, narrows/widens the scheduler to the detector's alive
        set, builds the new placement, and re-adopts the physical expert
        weights by collapsing through the MASKED old placement — reads only
        surviving replicas, so the shrink is zero-data-loss whenever the
        dead ranks' experts had replicas elsewhere. When an expert lost its
        last replica this warns ``DegradedRecovery`` and restores the whole
        tree from ``ckpt_dir`` (rebound to the new placement) or raises —
        never silent corruption. Logical (non-physical) weight mode keeps
        the full [E, ...] tree host/device-side, so no data can be lost and
        only the placement swap happens. The placement-salted routing hash
        force-rebuilds handles exactly once per transition."""
        t0 = time.perf_counter()
        kind = "shrink" if report.died else "expand"
        # per-transition phase durations (satellite of the opaque
        # recovery_latency_s total): repack = scheduler narrow/widen +
        # placement build; adopt = masked weight rebind; restore = the
        # checkpoint fallback. Each also lands as a nested tracer span.
        phases: dict[str, float] = {}
        with self.tracer.span(f"serve.recover:{kind}", step=step_idx,
                              died=list(report.died),
                              rejoined=list(report.rejoined)):
            dev = self._device_heat()
            if dev is not None:
                self._sched.observe(dev)
                self._heat_drained = (dev if self._heat_drained is None
                                      else self._heat_drained + dev)
                rl = PL.rank_loads(dev, self.cfg.moe.placement,
                                   self._sched.num_ranks)
                self._rank_loads = (rl if self._rank_loads is None
                                    else self._rank_loads + rl)
                self._record_window(step_idx, f"recover:{kind}", dev, rl)
                self.state["expert_heat"] = jnp.zeros_like(
                    self.state["expert_heat"])
            tp = time.perf_counter()
            with self.tracer.span("serve.recover:repack"):
                self._sched.set_alive(self._detector.alive)
                old = self.cfg.moe.placement
                pl = self._sched.advance()
            phases["repack_s"] = time.perf_counter() - tp
            event = dict(step=step_idx, kind=kind,
                         died=list(report.died),
                         rejoined=list(report.rejoined),
                         alive=list(self._detector.alive),
                         lost_experts=[], restored_from=None,
                         placement_changed=pl is not old, phases=phases)
            if pl is not old:
                if self.params_physical:
                    src_live = (old if old is not None else
                                PL.identity_placement(
                                    self.cfg.moe.num_experts,
                                    self._sched.num_ranks))
                    lost = (PL.lost_experts(src_live, self._sched.alive)
                            if report.died else ())
                    if lost:
                        # the dead ranks held every replica of these experts:
                        # their physical slot rows are unavailable on a real
                        # pod, so zero-data-loss recovery is impossible
                        event["lost_experts"] = list(lost)
                        ck = (latest_step(self.ckpt_dir)
                              if self.ckpt_dir is not None else None)
                        warnings.warn(DegradedRecovery(
                            f"rank death {list(report.died)} lost every "
                            f"replica of experts {list(lost)[:8]} — "
                            "zero-data-loss shrink impossible; "
                            + (f"restoring from checkpoint step {ck}"
                               if ck is not None else
                               f"no checkpoint available (ckpt_dir="
                               f"{self.ckpt_dir!r})")))
                        if ck is None:
                            # record the failed transition before bailing so
                            # post-mortems see what died and what was lost
                            event["latency_s"] = time.perf_counter() - t0
                            self.recoveries.append(event)
                            raise RuntimeError(
                                f"experts {list(lost)[:8]} unrecoverable "
                                "from surviving ranks and no checkpoint to "
                                f"restore from (ckpt_dir={self.ckpt_dir!r}) "
                                "— pass ckpt_dir= with a saved checkpoint "
                                "or add redundant replicas "
                                "(num_redundant_experts)")
                        new_cfg = dataclasses.replace(
                            self.cfg, moe=dataclasses.replace(self.cfg.moe,
                                                              placement=pl))
                        tp = time.perf_counter()
                        with self.tracer.span("serve.checkpoint", restore=True,
                                              ckpt_step=ck):
                            self.params, _ = restore_checkpoint(
                                self.ckpt_dir, ck,
                                self.model.params_spec(new_cfg),
                                mesh=self.mesh, rules=arch_rules(new_cfg),
                                placement=pl)
                        phases["restore_s"] = time.perf_counter() - tp
                        event["restored_from"] = ck
                        self._ckpt_restores += 1
                    else:
                        src = (PL.mask_placement(src_live, self._sched.alive)
                               if report.died else old)
                        tp = time.perf_counter()
                        with self.tracer.span("serve.recover:adopt"):
                            self.params = adopt_expert_params(
                                self.params,
                                self.model.params_spec(self._logical_cfg()),
                                src, pl)
                        phases["adopt_s"] = time.perf_counter() - tp
                self.cfg = dataclasses.replace(
                    self.cfg, moe=dataclasses.replace(self.cfg.moe,
                                                      placement=pl))
                self.placements.append(pl)
                self.tracer.instant("placement_swap", step=step_idx,
                                    version=len(self.placements))
                self.step = self._compiled_step()
        dt = time.perf_counter() - t0
        event["latency_s"] = dt
        self._recovery_wall_s += dt
        self.recoveries.append(event)

    def _preempt(self, step_idx: int):
        """SIGTERM/SIGINT drain path: with the pipeline already drained by
        the caller, write a placement-tagged checkpoint (``ckpt_dir``) and
        mark the server preempted — ``decode`` then exits cleanly at this
        step boundary and ``serve`` reports metrics for the tokens that DID
        complete, with ``preempted=True``."""
        self.preempted = True
        if self.ckpt_dir is None:
            return
        pl = self.cfg.moe.placement if self.cfg.moe else None
        with self.tracer.span("serve.checkpoint", step=step_idx,
                              preempt=True):
            save_checkpoint(
                self.ckpt_dir, step_idx + 1, self.params,
                placement=pl if self.params_physical else None,
                extra=dict(preempted=True,
                           alive_ranks=(list(self._detector.alive)
                                        if self._detector is not None
                                        else None)))

    def close(self):
        """Uninstall the preemption signal handlers (restores whatever was
        registered before this server). Call when retiring a server inside
        a longer-lived process; tests do."""
        self.guard.restore()

    def prefill(self, prompts: jax.Array):
        """Token-by-token prefill through the decode path (keeps this harness
        family-agnostic; a production server runs a fused prefill)."""
        t0 = time.perf_counter()
        tok = None
        with self.tracer.span("serve.prefill", tokens=int(prompts.shape[1])):
            for i in range(prompts.shape[1]):
                tok, self.state = self.step(self.params, self.state,
                                            {"tokens": prompts[:, i:i + 1]})
            jax.block_until_ready(tok)
        return tok, time.perf_counter() - t0

    def decode(self, first_tok: jax.Array, steps: int):
        if self.pipeline_depth > 1:
            return self._decode_pipelined(first_tok, steps)
        tok = first_tok
        itls = []
        outs = [np.asarray(tok)]
        record_itls = self.series.enabled
        for i in range(steps):
            t0 = time.perf_counter()
            with self.tracer.span("serve.step"):
                tok, self.state = self.step(self.params, self.state,
                                            {"tokens": tok})
                jax.block_until_ready(tok)
            itls.append(time.perf_counter() - t0)
            if record_itls:
                self._win_itls.append(itls[-1])
            with self.tracer.span("serve.readback"):
                outs.append(np.asarray(tok))
            self._poll_boundary(i)
            if self._detector is not None and self._detector.dead:
                self._degraded_steps += 1
            if self.guard.should_stop:
                self._preempt(i)
                break
        return np.concatenate(outs, axis=1), np.asarray(itls)

    def _decode_pipelined(self, first_tok: jax.Array, steps: int):
        """Double-buffered decode: keep up to ``pipeline_depth`` steps in
        flight, blocking only on the oldest. ITL is completion-to-completion
        between drain points — steady state only: the fill interval (start
        to first completion, which amortizes ``depth`` issues) is excluded,
        so ``len(itls) == steps - 1`` (single-step windows fall back to the
        fill interval). serve() therefore charges tok/s against its own
        wall clock, never ``itls.sum()``."""
        tok = first_tok
        pending: collections.deque[jax.Array] = collections.deque()
        done: list[jax.Array] = []          # D2H conversion deferred: keeps
        marks = []                          # the timed loop free of readbacks,
        t0 = time.perf_counter()            # matching the unpipelined path
        for i in range(steps):
            tok, self.state = self.step(self.params, self.state,
                                        {"tokens": tok})
            pending.append(tok)
            if len(pending) >= self.pipeline_depth:
                d = pending.popleft()
                jax.block_until_ready(d)
                marks.append(time.perf_counter())
                done.append(d)
            boundary = (self._sched is not None and self.rebalance_every
                        and (i + 1) % self.rebalance_every == 0)
            with self.tracer.span("serve.poll"):
                report = self._poll_faults(i)
            if boundary or report is not None or self.guard.should_stop:
                # placement swap / recovery / preemption boundary: drain the
                # in-flight window first (a swap re-jits the step; in-flight
                # tokens must land under the placement that issued them).
                # The drain and any post-swap recompile are charged to the
                # ITL stream on purpose — swaps and recoveries cost real
                # latency, and the serving metrics should show it.
                with self.tracer.span("serve.drain", pending=len(pending)):
                    while pending:
                        d = pending.popleft()
                        jax.block_until_ready(d)
                        marks.append(time.perf_counter())
                        done.append(d)
                if report is not None:
                    self._recover(i, report)
                elif boundary:
                    self._maybe_rebalance(i)
                if self.guard.should_stop:
                    self._preempt(i)
                    break
            if self._detector is not None and self._detector.dead:
                self._degraded_steps += 1
        while pending:
            d = pending.popleft()
            jax.block_until_ready(d)
            marks.append(time.perf_counter())
            done.append(d)
        if len(marks) > 1:
            itls = np.diff(np.asarray(marks))
        else:                               # degenerate 1-step window
            itls = np.asarray([m - t0 for m in marks])
        outs = [np.asarray(first_tok)] + [np.asarray(d) for d in done]
        return np.concatenate(outs, axis=1), itls

    def serve(self, prompts: jax.Array, gen_steps: int) -> ServeMetrics:
        first, ttft = self.prefill(prompts)
        t0 = time.perf_counter()
        toks, itls = self.decode(first, gen_steps)
        # tok/s over the decode wall clock, not itls.sum(): the pipelined
        # path's itls are steady-state-only (fill excluded), so summing them
        # would inflate its tok/s relative to the depth-1 baseline
        decode_wall = time.perf_counter() - t0
        total = toks.shape[0] * toks.shape[1]
        for t in itls:      # straggler signal over the ITL stream
            self.watchdog.observe(float(t))
        # EPLB: fold the tracked per-expert heat into the metrics so serving
        # benchmarks report load imbalance alongside latency
        heat = self._tracked_heat()
        heat_mm = rank_mm = None
        if heat is not None:
            heat_mm = PL.imbalance(heat)
            n = self._ep_size()
            phys = (self.cfg.moe.placement.num_slots
                    if self.cfg.moe.placement is not None
                    else self.cfg.moe.num_experts)
            if n > 1 and phys % n == 0:
                # per-window attribution: drained windows were charged to
                # their active placement in _maybe_rebalance; only the
                # residual device counter ran under the current placement
                rl = PL.rank_loads(self._device_heat(),
                                   self.cfg.moe.placement, n)
                if self._rank_loads is not None:
                    rl = self._rank_loads + rl
                rank_mm = PL.imbalance(rl)
        return ServeMetrics(
            ttft_s=ttft, itl_mean_s=float(itls.mean()),
            itl_p99_s=float(np.percentile(itls, 99)),
            output_tok_s=total / (ttft + decode_wall),
            total_tokens=total,
            expert_heat=None if heat is None else heat.tolist(),
            heat_max_mean=heat_mm, rank_heat_max_mean=rank_mm,
            degraded_steps=self._degraded_steps,
            recovery_count=len(self.recoveries),
            recovery_latency_s=self._recovery_wall_s or None,
            recovery_events=list(self.recoveries) or None,
            checkpoint_restores=self._ckpt_restores,
            alive_ranks=(list(self._detector.alive)
                         if self._detector is not None else None),
            stragglers_flagged=self.watchdog.flagged,
            preempted=self.preempted,
            timeline=self.tracer.summary() or None,
            series=list(self.series.rows) or None)


class ContinuousDecodeServer(DecodeServer):
    """Continuous-batching serving engine over the paged KV pool.

    Same fault/rebalance/preemption machinery as DecodeServer — the engine
    hooks swap the decode state for per-layer page pools
    (models/kv_pages.py) and the step for the paged split-KV decode
    (runtime/steps.make_paged_serve_step) — plus ``serve_requests``: a
    request-level loop where admission, slot recycling, and page alloc/free
    all happen at the same step boundaries placement swaps and fault
    recoveries already use. ``batch`` is the fixed max concurrency (slot
    count); the page table / kv_lens / active mask are host-built per-step
    inputs with fixed shapes, so join/leave never retraces the step.

    Per-request token streams are bitwise identical to running each request
    alone through this same engine (and across placement swaps / rank-kill
    transitions): rows are batch-independent end to end given zero-drop MoE
    capacity — a capacity_factor would let co-residents compete for expert
    slots and break that, so it is rejected here.

    Pipelining stays depth-1: continuous batching feeds each request's
    PREVIOUS output token back in, so the host readback the fixed-batch
    pipelined path avoids is inherent here.
    """

    def __init__(self, cfg: ArchConfig, batch: int, max_len: int, mesh=None,
                 *, page_size: int = 8, num_pages: int | None = None,
                 **kwargs):
        from repro.models import kv_pages as KVP
        from repro.models.registry import get_model as _gm
        if _gm(cfg).paged_decode_step is None:
            raise NotImplementedError(
                f"family {cfg.family!r} has no paged decode path")
        a = cfg.attn
        if a is None or a.window is not None:
            raise NotImplementedError(
                "continuous batching requires non-windowed attention "
                "(sliding-window paged decode is not implemented)")
        if a.kv_chunk % page_size:
            raise ValueError(
                f"kv_chunk={a.kv_chunk} must be a multiple of "
                f"page_size={page_size} — chunked prefill attention and the "
                "paged decode kernel must agree on tiling")
        if cfg.moe and cfg.moe.capacity_factor is not None:
            raise ValueError(
                "continuous batching requires zero-drop MoE routing "
                "(capacity_factor=None): capacity competition couples "
                "co-resident requests and breaks solo-parity")
        if int(kwargs.get("pipeline_depth", 1)) > 1:
            raise ValueError("continuous batching is depth-1: the next step "
                             "consumes this step's tokens host-side")
        self.page_size = int(page_size)
        # page-table width: enough pages for max_len, rounded up so the
        # configured split count divides it (padding entries are pad pages)
        mp = KVP.pages_for_tokens(max_len, self.page_size)
        s = max(int(a.decode_kv_splits), 1)
        self.max_pages = -(-mp // s) * s
        # default pool = the dense-equivalent reservation (batch x max_len):
        # never exhausts; pass a smaller pool to realize the memory win
        self.num_pages = (int(num_pages) if num_pages is not None
                          else batch * self.max_pages)
        self.max_len = max_len
        self.reqsched = None
        super().__init__(cfg, batch, max_len, mesh, **kwargs)

    def _init_state(self, batch: int, max_len: int):
        st_spec, _ = paged_serve_state_specs(
            self.cfg, batch, self.num_pages, self.page_size, self.max_pages)
        return jax.tree.map(
            jnp.zeros_like,
            init_from_specs(jax.random.PRNGKey(1), st_spec, self.mesh))

    def _step_factory(self):
        return make_paged_serve_step(self.cfg, self.mesh)

    def serve_requests(self, requests, max_steps: int | None = None
                       ) -> ServeMetrics:
        """Run the continuous-batching loop until every request completes
        (or ``max_steps``). Placement swaps, fault recoveries, and
        preemption run at the same boundaries as admission/retirement —
        page tables are host state, so a transition can never corrupt them
        (pinned by tests/test_elastic.py)."""
        from repro.models.kv_pages import PageAllocator, pages_for_tokens
        from repro.runtime.scheduler import ContinuousScheduler
        allocator = PageAllocator(self.num_pages, self.page_size)
        sched = ContinuousScheduler(requests, self.batch, self.max_pages,
                                    allocator,
                                    tracer=(self.tracer if self.tracer.enabled
                                            else None))
        self.reqsched = sched
        record = self.series.enabled
        t0 = time.perf_counter()
        step_idx = 0
        marks = []
        while not sched.done:
            if max_steps is not None and step_idx >= max_steps:
                break
            # one step = four boundary spans; the admission counters ride
            # on serve.admit (host state the scheduler already holds)
            with self.tracer.span("serve.admit") as span:
                feed = sched.advance(step_idx)
                span.set_metadata(**sched.counters)
            with self.tracer.span("serve.step"):
                tok, self.state = self.step(self.params, self.state, feed)
                jax.block_until_ready(tok)
            now = time.perf_counter()
            with self.tracer.span("serve.readback") as span:
                if "held_load" in self.state and recording(self.tracer):
                    # the step's held-expert load rides on the tokens'
                    # readback (the step is already done)
                    load = np.asarray(self.state["held_load"])
                    span.set_metadata(local_rows=int(load[0]),
                                      experts_hit=int(load[1]))
                sched.observe(np.asarray(tok), now)
            if record:
                # pure host state — engine occupancy at this boundary
                itl = now - (marks[-1] if marks else t0)
                self._win_itls.append(itl)
                self.series.record(
                    kind="step", step=step_idx, itl_s=itl,
                    queue_depth=len(sched.queue), active=sched.live_count,
                    pages_live=allocator.live_count,
                    pages_peak=allocator.peak_live)
            marks.append(now)
            self._poll_boundary(step_idx)
            if self._detector is not None and self._detector.dead:
                self._degraded_steps += 1
            if self.guard.should_stop:
                self._preempt(step_idx)
                break
            step_idx += 1
        wall = time.perf_counter() - t0
        step_itls = np.diff(np.asarray(marks)) if len(marks) > 1 else np.asarray([0.0])
        for t in step_itls:
            self.watchdog.observe(float(t))
        recs = [sched.request_metrics(rid) for rid in sorted(sched.finished)]
        ttfts = np.asarray([r["ttft_s"] for r in recs]) if recs else np.asarray([0.0])
        itls = np.concatenate([np.asarray(r["itl_s"]) for r in recs
                               if r["itl_s"]] or [np.zeros(1)])
        total = int(sum(r["tokens"] for r in recs))
        heat = self._tracked_heat()
        heat_mm = rank_mm = None
        if heat is not None:
            heat_mm = PL.imbalance(heat)
            n = self._ep_size()
            phys = (self.cfg.moe.placement.num_slots
                    if self.cfg.moe.placement is not None
                    else self.cfg.moe.num_experts)
            if n > 1 and phys % n == 0:
                rl = PL.rank_loads(self._device_heat(),
                                   self.cfg.moe.placement, n)
                if self._rank_loads is not None:
                    rl = self._rank_loads + rl
                rank_mm = PL.imbalance(rl)
        return ServeMetrics(
            ttft_s=float(ttfts.mean()),
            itl_mean_s=float(itls.mean()),
            itl_p99_s=float(np.percentile(itls, 99)),
            output_tok_s=total / wall if wall > 0 else 0.0,
            total_tokens=total,
            ttft_p50_s=float(np.percentile(ttfts, 50)),
            ttft_p95_s=float(np.percentile(ttfts, 95)),
            ttft_p99_s=float(np.percentile(ttfts, 99)),
            itl_p50_s=float(np.percentile(itls, 50)),
            itl_p95_s=float(np.percentile(itls, 95)),
            requests_completed=len(recs),
            serve_steps=step_idx,
            pages_peak=allocator.peak_live,
            # dense baseline = un-rounded B x ceil(S_max/page): what a dense
            # [B, S_max] cache would pin regardless of live occupancy
            pages_dense_equiv=self.batch * pages_for_tokens(self.max_len,
                                                            self.page_size),
            per_request=recs,
            expert_heat=None if heat is None else heat.tolist(),
            heat_max_mean=heat_mm, rank_heat_max_mean=rank_mm,
            degraded_steps=self._degraded_steps,
            recovery_count=len(self.recoveries),
            recovery_latency_s=self._recovery_wall_s or None,
            recovery_events=list(self.recoveries) or None,
            checkpoint_restores=self._ckpt_restores,
            alive_ranks=(list(self._detector.alive)
                         if self._detector is not None else None),
            stragglers_flagged=self.watchdog.flagged,
            preempted=self.preempted,
            timeline=self.tracer.summary() or None,
            series=list(self.series.rows) or None)
