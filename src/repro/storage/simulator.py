"""Discrete-time storage simulator (replaces the paper's CloudLab/Lustre
testbed; DESIGN.md section 2 "hardware adaptation").

Model
-----
* time advances in ticks (default 10 ms); an observation window is
  ``window_ticks`` ticks (default 10 -> 100 ms, the paper's chosen frequency).
* 1 token = 1 RPC = 1 MB bulk I/O (paper: "1RPC=1Token", Lustre 1 MB bulk).
* each job issues RPCs into its server-side queue according to a rate trace,
  bounded by its remaining volume (closed loop) and a client-side
  max-RPCs-in-flight backlog cap (~16 per process, Lustre default).
* the OST serves at most ``capacity_per_tick`` RPCs per tick, in two phases
  mirroring the Lustre NRS TBF semantics (paper Section II-A / III-D):
    1. *ruled* jobs (finite token budget) dequeue up to their remaining window
       budget; when gated wants exceed disk capacity, service is scaled
       proportionally (approximating the deadline-heap fairness).  Unused
       gated capacity is NOT given to other ruled jobs -- plain TBF is
       non-work-conserving; fixing that at the allocator level is AdapTBF's
       entire point.
    2. *unruled* jobs (no rule / rule stopped -> infinite budget) form the
       fallback queue: they are served opportunistically from whatever
       capacity phase 1 left idle.
* control disciplines are pluggable ``ControlPolicy`` objects resolved from
  the registry in ``core/policies.py`` (``adaptbf``, ``static``, ``nobw``,
  ``static_wc``, ``aimd``, ...): the policy decides the window-0 gating
  (``init_alloc``), how an allocation becomes a token budget (``gate``), and
  the next allocation from the window's observation (``step``).
* the demand signal d_x fed to every policy is what the server can observe:
  RPCs served during the window plus the standing queue at window end.
  Counting the queue is essential for allocation-starved jobs -- their
  clients' in-flight caps throttle issuance to ~the service rate, so an
  issuance-only signal would report u_x ~= 1 and never trigger the Eq. 6
  deficit boost (DESIGN.md section 3).

ONE window engine (``_run_windows``) drives both entry points:

* ``simulate``       -- one storage target (the paper's testbed): the O=1
                        view of the fleet engine, outputs squeezed.
* ``simulate_fleet`` -- ``n_ost`` targets with per-OST queues and (possibly
  heterogeneous) capacities; clients stripe their RPC streams across targets
  (see ``storage/striping.py``).  Every OST runs its policy independently
  -- the per-OST service/control path is the *same* function ``vmap``-ed
  over the OST axis, so the paper's decentralization claim is structural:
  a fleet run bitwise-matches independent single-OST runs on the same
  per-OST demand (tested in ``tests/test_fleet_sim.py``).

The engine is a ``lax.scan`` over windows -- jittable end to end.  The
per-window body is a standalone step (``window_step``) over a named
``WindowCarry``: the offline scan here and the online ``FleetService`` loop
(``storage/service.py``) call the *same* function, so the two disciplines
cannot drift -- streaming N windows through the online step is bitwise
identical to one offline scan of the same trace
(``tests/test_service.py``).  The inner per-tick loop is either a
``lax.scan`` of small ops (``serve_backend="scan"``) or one fused
whole-window kernel invocation per window (``serve_backend="fused"``,
``kernels/fleet_window``).  ``control="coded"`` routes through the generic
``CodedPolicy`` combinator so a benchmark sweep can ``vmap`` one compiled
program over scenarios x policies (``benchmarks/fleet_sweep.py``).

Because every per-window op is row-local, the same loop shards across
devices: ``FleetConfig(partition="ost_shard")`` runs ``_run_windows`` under
``shard_map`` over a 1-D ``ost`` device mesh, each device owning a
contiguous block of OST rows (queues, token state, policy state, telemetry
carries all device-local), bitwise-equal to the single-device run
(``tests/test_sharding.py``, DESIGN.md section 8).

Telemetry is selectable (``telemetry="trajectory" | "streaming"``):
trajectory mode materializes the full ``[n_windows, O, J]`` outputs the
paper-figure harnesses consume; streaming mode reduces per-window metric
accumulators *inside* the scan carry (``storage/telemetry.py``) so peak
memory is independent of horizon length, and ``n_windows=`` can extend a
periodic trace to horizons far longer than the materialized rate array.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.policies import (
    CodedPolicy,
    ControlPolicy,
    PolicyContext,
    WindowObs,
    control_codes,
    get_policy,
)
from repro.storage import telemetry
from repro.storage.faults import FaultPlan
from repro.storage.telemetry import StreamStats

_EPS = 1e-9

#: Default coded-policy subset (order defines the traced codes); kept to the
#: paper's three evaluation modes for compatibility with existing sweeps.
DEFAULT_CODED_POLICIES = ("adaptbf", "static", "nobw")
FLEET_CONTROL_CODES = control_codes(DEFAULT_CODED_POLICIES)


class SimConfig(NamedTuple):
    capacity_per_tick: float = 20.0    # RPCs/tick the OST can serve (2000/s @10 ms)
    window_ticks: int = 10             # observation window length in ticks
    tick_seconds: float = 0.01
    control: str = "adaptbf"           # any registered policy name
    u_max: float = 64.0
    integer_tokens: bool = True
    max_backlog: float = 256.0         # default client in-flight cap per job
    telemetry: str = "trajectory"      # trajectory | streaming


class FleetConfig(NamedTuple):
    """Static configuration for ``simulate_fleet`` (hashable -> one
    compilation per (shape, control, backend, telemetry) combination)."""

    capacity_per_tick: float = 20.0    # default per-OST capacity (RPCs/tick)
    window_ticks: int = 10
    tick_seconds: float = 0.01
    control: str = "adaptbf"           # any registered policy name | coded
    u_max: float = 64.0
    integer_tokens: bool = True
    max_backlog: float = 256.0
    alloc_backend: str = "core"        # core (vmap) | pallas (kernel)
    serve_backend: str = "scan"        # scan (per-tick lax.scan) | fused
                                       #   (whole-window serve kernel, one
                                       #   invocation per window) | mega
                                       #   (whole CONTROL ROUND fused:
                                       #   gate + ticks + observe + policy
                                       #   step, kernels/window_mega;
                                       #   alloc_backend is ignored -- the
                                       #   allocator runs in-block)
    telemetry: str = "trajectory"      # trajectory | streaming
    coded_policies: tuple = DEFAULT_CODED_POLICIES
                                       # member subset for control="coded"
    partition: str = "none"            # none (single device) | ost_shard
                                       #   (shard_map over the OST axis of a
                                       #   1-D device mesh; bitwise-equal to
                                       #   the single-device run)


class SimResult(NamedTuple):
    served: jnp.ndarray        # [n_windows, J] RPCs served per window per job
    demand: jnp.ndarray        # [n_windows, J] observed demand d_x per window
                               #   (RPCs served + standing queue at window end)
    alloc: jnp.ndarray         # [n_windows, J] token budget applied that window
    record: jnp.ndarray        # [n_windows, J] policy record after window
    queue_final: jnp.ndarray   # [J]
    window_seconds: float

    @property
    def throughput_mb_s(self):
        """[n_windows, J] MB/s assuming 1 RPC = 1 MB."""
        return self.served / self.window_seconds


class FleetResult(NamedTuple):
    served: jnp.ndarray        # [n_windows, O, J]
    demand: jnp.ndarray        # [n_windows, O, J]
    alloc: jnp.ndarray         # [n_windows, O, J]
    record: jnp.ndarray        # [n_windows, O, J]
    queue_final: jnp.ndarray   # [O, J]
    window_seconds: float

    @property
    def throughput_mb_s(self):
        """[n_windows, O, J] MB/s assuming 1 RPC = 1 MB."""
        return self.served / self.window_seconds

    def per_ost(self, i: int) -> SimResult:
        """View of one OST's trajectory as a single-target result."""
        return SimResult(
            served=self.served[:, i], demand=self.demand[:, i],
            alloc=self.alloc[:, i], record=self.record[:, i],
            queue_final=self.queue_final[i],
            window_seconds=self.window_seconds,
        )


class StreamResult(NamedTuple):
    """Result of a ``telemetry="streaming"`` run: carry-resident sufficient
    statistics instead of ``[n_windows, ...]`` trajectories.  Stats arrays
    are [O, J] from ``simulate_fleet`` and [J] from ``simulate``; feed them
    to the ``streaming_*`` finalizers in ``storage/metrics.py``."""

    stats: StreamStats
    queue_final: jnp.ndarray   # [O, J] (fleet) or [J] (single target)
    window_seconds: float


# --------------------------------------------------------- shared machinery


def _serve_tick(queue, vol_left, budget, rate_t, backlog_cap, capacity):
    """One tick of two-phase NRS-TBF service: client issuance into the
    server-side queue, then token-gated service and opportunistic fallback.

    Shape-generic over leading axes: jobs live on the LAST axis and
    ``capacity`` broadcasts against ``[..., 1]`` (a scalar for one target).
    The fleet scan path vmaps the 1-D form over the OST axis and the fused
    window kernel (``kernels/fleet_window``) calls the 2-D form directly --
    one definition, so the service discipline cannot drift between backends
    (decentralization stays structural: no op mixes jobs across rows)."""
    headroom = jnp.maximum(backlog_cap - queue, 0.0)
    issued = jnp.minimum(jnp.minimum(rate_t, vol_left), headroom)
    queue = queue + issued
    vol_left = vol_left - issued
    queue = jnp.maximum(queue, 0.0)  # fp guard
    ruled = jnp.isfinite(budget)
    # phase 1: token-gated service for ruled jobs
    want1 = jnp.where(ruled, jnp.minimum(queue, jnp.maximum(budget, 0.0)), 0.0)
    s1 = want1 * jnp.minimum(1.0, capacity / jnp.maximum(
        jnp.sum(want1, axis=-1, keepdims=True), _EPS))
    # phase 2: fallback queue served from idle capacity only
    spare = jnp.maximum(
        capacity - jnp.sum(s1, axis=-1, keepdims=True), 0.0)
    want2 = jnp.where(ruled, 0.0, queue)
    s2 = want2 * jnp.minimum(1.0, spare / jnp.maximum(
        jnp.sum(want2, axis=-1, keepdims=True), _EPS))
    # proportional scaling can overshoot the queue by an ulp; clamping keeps
    # cumulative served <= cumulative issued over long horizons
    served = jnp.minimum(s1 + s2, queue)
    queue = queue - served
    budget = budget - served  # inf stays inf for unruled jobs
    return queue, vol_left, budget, served, issued


# ------------------------------------------------------- the window engine


class HeldObs(NamedTuple):
    """The last observation the controller actually received ([O, J]).

    The last-observation-hold state for telemetry loss: when a window's
    fault row says ``telem_ok == 0`` for an OST, the policy's ``step`` is
    fed this row instead of the fresh window observation, and the held row
    stays put until a delivered window replaces it (consecutive losses
    keep holding the same observation).
    """

    served: jnp.ndarray
    demand: jnp.ndarray
    alloc: jnp.ndarray


class WindowCarry(NamedTuple):
    """The complete cross-window state of the window engine.

    This is the engine's *resume point*: everything the next window needs
    is in here, so checkpointing the carry and feeding the restored pytree
    back into ``window_step`` continues the run bitwise
    (``storage/service.py``).  Field names are part of the checkpoint
    contract -- ``repro/checkpoint`` keys saved leaves by pytree path
    (``.queue``, ``.stats.served_sum``, ...), so renaming a field silently
    orphans every existing checkpoint (pinned by
    ``tests/test_service.py::test_carry_checkpoint_paths_are_stable``);
    extend by *appending* fields (as ``held`` was), never by renaming or
    reordering.
    """

    window: jnp.ndarray        # () int32: windows completed so far
    queue: jnp.ndarray         # [O, J] standing server-side queues
    vol_left: jnp.ndarray      # [O, J] remaining volume per job per target
    policy_state: Any          # policy pytree (shape fixed by cfg.control)
    alloc: jnp.ndarray         # [O, J] allocation applied next window
    stats: Any                 # StreamStats (streaming) | () (trajectory)
    held: HeldObs              # last *delivered* observation (lost-telemetry
                               #   hold state; fault injection, DESIGN.md 11)


class WindowOut(NamedTuple):
    """One window's trajectory-mode observation ([O, J] each)."""

    served: jnp.ndarray
    demand: jnp.ndarray
    alloc: jnp.ndarray
    record: jnp.ndarray


def init_carry(cfg: FleetConfig, policy: ControlPolicy, ctx: PolicyContext,
               volume) -> WindowCarry:
    """Window-0 carry: empty queues, full volumes, the policy's cold-start
    state and allocation, and zeroed streaming stats when enabled."""
    n_ost, n_jobs = ctx.nodes.shape
    if cfg.telemetry not in ("trajectory", "streaming"):
        raise ValueError(f"unknown telemetry mode: {cfg.telemetry!r}")
    def zoj():
        # fresh buffer per leaf (donated carries must not alias leaves)
        return jnp.zeros((n_ost, n_jobs), jnp.float32)

    return WindowCarry(
        window=jnp.int32(0),
        queue=zoj(),
        vol_left=jnp.asarray(volume, jnp.float32),
        policy_state=policy.init_state(ctx),
        alloc=policy.init_alloc(ctx),
        stats=(telemetry.init_stats(n_ost, n_jobs)
               if cfg.telemetry == "streaming" else ()),
        # init_alloc called again (not aliased to .alloc), see above
        held=HeldObs(served=zoj(), demand=zoj(),
                     alloc=policy.init_alloc(ctx)),
    )


def _serve_window(cfg: FleetConfig, queue, vol_left, budget0, rates_w,
                  backlog_cap, cap_tick):
    """All ticks of one window -> (queue, vol_left, served_window)."""
    if cfg.serve_backend == "fused":
        # imported lazily: the kernel path pulls in pallas machinery
        # that the plain scan backend never needs
        from repro.kernels.fleet_window import ops as window_ops
        return window_ops.fleet_window_serve(
            queue, vol_left, budget0, rates_w, backlog_cap, cap_tick)
    if cfg.serve_backend == "scan":
        serve_tick = jax.vmap(_serve_tick)

        def tick_fn(carry, rate_t):
            queue, vol_left, budget = carry
            queue, vol_left, budget, served, _ = serve_tick(
                queue, vol_left, budget, rate_t, backlog_cap, cap_tick)
            return (queue, vol_left, budget), served

        (queue, vol_left, _), served_t = jax.lax.scan(
            tick_fn, (queue, vol_left, budget0), rates_w
        )
        return queue, vol_left, served_t.sum(axis=0)
    raise ValueError(f"unknown serve_backend: {cfg.serve_backend!r}")


def window_step(cfg: FleetConfig, policy: ControlPolicy, ctx: PolicyContext,
                cap_tick, backlog_cap, carry: WindowCarry, rates_w,
                axis_name: Optional[str] = None, faults_w=None):
    """One observation window: gate, serve every tick, observe, re-allocate.

    THE per-window body -- the offline ``lax.scan`` in ``_run_windows`` and
    the online ``FleetService`` loop both call exactly this function, which
    is what makes the online==offline bitwise oracle free.

    Args:
      cfg/policy/ctx: static configuration, control discipline, per-run
        context (``ctx.cap_w`` must equal ``cap_tick * cfg.window_ticks``).
      cap_tick: [O] per-target service rate; backlog_cap: [O, J].
      carry: the ``WindowCarry`` from the previous window (or
        ``init_carry``).
      rates_w: [window_ticks, O, J] this window's client issue attempts.
      axis_name: mesh axis when running inside ``shard_map``.
      faults_w: optional ``faults.FaultPlan`` row ([O] leaves) -- this
        window's fault state (see below).  None means no fault machinery
        in the trace at all (the legacy program, bit for bit).

    Fault semantics (DESIGN.md section 11).  All three effects are
    row-local, so the sharded engine needs no new mesh crossings:

    * down (``up == 0``): the OST serves nothing and its clients issue
      nothing (their RPCs have nowhere to land), so queue and remaining
      volumes freeze -- volume conservation holds through the outage.
    * droop: ``cap_scale`` multiplies the window's effective service rate.
    * lost telemetry (``telem_ok == 0``): the engine serves normally but
      the policy's ``step`` sees the previously *delivered* observation
      (``carry.held``, explicit last-observation-hold).  Capacity and
      liveness are NOT held: AdapTBF's controller runs *on* the OST
      (decentralized), so it always knows its own hardware state --
      what rides (droppable) RPCs is the client demand statistics.

    The policy sees the *effective* capacity in ``ctx.cap_w`` and the
    liveness column in ``obs.up``; streaming telemetry folds utilization
    against effective capacity and advances the row-local fault counters.

    Returns ``(carry', out)`` with ``out`` a ``WindowOut`` in trajectory
    mode and ``None`` in streaming mode (the stats live in the carry).
    """
    if faults_w is None:
        ctx_w, cap_tick_w, up_col = ctx, cap_tick, None
    else:
        # effective service rate: down kills it, droop scales it.  With an
        # all-ones row every op below is an IEEE identity, so a no-fault
        # plan is bitwise the no-plan program.
        cap_tick_w = cap_tick * faults_w.up * faults_w.cap_scale
        rates_w = rates_w * faults_w.up[None, :, None]
        ctx_w = ctx._replace(cap_w=cap_tick_w * cfg.window_ticks)
        up_col = faults_w.up[:, None]
    if cfg.serve_backend == "mega":
        # the whole control round -- gate, every tick, observation select,
        # policy step -- in ONE fused invocation per window, so engine and
        # allocator state stay block-resident across the phase boundary
        # (imported lazily like the other kernel backends)
        from repro.kernels.window_mega import ops as mega_ops
        (queue, vol_left, served_w, demand, obs_served, obs_demand,
         obs_alloc, pstate, alloc_next) = mega_ops.mega_window_round(
            policy, ctx_w, cap_tick_w, backlog_cap, carry.queue,
            carry.vol_left, carry.alloc, carry.held, carry.policy_state,
            rates_w,
            telem_ok=None if faults_w is None else faults_w.telem_ok,
            up=None if faults_w is None else faults_w.up)
    else:
        budget0 = policy.gate(carry.alloc, ctx_w)
        queue, vol_left, served_w = _serve_window(
            cfg, carry.queue, carry.vol_left, budget0, rates_w, backlog_cap,
            cap_tick_w)
        demand = served_w + queue
        if faults_w is None:
            obs_served, obs_demand, obs_alloc = served_w, demand, carry.alloc
        else:
            delivered = faults_w.telem_ok[:, None] > 0
            obs_served = jnp.where(delivered, served_w, carry.held.served)
            obs_demand = jnp.where(delivered, demand, carry.held.demand)
            obs_alloc = jnp.where(delivered, carry.alloc, carry.held.alloc)
        pstate, alloc_next = policy.step(
            carry.policy_state,
            WindowObs(served=obs_served, demand=obs_demand, alloc=obs_alloc,
                      up=up_col), ctx_w)
    if cfg.telemetry == "streaming":
        stats = telemetry.update_stats(carry.stats, served_w, demand,
                                       carry.alloc, ctx_w.cap_w,
                                       axis_name=axis_name,
                                       faults_w=faults_w)
        out = None
    else:
        stats = carry.stats
        out = WindowOut(served=served_w, demand=demand, alloc=carry.alloc,
                        record=policy.record(pstate, ctx_w))
    return WindowCarry(window=carry.window + 1, queue=queue,
                       vol_left=vol_left, policy_state=pstate,
                       alloc=alloc_next, stats=stats,
                       held=HeldObs(served=obs_served, demand=obs_demand,
                                    alloc=obs_alloc)), out


def _run_windows(cfg: FleetConfig, policy: ControlPolicy, nodes, rates,
                 volume, cap_tick, backlog_cap, control_code,
                 n_windows: Optional[int], axis_name: Optional[str] = None,
                 fault_plan: Optional[FaultPlan] = None):
    """The single window loop behind both entry points.

    nodes/volume/backlog_cap: [O, J]; rates: [T, O, J]; cap_tick: [O].
    ``n_windows`` extends (or trims) the horizon by indexing the trace
    periodically; None runs exactly the windows the trace covers.

    ``axis_name`` names the mesh axis when the loop runs inside
    ``shard_map`` (``partition="ost_shard"``): every array above is then
    the *local* OST shard and the only cross-device op is the streaming
    busy-flag psum (``telemetry.update_stats``).

    ``fault_plan`` (optional, [W, O] leaves) must cover the *run* horizon
    exactly -- one row per executed window.  Unlike the rate trace it is
    never tiled: on a tiled horizon the demand repeats but the fault
    timeline stays absolute, which is the useful semantics (an outage at
    window 1500 of a periodic trace).

    Returns ``(queue_final, outs)`` where ``outs`` is the per-window
    (served, demand, alloc, record) stack in trajectory mode or the final
    ``StreamStats`` in streaming mode.
    """
    t_total, n_ost, n_jobs = rates.shape
    trace_windows = t_total // cfg.window_ticks
    if trace_windows == 0:
        raise ValueError(
            f"trace covers {t_total} ticks < one {cfg.window_ticks}-tick window")
    if n_windows is None:
        n_windows = trace_windows
    tiled = n_windows != trace_windows
    if fault_plan is not None:
        fault_plan = jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), fault_plan)
        for name, leaf in zip(FaultPlan._fields, fault_plan):
            if leaf.shape != (n_windows, n_ost):
                raise ValueError(
                    f"fault_plan.{name} must be [n_windows={n_windows}, "
                    f"n_ost={n_ost}]; got {leaf.shape} (the plan covers "
                    "the run horizon, one row per executed window)")
    trace = rates[: trace_windows * cfg.window_ticks].reshape(
        trace_windows, cfg.window_ticks, n_ost, n_jobs)
    cap_w = cap_tick * cfg.window_ticks
    ctx = PolicyContext(
        nodes=nodes, cap_w=cap_w, u_max=cfg.u_max,
        integer_tokens=cfg.integer_tokens, alloc_backend=cfg.alloc_backend,
        control_code=control_code)
    streaming = cfg.telemetry == "streaming"

    def window_fn(carry, xs_w):
        rates_w, faults_w = xs_w
        if tiled:
            rates_w = jax.lax.dynamic_index_in_dim(
                trace, jnp.mod(carry.window, trace_windows), keepdims=False)
        return window_step(cfg, policy, ctx, cap_tick, backlog_cap, carry,
                           rates_w, axis_name=axis_name, faults_w=faults_w)

    carry0 = init_carry(cfg, policy, ctx, volume)
    xs = (None if tiled else trace, fault_plan)
    carry, outs = jax.lax.scan(window_fn, carry0, xs, length=n_windows)
    return carry.queue, (carry.stats if streaming else outs)


def _run_windows_sharded(cfg: FleetConfig, policy: ControlPolicy, nodes,
                         rates, volume, cap_tick, backlog_cap, control_code,
                         n_windows: Optional[int],
                         fault_plan: Optional[FaultPlan] = None):
    """``_run_windows`` under ``shard_map`` over a 1-D device mesh on the
    OST axis (``partition="ost_shard"``).

    Per-OST queues, token state, policy state, and streaming-telemetry
    carries all live on the device that owns the row: the window loop's
    body is row-local by the decentralization contract (``core/policies``),
    so each shard runs the *same program* the single-device engine runs on
    its rows and the concatenated result is bitwise identical.  The only
    per-window mesh crossing is the int32 busy-flag psum in streaming mode
    (exact -- see ``telemetry.update_stats``); trajectories stay sharded
    until the caller gathers them.

    A ``fault_plan`` shards ``P(None, "ost")`` like every other piece of
    row state -- each device consumes only its own OSTs' fault rows, so
    fault injection adds **no** mesh crossings and the bitwise guarantee
    extends to faulted runs (``tests/test_faults.py``).
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import ost_mesh

    n_ost = rates.shape[1]
    mesh = ost_mesh()
    n_dev = mesh.devices.size
    if n_ost % n_dev:
        raise ValueError(
            f'partition="ost_shard" needs n_ost ({n_ost}) divisible by the '
            f"mesh size ({n_dev} devices); pad the fleet or force a "
            "compatible device count (--xla_force_host_platform_device_count)")

    def body(nodes, rates, volume, cap_tick, backlog_cap, *rest):
        rest = list(rest)
        code = rest.pop(0) if control_code is not None else None
        plan = rest.pop(0) if fault_plan is not None else None
        return _run_windows(cfg, policy, nodes, rates, volume, cap_tick,
                            backlog_cap, code, n_windows, axis_name="ost",
                            fault_plan=plan)

    oj = P("ost", None)
    in_specs = [oj, P(None, "ost", None), oj, P("ost"), oj]
    args = [nodes, rates, volume, cap_tick, backlog_cap]
    if control_code is not None:
        in_specs.append(P())
        args.append(control_code)
    if fault_plan is not None:
        in_specs.append(FaultPlan(*(P(None, "ost"),) * 3))
        args.append(jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), fault_plan))
    if cfg.telemetry == "streaming":
        outs_specs = telemetry.stats_pspecs("ost")
    else:
        outs_specs = WindowOut(*(P(None, "ost", None),) * 4)
    run = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=(oj, outs_specs), check_vma=False)
    return run(*args)


def _dispatch_windows(cfg: FleetConfig, policy: ControlPolicy, nodes, rates,
                      volume, cap_tick, backlog_cap, control_code,
                      n_windows: Optional[int],
                      fault_plan: Optional[FaultPlan] = None):
    if cfg.partition == "ost_shard":
        return _run_windows_sharded(cfg, policy, nodes, rates, volume,
                                    cap_tick, backlog_cap, control_code,
                                    n_windows, fault_plan=fault_plan)
    if cfg.partition == "none":
        return _run_windows(cfg, policy, nodes, rates, volume, cap_tick,
                            backlog_cap, control_code, n_windows,
                            fault_plan=fault_plan)
    raise ValueError(f"unknown partition: {cfg.partition!r}")


def _resolve_policy(cfg, control_code) -> ControlPolicy:
    coded = cfg.control == "coded"
    if coded and control_code is None:
        raise ValueError('cfg.control == "coded" requires control_code')
    if not coded and control_code is not None:
        raise ValueError('control_code requires cfg.control == "coded"')
    if coded:
        return CodedPolicy(cfg.coded_policies)
    return get_policy(cfg.control)


# ------------------------------------------------------------ single target


@functools.partial(jax.jit, static_argnames=("cfg", "n_windows"))
def simulate(
    cfg: SimConfig,
    nodes: jnp.ndarray,
    issue_rate: jnp.ndarray,
    volume: jnp.ndarray,
    max_backlog: Optional[jnp.ndarray] = None,
    n_windows: Optional[int] = None,
) -> SimResult:
    """Simulate one storage target: the O=1 view of the fleet engine.

    Args:
      cfg: SimConfig (static arg -> one compilation per control mode).
      nodes: [J] compute nodes per job (priorities derive from these).
      issue_rate: [T, J] client issue attempts (RPCs per tick).
      volume: [J] total RPCs each job will ever issue (inf = unbounded).
      max_backlog: optional [J] per-job client in-flight cap (defaults to
        cfg.max_backlog for every job).
      n_windows: optional horizon override; the rate trace is indexed
        periodically beyond its own length (pair with streaming telemetry).
    """
    _t, n_jobs = issue_rate.shape
    # SimConfig's field names are a strict subset of FleetConfig's, so the
    # O=1 lift cannot silently drop a future shared knob
    fcfg = FleetConfig(**cfg._asdict())
    policy = _resolve_policy(fcfg, None)
    nodes = jnp.asarray(nodes, jnp.float32).reshape(1, n_jobs)
    rates = jnp.asarray(issue_rate, jnp.float32)[:, None, :]
    volume = jnp.asarray(volume, jnp.float32).reshape(1, n_jobs)
    cap_tick = jnp.full((1,), cfg.capacity_per_tick, jnp.float32)
    if max_backlog is None:
        backlog_cap = jnp.full((1, n_jobs), cfg.max_backlog, jnp.float32)
    else:
        backlog_cap = jnp.asarray(max_backlog, jnp.float32).reshape(1, n_jobs)

    queue, outs = _run_windows(fcfg, policy, nodes, rates, volume, cap_tick,
                               backlog_cap, None, n_windows)
    window_seconds = cfg.window_ticks * cfg.tick_seconds
    if cfg.telemetry == "streaming":
        return StreamResult(stats=telemetry.squeeze_stats(outs),
                            queue_final=queue[0],
                            window_seconds=window_seconds)
    served, demand, alloc, record = (x[:, 0] for x in outs)
    return SimResult(served=served, demand=demand, alloc=alloc,
                     record=record, queue_final=queue[0],
                     window_seconds=window_seconds)


# -------------------------------------------------------------------- fleet


@functools.partial(jax.jit, static_argnames=("cfg", "n_windows"))
def simulate_fleet(
    cfg: FleetConfig,
    nodes: jnp.ndarray,
    issue_rate: jnp.ndarray,
    volume: jnp.ndarray,
    capacity_per_tick: Optional[jnp.ndarray] = None,
    max_backlog: Optional[jnp.ndarray] = None,
    control_code: Optional[jnp.ndarray] = None,
    n_windows: Optional[int] = None,
    fault_plan: Optional[FaultPlan] = None,
) -> FleetResult:
    """Simulate ``n_ost`` storage targets with striped client demand.

    Args:
      cfg: FleetConfig (static).  ``cfg.control`` names a registered policy,
        or ``"coded"`` (see ``control_code``).
      nodes: [J] or [O, J] compute nodes per job.
      issue_rate: [T, O, J] per-target client issue attempts (RPCs/tick) --
        the output of a striping policy (``storage.striping``) or raw
        per-OST traces.
      volume: [O, J] total RPCs per job per target (inf = unbounded).
      capacity_per_tick: optional [O] heterogeneous per-OST service rates
        (defaults to cfg.capacity_per_tick everywhere).
      max_backlog: optional [O, J] per-target client in-flight caps.
      control_code: traced scalar int32 selecting the policy at runtime from
        ``cfg.coded_policies`` (default codes: ``FLEET_CONTROL_CODES``);
        requires ``cfg.control == "coded"``.  This is what lets one compiled
        program sweep scenarios x policies under vmap.
      n_windows: optional horizon override; the rate trace is indexed
        periodically beyond its own length (pair with streaming telemetry).
      fault_plan: optional ``faults.FaultPlan`` ([n_windows, O] leaves,
        one row per *executed* window -- never tiled): OST outages freeze
        queues/volumes, capacity droop scales service, lost-telemetry
        windows hold the controller's previous observation (DESIGN.md
        section 11).  A traced pytree argument like ``rates``: plans vary
        freely without recompilation, and ``None`` keeps the legacy
        fault-free program (a separate trace with zero fault overhead).

    Returns:
      FleetResult with [n_windows, O, J] trajectories, or StreamResult when
      ``cfg.telemetry == "streaming"``.

    With ``cfg.partition == "ost_shard"`` the window loop runs under
    ``shard_map`` on a 1-D mesh over every visible device (the device
    count must divide ``n_ost``); results are bitwise identical to the
    default single-device execution.
    """
    _t, n_ost, n_jobs = issue_rate.shape
    policy = _resolve_policy(cfg, control_code)
    nodes = jnp.asarray(nodes, jnp.float32)
    if nodes.ndim == 1:
        nodes = jnp.broadcast_to(nodes, (n_ost, n_jobs))
    if capacity_per_tick is None:
        cap_tick = jnp.full((n_ost,), cfg.capacity_per_tick, jnp.float32)
    else:
        cap_tick = jnp.asarray(capacity_per_tick, jnp.float32)
    if max_backlog is None:
        backlog_cap = jnp.full((n_ost, n_jobs), cfg.max_backlog, jnp.float32)
    else:
        backlog_cap = jnp.asarray(max_backlog, jnp.float32)

    queue, outs = _dispatch_windows(
        cfg, policy, nodes, jnp.asarray(issue_rate, jnp.float32), volume,
        cap_tick, backlog_cap, control_code, n_windows,
        fault_plan=fault_plan)
    window_seconds = cfg.window_ticks * cfg.tick_seconds
    if cfg.telemetry == "streaming":
        return StreamResult(stats=outs, queue_final=queue,
                            window_seconds=window_seconds)
    served, demand, alloc, record = outs
    return FleetResult(served=served, demand=demand, alloc=alloc,
                       record=record, queue_final=queue,
                       window_seconds=window_seconds)


def utilization(result, cfg, capacity_per_tick=None):
    """Per-window fraction of disk capacity actually used.

    Thin re-export kept for compatibility -- the single definition lives in
    ``storage/metrics.py``.
    """
    from repro.storage import metrics
    return metrics.utilization(result, cfg,
                               capacity_per_tick=capacity_per_tick)
