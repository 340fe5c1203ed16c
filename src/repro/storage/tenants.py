"""Tenant axis: batch thousands of independent fleets in one compiled
program.

The paper's setting is a storage *provider* arbitrating many independent
applications; the engine in ``storage/simulator.py`` runs one fleet of O
OSTs x J jobs.  A provider serving millions of users runs many *tenants*
-- each an independent AdapTBF control loop over its own fleet -- and the
benchmark sweeps (``fleet_sweep``, ``scenario_sweep``, ``fault_sweep``)
were already hand-rolling "one program, many configs" by wrapping
``simulate_fleet`` in ad-hoc ``vmap`` towers.  ``simulate_tenants`` makes
that a first-class entry point with a leading fleet axis ``[F, O, J]``:

* **vmap over the window engine.**  The whole ``_run_windows`` loop --
  gate, serve ticks, observe, policy step, telemetry fold -- is vmapped
  over the fleet axis.  Because every engine and policy op is row-local
  (the decentralization contract, ``core/policies.py``), batched
  execution is **bitwise identical** to a Python loop of per-fleet
  ``simulate_fleet`` calls, for every registered policy, both telemetry
  modes, and fault-injected runs (``tests/test_tenants.py``).  This is
  the same leading-axis-extent-independence argument behind fleet ==
  independent-single-OST (PR 1) and sharded == unsharded (PR 4).

* **per-argument broadcasting.**  Each array argument is either *batched*
  (carries the leading ``[F]`` axis) or *shared* (the unbatched rank, one
  copy reused by every fleet -- ``vmap in_axes=None``, so a 5-policy
  sweep over one scenario never materializes 5 rate traces).  Rank
  disambiguates: ``issue_rate`` is ``[T, O, J]`` shared or
  ``[F, T, O, J]`` batched, ``nodes`` is ``[J]``/``[O, J]`` shared or
  ``[F, O, J]`` batched, ``control_code`` is a scalar or ``[F]``, fault
  plans are ``[W, O]`` or ``[F, W, O]`` leaves.

* **2-D device sharding.**  ``cfg.partition == "fleet_shard"`` runs the
  batched loop under ``shard_map`` on a 2-D ``(fleet, ost)`` mesh
  (``launch/mesh.fleet_ost_mesh``): the fleet axis splits whole tenants
  (zero communication crosses it -- tenants are independent programs),
  the ost axis splits each fleet's rows exactly like the 1-D
  ``partition="ost_shard"`` path, and the one per-window busy-OST
  ``psum`` stays inside each fleet's ``ost`` mesh slice (the psum is
  vmapped over the local fleet block, so each fleet's busy flag sums
  only its own rows).  2-D-sharded == unsharded bitwise, proved on
  forced 4-device 2x2 meshes (``tests/test_tenants.py``).

* **telemetry contract.**  A streaming run returns a ``StreamStats``
  whose every leaf carries the leading ``[F]`` axis -- the two int32
  counters included (``windows``/``busy_windows`` become ``[F]``).  The
  shape-polymorphic ``streaming_*`` finalizers in ``storage/metrics.py``
  reduce over the trailing ``[O]``/``[O, J]`` axes only, so per-tenant
  metrics come straight off the batched carry.

One dispatch covers a 16-seed x 5-policy envelope grid or a 10k-tenant
fleet; the adversarial scenario search and policy-zoo gain sweeps
(ROADMAP items 4-5) ride this axis.  ``benchmarks/tenant_scaling.py``
measures batched dispatch against the F-iteration Python loop it
replaces (committed ``BENCH_tenant_scaling.json``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.storage import telemetry
from repro.storage.faults import FaultPlan
from repro.storage.simulator import (
    FleetConfig,
    FleetResult,
    StreamResult,
    WindowOut,
    _resolve_policy,
    _run_windows,
)


def _infer_fleets(batched_extents, n_fleets: Optional[int]) -> int:
    """The fleet-axis extent, from the batched arguments' leading axes
    (which must agree) or the explicit ``n_fleets``."""
    extents = {int(e) for e in batched_extents}
    if n_fleets is not None:
        extents.add(int(n_fleets))
    if not extents:
        raise ValueError(
            "simulate_tenants: no argument carries a leading fleet axis; "
            "batch at least one argument or pass n_fleets= explicitly")
    if len(extents) > 1:
        raise ValueError(
            "simulate_tenants: inconsistent fleet-axis extents "
            f"{sorted(extents)} across the batched arguments"
            + ("/n_fleets" if n_fleets is not None else ""))
    return extents.pop()


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_windows", "n_fleets",
                                    "mesh_shape"))
def simulate_tenants(
    cfg: FleetConfig,
    nodes: jnp.ndarray,
    issue_rate: jnp.ndarray,
    volume: jnp.ndarray,
    capacity_per_tick: Optional[jnp.ndarray] = None,
    max_backlog: Optional[jnp.ndarray] = None,
    control_code: Optional[jnp.ndarray] = None,
    n_windows: Optional[int] = None,
    fault_plan: Optional[FaultPlan] = None,
    n_fleets: Optional[int] = None,
    mesh_shape: Optional[Tuple[int, int]] = None,
) -> FleetResult:
    """Simulate ``F`` independent fleets in one compiled program.

    Every argument of ``simulate_fleet`` is accepted either *shared*
    (its usual rank -- one copy reused by all fleets) or *batched* (a
    leading ``[F]`` axis):

      nodes:             [J] | [O, J] shared; [F, O, J] batched.
      issue_rate:        [T, O, J] shared; [F, T, O, J] batched.
      volume:            [O, J] shared; [F, O, J] batched.
      capacity_per_tick: None | [O] shared; [F, O] batched.
      max_backlog:       None | [O, J] shared; [F, O, J] batched.
      control_code:      None | scalar shared; [F] batched (per-fleet
                         policy selection under ``control="coded"`` --
                         a policy-zoo sweep is one dispatch).
      fault_plan:        None, or [W, O] leaves shared / [F, W, O]
                         batched (per-tenant chaos timelines).

    ``n_fleets`` (static) is required only when *every* argument is
    shared; otherwise it is inferred from the batched leading axes
    (which must agree).

    Partitioning (``cfg.partition``):

      "none"        -- single-device vmap over the fleet axis.
      "fleet_shard" -- ``shard_map`` over the 2-D ``(fleet, ost)`` mesh
                       ``launch.mesh.fleet_ost_mesh(mesh_shape)`` (static
                       ``mesh_shape``, default: all devices on the fleet
                       axis).  ``F`` must divide the fleet axis and
                       ``n_ost`` the ost axis.  Bitwise-equal to
                       ``partition="none"``.
      "ost_shard"   -- rejected: the 1-D mesh is the single-fleet
                       engine's layout; use ``"fleet_shard"`` with
                       ``mesh_shape=(1, n_devices)`` for ost-only
                       sharding of a tenant batch.

    Returns a ``FleetResult`` whose every array carries the leading
    ``[F]`` axis ([F, W, O, J] trajectories, [F, O, J] queues), or a
    ``StreamResult`` whose ``StreamStats`` leaves all do (int32 counters
    become [F]).  Batched results are bitwise a stack of the per-fleet
    ``simulate_fleet`` results.
    """
    issue_rate = jnp.asarray(issue_rate, jnp.float32)
    if issue_rate.ndim not in (3, 4):
        raise ValueError(
            "simulate_tenants: issue_rate must be [T, O, J] (shared) or "
            f"[F, T, O, J] (batched); got shape {issue_rate.shape}")
    n_ost, n_jobs = issue_rate.shape[-2:]

    batched_extents = []

    def classify(x, shared_rank: int, name: str):
        """Append to args/axes: in_axes 0 for a leading-[F] argument,
        None for a shared one (rank decides)."""
        if x.ndim == shared_rank:
            return None
        if x.ndim == shared_rank + 1:
            batched_extents.append(x.shape[0])
            return 0
        raise ValueError(
            f"simulate_tenants: {name} must have rank {shared_rank} "
            f"(shared) or {shared_rank + 1} (leading fleet axis); got "
            f"shape {x.shape}")

    rates_ax = classify(issue_rate, 3, "issue_rate")

    nodes = jnp.asarray(nodes, jnp.float32)
    if nodes.ndim == 1:
        nodes = jnp.broadcast_to(nodes, (n_ost, n_jobs))
    nodes_ax = classify(nodes, 2, "nodes")

    volume = jnp.asarray(volume, jnp.float32)
    vol_ax = classify(volume, 2, "volume")

    if capacity_per_tick is None:
        cap_tick = jnp.full((n_ost,), cfg.capacity_per_tick, jnp.float32)
    else:
        cap_tick = jnp.asarray(capacity_per_tick, jnp.float32)
    cap_ax = classify(cap_tick, 1, "capacity_per_tick")

    if max_backlog is None:
        backlog = jnp.full((n_ost, n_jobs), cfg.max_backlog, jnp.float32)
    else:
        backlog = jnp.asarray(max_backlog, jnp.float32)
    backlog_ax = classify(backlog, 2, "max_backlog")

    args = [nodes, issue_rate, volume, cap_tick, backlog]
    axes = [nodes_ax, rates_ax, vol_ax, cap_ax, backlog_ax]
    # per-fleet inner specs, "ost" in the row slot (None placeholder is
    # replaced by the fleet axis name for batched args on the 2-D mesh)
    inner_specs = [("ost", None), (None, "ost", None), ("ost", None),
                   ("ost",), ("ost", None)]

    if control_code is not None:
        code = jnp.asarray(control_code, jnp.int32)
        args.append(code)
        axes.append(classify(code, 0, "control_code"))
        inner_specs.append(())
        # _resolve_policy only inspects None-ness; the per-fleet [F] form
        # dispatches through the same CodedPolicy combinator
        policy = _resolve_policy(cfg, code)
    else:
        policy = _resolve_policy(cfg, None)

    if fault_plan is not None:
        fault_plan = jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), fault_plan)
        plan_ax = {classify(leaf, 2, f"fault_plan.{name}")
                   for name, leaf in zip(FaultPlan._fields, fault_plan)}
        if len(plan_ax) != 1:
            raise ValueError(
                "simulate_tenants: fault_plan leaves must be uniformly "
                "shared [W, O] or uniformly batched [F, W, O]")
        plan_ax = plan_ax.pop()
        args.append(fault_plan)
        axes.append(None if plan_ax is None else FaultPlan(0, 0, 0))
        inner_specs.append((None, "ost"))

    n_f = _infer_fleets(batched_extents, n_fleets)

    def body(axis_name, *xs):
        xs = list(xs)
        nodes_f, rates_f, vol_f, cap_f, backlog_f = xs[:5]
        rest = xs[5:]
        code_f = rest.pop(0) if control_code is not None else None
        plan_f = rest.pop(0) if fault_plan is not None else None
        return _run_windows(cfg, policy, nodes_f, rates_f, vol_f, cap_f,
                            backlog_f, code_f, n_windows,
                            axis_name=axis_name, fault_plan=plan_f)

    if cfg.partition == "none":
        run = jax.vmap(functools.partial(body, None), in_axes=tuple(axes),
                       axis_size=n_f)
        return _package(cfg, *run(*args))

    if cfg.partition != "fleet_shard":
        raise ValueError(
            f"simulate_tenants: unknown partition {cfg.partition!r} "
            '(use "none" or "fleet_shard"; the 1-D "ost_shard" layout is '
            'the single-fleet engine\'s -- fleet_shard with '
            "mesh_shape=(1, n_devices) shards the ost axis only)")

    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import fleet_ost_mesh

    mesh = fleet_ost_mesh(mesh_shape)
    f_dev = mesh.shape["fleet"]
    o_dev = mesh.shape["ost"]
    if n_f % f_dev:
        raise ValueError(
            f'partition="fleet_shard" needs n_fleets ({n_f}) divisible '
            f"by the mesh fleet axis ({f_dev} devices)")
    if n_ost % o_dev:
        raise ValueError(
            f'partition="fleet_shard" needs n_ost ({n_ost}) divisible '
            f"by the mesh ost axis ({o_dev} devices)")

    in_specs = []
    for i, (ax, inner) in enumerate(zip(axes, inner_specs)):
        # batched args shard their leading axis over "fleet"; shared args
        # replicate across it (every fleet slice reads the same copy)
        batched = ax is not None
        spec = P("fleet", *inner) if batched else P(*inner)
        if fault_plan is not None and i == len(axes) - 1:
            spec = FaultPlan(spec, spec, spec)
        in_specs.append(spec)

    foj = P("fleet", "ost", None)
    if cfg.telemetry == "streaming":
        outs_specs = telemetry.stats_pspecs("ost", lead="fleet")
    else:
        outs_specs = WindowOut(*(P("fleet", None, "ost", None),) * 4)

    def sharded_body(*xs):
        # local blocks: [F/f_dev, ...] batched args, unbatched shared
        # ones; vmap re-batches over the local fleet block with the
        # busy-OST psum named over the ost mesh axis only -- each fleet's
        # flag sums its own rows, never a neighbor tenant's
        local_axes = tuple(0 if ax is not None else None for ax in axes)
        return jax.vmap(functools.partial(body, "ost"),
                        in_axes=local_axes, axis_size=n_f // f_dev)(*xs)

    run = jax.shard_map(sharded_body, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=(foj, outs_specs), check_vma=False)
    return _package(cfg, *run(*args))


def _package(cfg: FleetConfig, queue, outs):
    window_seconds = cfg.window_ticks * cfg.tick_seconds
    if cfg.telemetry == "streaming":
        return StreamResult(stats=outs, queue_final=queue,
                            window_seconds=window_seconds)
    served, demand, alloc, record = outs
    return FleetResult(served=served, demand=demand, alloc=alloc,
                       record=record, queue_final=queue,
                       window_seconds=window_seconds)
