"""Online serving mode: a long-lived windowed controller over the fleet
engine, with checkpoint/restore of the full carry.

Everything else in ``storage/`` is *offline*: build a full ``[T, O, J]``
trace, run one ``lax.scan``, read the metrics.  Production control is
*online* -- rate observations arrive every 100 ms window and the controller
must step incrementally, for days, and survive restarts (the long-running
feedback-service framing of SDN storage QoS, arXiv:1805.06169, and the
control-theory throttler, arXiv:2511.16177).

``FleetService`` is that loop.  It ingests one window of rate observations
at a time and advances the *same* ``window_step`` the offline scan uses
(``storage/simulator.py``) under a donated-carry jit, so:

* the disciplines cannot drift -- streaming N windows through
  ``FleetService.step`` is **bitwise identical** to one offline
  ``simulate_fleet`` scan of the concatenated trace, for every registered
  policy and both telemetry modes (``tests/test_service.py``);
* the horizon is unbounded -- there is no trace array to outgrow, and with
  ``telemetry="streaming"`` the resident state is the ~[O, J] carry;
* crash recovery is exact -- ``save()`` checkpoints the complete
  ``WindowCarry`` (queues, volumes, policy state, allocation, StreamStats)
  through ``repro/checkpoint``; ``restore()`` resumes bitwise from any
  saved window (save -> kill -> restore == the uninterrupted run).

Each round carries three profiler spans, ``fleet.fetch``, ``fleet.copy``
(the host side of the window's copy to the device) and ``fleet.dispatch``
(the call of the jitted step): ``TraceAnnotation``s on the clock of the
device planes, which record nothing without a profiler session.

The carry's pytree *paths* are the checkpoint naming contract: leaves are
saved keyed by ``jax.tree_util.keystr`` paths (``.queue``,
``.stats.served_sum``, ...), so the ``WindowCarry``/``StreamStats`` field
names must stay stable across versions
(``telemetry.stream_stats_leaf_paths``, DESIGN.md section 10).
"""
from __future__ import annotations

import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.policies import PolicyContext
from repro.storage import telemetry
from repro.storage.faults import FaultPlan, lost_telemetry_row
from repro.storage.simulator import (
    FleetConfig,
    FleetResult,
    StreamResult,
    WindowCarry,
    WindowOut,
    _resolve_policy,
    init_carry,
    window_step,
)


class IngestResult(NamedTuple):
    """What one ``FleetService.ingest`` round did.

    out:       the window's ``WindowOut`` (trajectory mode) or None.
    delivered: True when the observation arrived (possibly after
               retries); False when the watchdog substituted the
               loss-mask path.
    attempts:  fetch attempts made (1 = first try succeeded).
    """

    out: Optional[WindowOut]
    delivered: bool
    attempts: int


class FleetService:
    """A long-lived fleet controller stepped one observation window at a
    time.

    Args:
      cfg: FleetConfig.  ``partition`` must be ``"none"`` -- the online
        loop is a host-driven single-process service (shard the offline
        engine instead for batch sweeps).
      nodes: [J] or [O, J] compute nodes per job (priorities).
      volume: [O, J] total RPCs per job per target (inf = unbounded).
      capacity_per_tick: optional [O] per-OST service rates.
      max_backlog: optional [O, J] client in-flight caps.
      control_code: traced policy selector (requires ``control="coded"``).
      checkpoint_dir: where ``save()``/``restore()`` keep carries; may be
        None for a checkpoint-less service.
      fault_plan: optional ``faults.FaultPlan`` ([W, O] leaves).  Each
        ``step`` consumes row ``window % W`` (the plan tiles an unbounded
        online horizon the way rate traces tile), unless the caller
        passes an explicit per-step fault row.
      checkpoint_on_fault: with a ``checkpoint_dir``, ``save()``
        automatically *before* stepping into any window where an OST
        transitions up -> down, so a post-mortem ``restore()`` replays
        the run from the disturbance onward.

    Usage::

        svc = FleetService(cfg, nodes, volume, checkpoint_dir="ckpt/")
        for rates_w in observation_source():      # [window_ticks, O, J]
            out = svc.step(rates_w)
            if svc.window % 600 == 0:
                svc.save()                        # survive a crash
        # after a crash: a fresh FleetService + svc.restore() resumes
        # bitwise where the last save() left off
    """

    def __init__(
        self,
        cfg: FleetConfig,
        nodes,
        volume,
        capacity_per_tick=None,
        max_backlog=None,
        control_code=None,
        checkpoint_dir: Optional[str] = None,
        keep_checkpoints: int = 3,
        fault_plan: Optional[FaultPlan] = None,
        checkpoint_on_fault: bool = True,
    ):
        if cfg.partition != "none":
            raise ValueError(
                'FleetService runs the single-process online loop; '
                f'partition={cfg.partition!r} is an offline-scan feature '
                '(use simulate_fleet for sharded batch runs)')
        self.cfg = cfg
        self.checkpoint_dir = checkpoint_dir
        self.keep_checkpoints = keep_checkpoints
        self.checkpoint_on_fault = checkpoint_on_fault
        self._policy = _resolve_policy(cfg, control_code)
        self._control_code = (None if control_code is None
                              else jnp.asarray(control_code, jnp.int32))

        volume = np.asarray(volume, np.float32)
        n_ost, n_jobs = volume.shape
        self.n_ost, self.n_jobs = n_ost, n_jobs
        nodes = jnp.asarray(nodes, jnp.float32)
        if nodes.ndim == 1:
            nodes = jnp.broadcast_to(nodes, (n_ost, n_jobs))
        self._nodes = nodes
        if capacity_per_tick is None:
            self._cap_tick = jnp.full((n_ost,), cfg.capacity_per_tick,
                                      jnp.float32)
        else:
            self._cap_tick = jnp.asarray(capacity_per_tick, jnp.float32)
        if max_backlog is None:
            self._backlog_cap = jnp.full((n_ost, n_jobs), cfg.max_backlog,
                                         jnp.float32)
        else:
            self._backlog_cap = jnp.asarray(max_backlog, jnp.float32)

        if fault_plan is not None:
            fault_plan = FaultPlan(*(np.asarray(x, np.float32)
                                     for x in fault_plan))
            for name, leaf in zip(FaultPlan._fields, fault_plan):
                if leaf.ndim != 2 or leaf.shape[1] != n_ost:
                    raise ValueError(
                        f"fault_plan.{name} must be [W, n_ost={n_ost}]; "
                        f"got {leaf.shape}")
        self._fault_plan = fault_plan
        # host-side liveness shadow for the fault-transition checkpoint
        # trigger (which OSTs were up at the end of the last step)
        self._up_prev = np.ones(n_ost, bool)
        #: windows advanced through the watchdog loss-mask path
        self.lost_windows = 0
        #: total ingest retries used across the service lifetime
        self.retry_count = 0

        # the arrays stay *traced* jit arguments (not baked constants) so
        # the compiled step is the same program the offline scan body runs
        # -- constant folding must not get a chance to fork the numerics.
        # ``faults_w=None`` vs a FaultPlan row are different pytree
        # structures, so jit keeps the legacy fault-free program and the
        # faulted program as separate traces automatically.
        def step_fn(nodes, cap_tick, backlog_cap, control_code, carry,
                    rates_w, faults_w):
            ctx = PolicyContext(
                nodes=nodes, cap_w=cap_tick * cfg.window_ticks,
                u_max=cfg.u_max, integer_tokens=cfg.integer_tokens,
                alloc_backend=cfg.alloc_backend, control_code=control_code)
            return window_step(cfg, self._policy, ctx, cap_tick,
                               backlog_cap, carry, rates_w,
                               faults_w=faults_w)

        # donated carry: the previous window's buffers are dead the moment
        # the step returns, so XLA reuses them in place -- the long-lived
        # loop allocates O(1) however many days it runs.  XLA:CPU has no
        # donation (it would warn on every compile), so only donate where
        # the runtime honours it; semantics are identical either way.
        donate = (4,) if jax.default_backend() in ("tpu", "gpu") else ()
        self._step = jax.jit(step_fn, donate_argnums=donate)
        # a donated carry needs one buffer per leaf, and the initializers
        # may hand several zero-filled leaves one shared array
        self._carry = jax.tree.map(
            jnp.copy, init_carry(cfg, self._policy, self._ctx(), volume))

    def _ctx(self) -> PolicyContext:
        return PolicyContext(
            nodes=self._nodes, cap_w=self._cap_tick * self.cfg.window_ticks,
            u_max=self.cfg.u_max, integer_tokens=self.cfg.integer_tokens,
            alloc_backend=self.cfg.alloc_backend,
            control_code=self._control_code)

    # ------------------------------------------------------------ stepping

    def step(self, rates_w, faults_w: Optional[FaultPlan] = None
             ) -> Optional[WindowOut]:
        """Advance one observation window.

        Args:
          rates_w: [window_ticks, O, J] client issue attempts observed
            this window (what the OSTs saw arrive).
          faults_w: optional fault row ([O] leaves) for this window;
            defaults to the constructor ``fault_plan``'s row for the
            current window index (None when the service has no plan).

        Returns the window's ``WindowOut`` (served/demand/alloc/record,
        each [O, J]) in trajectory mode, None in streaming mode (the
        accumulated ``StreamStats`` are at ``self.stats``).

        With ``checkpoint_on_fault`` and a ``checkpoint_dir``, a fault
        row that takes a previously-up OST down triggers ``save()``
        *before* the step, so restore replays from the disturbance.
        """
        with TraceAnnotation("fleet.copy"):
            rates_w = jnp.asarray(rates_w, jnp.float32)
        if rates_w.shape != (self.cfg.window_ticks, self.n_ost, self.n_jobs):
            raise ValueError(
                f"rates_w must be [window_ticks={self.cfg.window_ticks}, "
                f"O={self.n_ost}, J={self.n_jobs}]; got {rates_w.shape}")
        if faults_w is None and self._fault_plan is not None:
            faults_w = self._fault_plan.row(self.window)
        if faults_w is not None:
            faults_w = FaultPlan(*(jnp.asarray(x, jnp.float32)
                                   for x in faults_w))
            for name, leaf in zip(FaultPlan._fields, faults_w):
                if leaf.shape != (self.n_ost,):
                    raise ValueError(
                        f"faults_w.{name} must be a fault *row* "
                        f"[n_ost={self.n_ost}]; got {leaf.shape}")
            up_now = np.asarray(faults_w.up) > 0
            if (self._up_prev & ~up_now).any() and self.checkpoint_on_fault \
                    and self.checkpoint_dir is not None:
                self.save()
            self._up_prev = up_now
        else:
            self._up_prev = np.ones(self.n_ost, bool)
        with TraceAnnotation("fleet.dispatch"):
            self._carry, out = self._step(
                self._nodes, self._cap_tick, self._backlog_cap,
                self._control_code, self._carry, rates_w, faults_w)
        return out

    def run(self, rates, n_windows: Optional[int] = None,
            fault_plan: Optional[FaultPlan] = None):
        """Drive the service from a materialized [T, O, J] trace (tiled
        periodically past its own length when ``n_windows`` asks for
        more), collecting outputs into the same result types
        ``simulate_fleet`` returns.  Mainly a convenience for demos and
        the online==offline oracle tests.

        ``fault_plan`` must cover the run horizon exactly ([n_windows, O]
        leaves, row ``w`` consumed at window ``w``) -- the same absolute
        fault-timeline semantics ``simulate_fleet`` uses, so the bitwise
        online==offline oracle extends to faulted runs."""
        rates = np.asarray(rates, np.float32)
        wt = self.cfg.window_ticks
        trace_windows = rates.shape[0] // wt
        if trace_windows == 0:
            raise ValueError(
                f"trace covers {rates.shape[0]} ticks < one {wt}-tick window")
        if n_windows is None:
            n_windows = trace_windows
        if fault_plan is not None and fault_plan.n_windows != n_windows:
            raise ValueError(
                f"fault_plan covers {fault_plan.n_windows} windows but the "
                f"run is {n_windows} windows (the plan is never tiled here)")
        outs = []
        for w in range(n_windows):
            s = (w % trace_windows) * wt
            out = self.step(rates[s:s + wt],
                            faults_w=(None if fault_plan is None
                                      else fault_plan.row(w)))
            if out is not None:
                outs.append(out)
        window_seconds = wt * self.cfg.tick_seconds
        if self.cfg.telemetry == "streaming":
            return StreamResult(stats=self.stats, queue_final=self.queue,
                                window_seconds=window_seconds)
        stack = WindowOut(*(jnp.stack(x) for x in zip(*outs)))
        return FleetResult(served=stack.served, demand=stack.demand,
                           alloc=stack.alloc, record=stack.record,
                           queue_final=self.queue,
                           window_seconds=window_seconds)

    def ingest(self, fetch: Callable, faults_w: Optional[FaultPlan] = None,
               retries: int = 3, backoff_s: float = 0.05,
               deadline_s: Optional[float] = None,
               sleep: Callable = time.sleep,
               clock: Callable = time.monotonic) -> IngestResult:
        """One production control round: fetch this window's observation
        with bounded retry + exponential backoff, then step -- and if
        delivery ultimately fails, advance through the loss-mask path
        instead of stalling the loop.

        Args:
          fetch: zero-arg callable returning this window's
            ``[window_ticks, O, J]`` rates, or None / raising on a failed
            delivery attempt (a dropped stats RPC, a timed-out
            collector).
          faults_w: optional fault row forwarded to ``step`` (defaults to
            the constructor plan's row, like ``step``).
          retries: attempts after the first (so ``retries + 1`` fetches
            max).
          backoff_s: first retry delay; doubles per retry (bounded
            exponential backoff).
          deadline_s: optional missed-deadline watchdog: once this much
            wall time has elapsed, no further retry is attempted even if
            the retry budget remains -- the controller must re-allocate
            every 100 ms window, so a late observation is a lost
            observation.
          sleep/clock: injectable for deterministic tests.

        On delivery failure the service steps anyway with zero observed
        arrivals and the window's ``telem_ok`` mask forced to zero: the
        engine keeps draining standing queues at full (fault-adjusted)
        capacity while the policy holds its last delivered observation --
        graceful degradation, not a stalled control plane.  Counted in
        ``self.lost_windows`` / ``self.retry_count``.
        """
        if faults_w is None and self._fault_plan is not None:
            faults_w = self._fault_plan.row(self.window)
        t0 = clock()
        rates_w, attempts = None, 0
        while rates_w is None and attempts <= retries:
            try:
                attempts += 1
                with TraceAnnotation("fleet.fetch"):
                    rates_w = fetch()
            except Exception:
                rates_w = None
            if rates_w is not None:
                break
            if attempts > retries:
                break
            delay = backoff_s * (2.0 ** (attempts - 1))
            if deadline_s is not None:
                remaining = deadline_s - (clock() - t0)
                if remaining <= 0:
                    break                      # watchdog: deadline missed
                delay = min(delay, remaining)
            sleep(delay)
        self.retry_count += attempts - 1
        if rates_w is not None:
            out = self.step(rates_w, faults_w=faults_w)
            return IngestResult(out=out, delivered=True, attempts=attempts)
        self.lost_windows += 1
        zeros = np.zeros((self.cfg.window_ticks, self.n_ost, self.n_jobs),
                         np.float32)
        lost = lost_telemetry_row(self.n_ost, base=faults_w)
        out = self.step(zeros, faults_w=lost)
        return IngestResult(out=out, delivered=False, attempts=attempts)

    # ------------------------------------------------------------- state

    @property
    def carry(self) -> WindowCarry:
        """The live engine state (treat as read-only)."""
        return self._carry

    @property
    def window(self) -> int:
        """Windows completed since init (or since the restored carry's
        origin)."""
        return int(self._carry.window)

    @property
    def queue(self) -> jnp.ndarray:
        """[O, J] standing server-side queues."""
        return self._carry.queue

    @property
    def alloc(self) -> jnp.ndarray:
        """[O, J] the allocation that will be applied next window."""
        return self._carry.alloc

    @property
    def budget(self) -> jnp.ndarray:
        """[O, J] the token budget next window's gate will grant
        (inf = unruled fallback)."""
        return self._policy.gate(self._carry.alloc, self._ctx())

    @property
    def stats(self) -> Optional[telemetry.StreamStats]:
        """Accumulated ``StreamStats`` (streaming telemetry only)."""
        return (self._carry.stats
                if self.cfg.telemetry == "streaming" else None)

    # -------------------------------------------------- checkpoint/restore

    def save(self, step: Optional[int] = None) -> str:
        """Checkpoint the full carry atomically; returns the final path.
        ``step`` defaults to the current window index."""
        from repro import checkpoint

        if self.checkpoint_dir is None:
            raise ValueError("FleetService built without checkpoint_dir")
        if step is None:
            step = self.window
        path = checkpoint.save_checkpoint(self.checkpoint_dir, self._carry,
                                          step=step)
        checkpoint.gc_checkpoints(self.checkpoint_dir,
                                  keep=self.keep_checkpoints)
        return path

    def restore(self, step: Optional[int] = None) -> int:
        """Replace the live carry with a saved one (latest by default);
        returns the restored checkpoint's step.  The service must have
        been built with the same cfg/shapes/policy that wrote the
        checkpoint -- leaves are matched by pytree path and shape, and
        the common config mismatches (different fleet shape, different
        telemetry mode, different control policy) are validated up front
        with errors that name the mismatch instead of surfacing as a
        cryptic leaf-level pytree error."""
        from repro import checkpoint

        if self.checkpoint_dir is None:
            raise ValueError("FleetService built without checkpoint_dir")
        self._validate_checkpoint_meta(
            checkpoint.checkpoint_meta(self.checkpoint_dir, step=step))
        carry, step = checkpoint.restore_checkpoint(
            self.checkpoint_dir, self._carry, step=step)
        self._carry = carry
        return step

    def _validate_checkpoint_meta(self, meta: dict):
        """Fail fast, by name, on checkpoints this service cannot host."""
        by_path = {m["path"]: tuple(m["shape"]) for m in meta["leaves"]}
        q = by_path.get(".queue")
        if q is None:
            raise ValueError(
                f"checkpoint step {meta['step']} has no '.queue' leaf -- "
                "not a FleetService carry checkpoint")
        if q != (self.n_ost, self.n_jobs):
            raise ValueError(
                f"checkpoint step {meta['step']} was written for a fleet "
                f"of (n_ost, n_jobs)={q}; this service is "
                f"({self.n_ost}, {self.n_jobs}) -- restore needs the "
                "same fleet shape the checkpoint was saved from")
        saved_streaming = any(p.startswith(".stats") for p in by_path)
        live_streaming = self.cfg.telemetry == "streaming"
        if saved_streaming != live_streaming:
            saved = "streaming" if saved_streaming else "trajectory"
            live = "streaming" if live_streaming else "trajectory"
            raise ValueError(
                f"checkpoint step {meta['step']} was written with "
                f"telemetry={saved!r} but this service runs "
                f"telemetry={live!r} -- the StreamStats carry cannot be "
                "invented or discarded on restore")
        flat, _ = jax.tree_util.tree_flatten_with_path(self._carry)
        live_pstate = sorted(
            jax.tree_util.keystr(p) for p, _ in flat
            if jax.tree_util.keystr(p).startswith(".policy_state"))
        saved_pstate = sorted(
            p for p in by_path if p.startswith(".policy_state"))
        if live_pstate != saved_pstate:
            raise ValueError(
                f"checkpoint step {meta['step']} was written for a "
                "different control policy: its policy_state leaves are "
                f"{saved_pstate} but cfg.control={self.cfg.control!r} "
                f"carries {live_pstate}")
