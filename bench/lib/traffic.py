"""The benchmark's traffic generator: one general generator for every
profile under ``bench/profiles/``.

A profile is data: a list of experiments, each a set of jobs with their
compute nodes (the priority weight), their processes, their volume and the
rate shapes they issue (``constant`` from a start tick, periodic ``bursts``)
over the experiment's timeline.  The fleet's jobs are copies of the
experiments' job sets, experiment ``c mod E`` for copy ``c`` in job order,
each copy at a point of its timeline drawn from the seed.

Layout is file per process: each process writes its own file of stripe
count 1, and the fleet's files are placed round robin, the k-th file on
target ``k mod O``, so a job of P processes spreads its rate evenly over P
consecutive targets.  A client keeps at most ``in_flight_per_process`` RPCs
in flight per file, and never issues past its job's volume.

``generate`` draws one fleet from a seed: the same seed and sizes give the
same arrays.  It returns the compact form (the per-job rate trace and the
[O, J] file weights); ``expand`` multiplies them out into the ``[T, O, J]``
per-target rates the program receives.  Summing the expanded rates over
targets gives back each job's (volume-clipped) rate exactly.

This copy is the yardstick and does not change with the program.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple

import numpy as np


class Fleet(NamedTuple):
    """One generated fleet in compact form."""

    nodes: np.ndarray      # [J] compute nodes per job (priority weight)
    trace: np.ndarray      # [T, J] volume-clipped job rates, RPCs/tick
    weights: np.ndarray    # [O, J] share of job j's rate routed to target o
    volume: np.ndarray     # [O, J] RPCs job j may issue to target o (inf)
    backlog: np.ndarray    # [O, J] client in-flight cap on each target
    capacity: np.ndarray   # [O] RPCs per tick each target serves


def _part(part: dict, tau: np.ndarray) -> np.ndarray:
    """Rates of one shape at experiment times ``tau`` (ticks)."""
    since = tau - int(part.get("start_tick", 0))
    kind = part["kind"]
    if kind == "constant":
        return np.where(since >= 0, float(part["rate"]), 0.0)
    if kind == "bursts":
        width = int(part["burst_ticks"])
        on = (since >= 0) & (np.mod(since, int(part["interval_ticks"]))
                             < width)
        return np.where(on, float(part["burst_rpcs"]) / width, 0.0)
    raise ValueError(f"unknown rate shape {kind!r}")


def file_weights(processes: np.ndarray, n_ost: int) -> np.ndarray:
    """[O, J] round-robin placement of one file per process: the fleet's
    k-th file on target ``k mod n_ost``; each file carries an equal share
    of its job's rate."""
    first = np.cumsum(processes) - processes
    w = np.zeros((n_ost, processes.shape[0]), np.float64)
    for j, (k0, p) in enumerate(zip(first, processes)):
        np.add.at(w[:, j], (k0 + np.arange(p)) % n_ost, 1.0 / p)
    return w.astype(np.float32)


def generate(profile: dict, n_ost: int, n_jobs: int,
             capacity_per_tick: float, t_ticks: int, seed: int,
             name: str = "") -> Fleet:
    """Draw one fleet of ``n_ost`` targets and ``n_jobs`` jobs whose rates
    cover ``t_ticks`` ticks, from ``seed`` (any integer)."""
    rng = np.random.default_rng(
        [int(seed) % 2 ** 64, zlib.crc32(name.encode())])
    experiments = profile["experiments"]
    specs, copy_of = [], []
    copy = 0
    while len(specs) < n_jobs:
        exp = experiments[copy % len(experiments)]
        specs += [(exp, job) for job in exp["jobs"]]
        copy_of += [copy] * len(exp["jobs"])
        copy += 1
    specs, copy_of = specs[:n_jobs], np.asarray(copy_of[:n_jobs])

    # each copy's point in its experiment's timeline
    duration = np.asarray([e["duration_ticks"] for e, _ in specs], np.int64)
    phase = rng.integers(0, 2 ** 62, copy)[copy_of] % duration
    tau = np.mod(np.arange(t_ticks)[:, None] + phase[None, :],
                 duration[None, :])
    rates = np.zeros((t_ticks, n_jobs), np.float64)
    for j, (_, job) in enumerate(specs):
        for part in job["parts"]:
            rates[:, j] += _part(part, tau[:, j])

    volume = np.asarray([np.inf if job["volume_rpcs"] is None
                         else float(job["volume_rpcs"])
                         for _, job in specs])
    processes = np.asarray([int(job["processes"]) for _, job in specs])
    nodes = np.asarray([float(job["nodes"]) for _, job in specs])

    # a client never issues past its job's volume
    cum = np.minimum(np.cumsum(rates, axis=0), volume[None, :])
    trace = np.diff(cum, axis=0, prepend=0.0).astype(np.float32)
    w = file_weights(processes, n_ost)
    on = w > 0
    vol_oj = np.where(on, volume[None, :].astype(np.float32), 0.0) \
        * np.where(on, w, 1.0)
    files = np.rint(w * processes[None, :])
    backlog = files * float(profile["in_flight_per_process"])
    capacity = np.full(n_ost, float(capacity_per_tick), np.float32)
    return Fleet(nodes=nodes.astype(np.float32), trace=trace, weights=w,
                 volume=vol_oj.astype(np.float32),
                 backlog=backlog.astype(np.float32), capacity=capacity)


def expand(fleet: Fleet, rows=None) -> np.ndarray:
    """[T, O, J] per-target rates on the host (``rows`` selects targets)."""
    w = fleet.weights if rows is None else fleet.weights[rows]
    out = np.empty((fleet.trace.shape[0],) + w.shape, np.float32)
    np.multiply(fleet.trace[:, None, :], w[None, :, :], out=out)
    return out
