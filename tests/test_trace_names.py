"""The control round's names in a profiler trace.

``window_step`` lowers each phase of the round inside a ``jax.named_scope``
(``fleet.serve``, ``fleet.allocate``, ``fleet.telemetry``), so the HLO of
every entry point that runs it carries the phase in each instruction's
``op_name``; ``FleetService.ingest`` marks each round on the host with
``fleet.fetch``, ``fleet.copy`` and ``fleet.dispatch`` spans.  A trace
reader charges device time and host time to these names, so they are a
contract: these tests pin them.  The scopes are metadata only; the bitwise
online == offline and golden tests pin that the numbers do not move.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.storage import (
    FleetConfig,
    FleetService,
    simulate_fleet,
    simulate_tenants,
)

PHASES = ("fleet.serve", "fleet.allocate", "fleet.telemetry")
SPANS = ("fleet.fetch", "fleet.copy", "fleet.dispatch")
O, J, WT, W = 4, 8, 10, 3
MEMBERS = ("adaptbf", "aimd", "nobw", "static", "static_wc")


def small_fleet():
    rng = np.random.default_rng(7)
    nodes = rng.integers(1, 16, (J,)).astype(np.float32)
    rates = rng.integers(0, 6, (W * WT, O, J)).astype(np.float32)
    volume = np.full((O, J), np.inf, np.float32)
    return nodes, rates, volume


def config(control, telemetry):
    kw = {"coded_policies": MEMBERS} if control == "coded" else {}
    return FleetConfig(control=control, telemetry=telemetry, **kw)


def hlo_text(lowered) -> str:
    return lowered.as_text(dialect="hlo", debug_info=True)


def phases_in(text: str) -> set:
    return set(re.findall(r'op_name="[^"]*?(fleet\.[a-z_]+)', text))


def assert_phases(text: str, telemetry: str):
    if telemetry == "streaming":
        assert phases_in(text) == set(PHASES)
        # the fold's lag histogram: off the TPU a scatter-add, lowered
        # inside the phase (under ``count_bins``'s platform branch)
        assert re.search(r'scatter\(.*op_name="([^"]*/)?fleet\.telemetry/'
                         r'(cond/branch_\d+_fun/)?scatter-add"', text)
    else:
        # the trajectory outputs are the window's arrays as they stand, and
        # an AdapTBF record is a leaf of its state: fleet.telemetry holds
        # operations only where a record is computed (a coded selection)
        assert {"fleet.serve", "fleet.allocate"} <= phases_in(text) \
            <= set(PHASES)


@pytest.mark.parametrize("telemetry", ["streaming", "trajectory"])
@pytest.mark.parametrize("control", ["adaptbf", "coded"])
def test_online_step_lowers_inside_the_phase_scopes(control, telemetry):
    nodes, rates, volume = small_fleet()
    svc = FleetService(config(control, telemetry), nodes, volume,
                       control_code=0 if control == "coded" else None)
    lowered = svc._step.lower(
        svc._nodes, svc._cap_tick, svc._backlog_cap, svc._control_code,
        svc.carry, jnp.asarray(rates[:WT]), None)
    assert_phases(hlo_text(lowered), telemetry)


@pytest.mark.parametrize("telemetry", ["streaming", "trajectory"])
def test_simulate_fleet_lowers_inside_the_phase_scopes(telemetry):
    nodes, rates, volume = small_fleet()
    lowered = simulate_fleet.lower(config("adaptbf", telemetry), nodes,
                                   rates, volume)
    assert_phases(hlo_text(lowered), telemetry)


def test_simulate_tenants_lowers_inside_the_phase_scopes():
    nodes, rates, volume = small_fleet()
    lowered = simulate_tenants.lower(
        config("coded", "streaming"), nodes, rates, volume,
        control_code=jnp.arange(len(MEMBERS), dtype=jnp.int32))
    assert_phases(hlo_text(lowered), "streaming")


def host_spans(trace_dir) -> list:
    """The ``fleet.*`` host spans of a trace, (start ns, end ns, name) in
    order of start."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    return sorted((e.start_ns, e.end_ns, e.name) for p in pd.planes
                  if p.name.startswith("/host:") for line in p.lines
                  for e in line.events if e.name.startswith("fleet."))


def profiled_rounds(tmp_path, fetches):
    """Run one ``ingest`` per fetch under the profiler (the step compiled
    before it starts) and return the host spans."""
    nodes, rates, volume = small_fleet()
    svc = FleetService(config("adaptbf", "streaming"), nodes, volume)
    svc.step(rates[:WT])
    jax.block_until_ready(svc.alloc)
    with jax.profiler.trace(str(tmp_path)):
        for fetch in fetches:
            svc.ingest(fetch, backoff_s=0.0)
        jax.block_until_ready(svc.alloc)
    return host_spans(tmp_path), svc


def test_ingest_rounds_are_fetch_copy_dispatch_in_order(tmp_path):
    _, rates, _ = small_fleet()
    fetches = [lambda w=w: rates[w * WT:(w + 1) * WT] for w in range(W)]
    spans, _ = profiled_rounds(tmp_path, fetches)
    assert [name for _, _, name in spans] == list(SPANS) * W
    # one after the other: each span ends before the next one starts
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert end <= start


def test_every_fetch_attempt_is_a_span(tmp_path):
    _, rates, _ = small_fleet()
    tries = iter([None, rates[:WT]])
    spans, svc = profiled_rounds(tmp_path, [lambda: next(tries)])
    assert svc.retry_count == 1
    assert [name for _, _, name in spans] == [
        "fleet.fetch", "fleet.fetch", "fleet.copy", "fleet.dispatch"]
