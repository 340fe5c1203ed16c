"""Compile every live Pallas kernel for a described TPU v5e, no chip needed.

Interpret mode (the rest of the suite) cannot see what Mosaic, the TPU's
kernel compiler, refuses: a ``dynamic_slice`` of a loaded value, a block
whose row extent is neither a multiple of 8 nor the whole array, more
scoped VMEM than the kernel asked for.  Each test here lowers one kernel
with ``interpret=False`` at the engine's documented peak shape
(O, J, W) = (256, 4096, 10) for one chip of a described ``v5e:2x2``
topology, compiles it, and checks that the program holds the kernel
(``tpu_custom_call``).  The streaming fold, which has a TPU-only way of
counting its histogram, is compiled the same way.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so a worker that is not
given this file must not touch it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policies import CodedPolicy, PolicyContext, get_policy
from repro.kernels.adaptbf_alloc.ops import fleet_alloc
from repro.kernels.fleet_window.ops import fleet_window_serve
from repro.kernels.window_mega import ops as mega_ops
from repro.storage import telemetry

O, J, W = 256, 4096, 10


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise keep its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one; keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _mega_text(one_chip, j, *, coded=False, faults=False) -> str:
    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    policy = (CodedPolicy(("adaptbf", "static", "nobw")) if coded
              else get_policy("adaptbf"))

    def round_(nodes, cap_tick, backlog, queue, vol, alloc, held, rates,
               code, telem, up):
        ctx = PolicyContext(nodes=nodes, cap_w=cap_tick * W,
                            control_code=code)
        return mega_ops.mega_window_round(
            policy, ctx, cap_tick, backlog, queue, vol, alloc, held,
            policy.init_state(ctx), rates, telem_ok=telem, up=up,
            interpret=False)

    oj = spec(O, j)
    return _compile_text(
        round_, oj, spec(O), oj, oj, oj, oj, (oj, oj, oj), spec(W, O, j),
        spec(dtype=jnp.int32) if coded else None,
        spec(O) if faults else None, spec(O) if faults else None)


def test_adaptbf_alloc_compiles_for_v5e(one_chip):
    oj = jax.ShapeDtypeStruct((O, J), jnp.float32, sharding=one_chip)
    cap = jax.ShapeDtypeStruct((O,), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda *a: fleet_alloc(*a, interpret=False),
                         oj, oj, oj, oj, oj, cap)
    assert "tpu_custom_call" in text


def test_fleet_window_compiles_for_v5e(one_chip):
    oj = jax.ShapeDtypeStruct((O, J), jnp.float32, sharding=one_chip)
    rates = jax.ShapeDtypeStruct((W, O, J), jnp.float32, sharding=one_chip)
    cap = jax.ShapeDtypeStruct((O,), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda q, v, b, r, bl, c: fleet_window_serve(q, v, b, r, bl, c,
                                                     interpret=False),
        oj, oj, oj, rates, oj, cap)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("variant", ["plain", "faults", "coded"])
def test_window_mega_compiles_for_v5e(one_chip, variant):
    text = _mega_text(one_chip, J, coded=variant == "coded",
                      faults=variant == "faults")
    assert "tpu_custom_call" in text


def test_window_mega_compiles_at_j8192_with_one_row_tile(one_chip):
    """J = 8192 used to shrink the block to 4 rows, which Mosaic refuses;
    the block now stays one 8-row tile under a raised VMEM limit."""
    from repro.kernels import dispatch
    assert dispatch.block_rows(O) == 8
    assert "tpu_custom_call" in _mega_text(one_chip, 8192)


def _unfused_shapes(text: str):
    """Result shapes of the instructions outside fusion bodies: the
    buffers the program writes to memory."""
    fused = set(re.findall(r"calls=(%[\w.-]+)", text))
    shapes, keep = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) .*\{$", line)
        if head:
            keep = head.group(1) not in fused
        elif keep and " = " in line:
            result = line.split(" = ", 1)[1]
            result = result[:re.search(r" [a-z][\w-]*\(", result).start()]
            shapes += re.findall(r"\w+\[[\d,]*\]", result)
    return shapes


@pytest.mark.parametrize("tenants", [None, 5])
def test_streaming_fold_counts_without_a_scatter_on_v5e(one_chip, tenants):
    """On a TPU the fold's backlog histogram is the one-hot contraction:
    no scatter, and its ``[O, J, 8]``/``[O, J, 16]`` one-hots (or an
    ``[O, J, NBINS]`` one) fused away, at the benchmark's (248, 4096),
    alone and under a vmap over five tenants."""
    o, j = 248, 4096
    lead = () if tenants is None else (tenants,)

    def spec(x):
        return jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                    sharding=one_chip)

    stats = jax.tree.map(
        spec, jax.eval_shape(lambda: telemetry.init_stats(o, j)))
    oj = spec(jax.ShapeDtypeStruct((o, j), jnp.float32))
    cap = spec(jax.ShapeDtypeStruct((o,), jnp.float32))
    fold = telemetry.update_stats
    if tenants is not None:
        fold = jax.vmap(fold)
    text = _compile_text(fold, stats, oj, oj, oj, cap)
    assert not re.search(r" scatter\(", text)
    shapes = _unfused_shapes(text)
    assert any(s.endswith(f"{o},{j}]") for s in shapes)  # it sees buffers
    one_hots = [s for s in shapes
                if re.search(rf"{o},{j},(8|16|{telemetry.NBINS})\]", s)]
    assert not one_hots
